"""The ``keyed_40m`` deployment and its cell ``keyed_40m.drain.4chip``: the
files load by name and say what ISSUE 31 fixed, the manifest keeps to the
limit on four-chip cells, the two readers parked under the fixtures read
the counters this PR adds (and nothing, without raising, where a program
lacks them — which is why they are parked: the harness refuses a traced line
that lacks a listed metric, and the parent commit would print such a line),
and a twin
of the cell cut to a size a test can hold — the same files with fewer keys
and events and a ring that has to grow — runs through the real engine on
four of the CPU's virtual devices, tumbling and sliding, and comes out
correct, and not correct under the control."""

import json
import os

import jax
import pytest

from benchmark.harness import lastline, manifest, runner

CELL = "keyed_40m.drain.4chip"
SEED = 3_000_000_031  # above 2**31, as the driver's are
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
with open(os.path.join(FIXTURES, "mesh_entries.json")) as _f:
    PARKED = json.load(_f)


def _parked(name):
    return manifest.load_reader(
        os.path.join(FIXTURES, "benchmark", "metrics", name + ".py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def _json(*path):
    with open(os.path.join(manifest.BENCH_DIR, *path)) as f:
        return json.load(f)


def test_cell_loads_with_four_chips_and_its_nine_readers(cell):
    assert cell.name == CELL and cell.chips == 4
    assert cell.config["name"] == "keyed_40m"
    assert cell.config["engine"]["mesh_devices"] == 4
    assert set(cell.end_to_end) == {"events_per_s", "setup_s"}
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # every per-layer metric the benchmark has lists the cell, but one:
    # ``d2h_bytes_per_window.drain`` finds nothing to read in a window
    # without a window close, and the parent commit, which ships rows on a
    # mesh (105K events/s on the chips), closes one every 95 s: its traced
    # run would print no line (PERF.md, section 7)
    assert set(cell.per_layer) == set(cell.readers) == {
        m["name"] for m in bench["per_layer"]
    } - {"d2h_bytes_per_window.drain"}
    assert len(cell.per_layer) == 9
    # the first four-chip cell, and inside the limit on them
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def test_the_two_parked_entries_keep_to_the_contract():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    taken = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert [(e["name"], e["unit"], e["layer"]) for e in PARKED] == [
        ("shard_cells_max_share.drain", "%", "device program"),
        ("stripe_copies_per_flush.drain", "copies", "H2D"),
    ]
    for e in PARKED:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert e["name"] not in taken and e["layer"] in layers
        assert (e["source"], e["moves"], e["better"], e["workloads"]) == (
            "program_counter", "events_per_s", "lower", [CELL])
        assert callable(_parked(e["name"]))
        assert not os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", e["name"] + ".py"))


def test_configuration_states_the_deployment(cell):
    cfg = cell.config
    assert 0 < len(cfg["source"]) <= 200 and "\n" not in cfg["source"]
    for said in ("tsbs", "cpu-only", "--scale=4000000", "--log-interval=10s"):
        assert said in cfg["source"], said
    assert cfg["keys"] == {"count": 40_000_000, "prefix": "key_"}
    assert cfg["partitions"] == 4 and cfg["records_per_batch"] == 512
    # no strategy, flag or size chosen by hand: the mesh and nothing else
    assert cfg["engine"] == {
        "mesh_devices": 4, "min_group_capacity": 40_000_000,
        "source_idle_timeout_ms": 1000}
    assert cfg["reduced"] == ["events_per_key_per_window"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert {"record_and_job", "arrivals", "group_capacity", "mesh"} <= set(
        cfg["assumed"])
    assert any("from memory" in note for note in cfg["source_notes"])
    # job, precision and guarantees are keyed_10m's, word for word
    small = _json("configs", "keyed_10m.json")
    for key in ("query", "guarantees", "precision", "topic"):
        assert cfg[key] == small[key], key


def test_traffic_is_keyed_10m_drains_feed_with_a_longer_warm_up(cell):
    small = _json("workloads", "keyed_10m.drain.json")
    for key in ("mode", "events_per_second", "chunk_ms", "lead_events",
                "ahead_chunks", "encoders", "check"):
        assert cell.traffic[key] == small[key], key
    assert cell.traffic["warmup_s"] == 40.0
    assert cell.traffic["check"] == {
        "block_ms": 10000, "max_blocks": 2, "every": 3}


def test_the_two_parked_readers():
    share = _parked("shard_cells_max_share.drain")
    copies = _parked("stripe_copies_per_flush.drain")
    counters = {
        "merge_cells_shard_0": 1_200_000, "merge_cells_shard_1": 1_000_000,
        "merge_cells_shard_2": 1_000_000, "merge_cells_shard_3": 800_000,
        "stripe_cells_active": 4_000_000,
        "bytes_h2d": 3.0e8, "stripe_bytes_packed": 1.0e8,
    }
    assert share({"counters": counters}) == pytest.approx(30.0)
    assert copies({"counters": counters}) == pytest.approx(3.0)
    # the parent commit has no such counters; a row-shipping backend packs
    # nothing; a window may see no flush: nothing reported, nothing raised
    for nothing in ({}, {"bytes_h2d": 5, "rows_in": 9},
                    {"bytes_h2d": 5, "stripe_bytes_packed": 0,
                     "merge_cells_shard_0": 0, "merge_cells_shard_1": 0}):
        assert share({"counters": nothing}) is None
        assert copies({"counters": nothing}) is None


def _twin(tmp_path_factory, slide_ms):
    """The cell's own files with the scale cut: 20,000 keys, 20,000 events
    an event-second, a ring of 4,096 groups that the keys outgrow — on
    four devices, 1,024 groups each to begin with."""
    root = tmp_path_factory.mktemp("keyed_40m_twin")
    os.makedirs(root / "benchmark" / "configs")
    os.makedirs(root / "benchmark" / "workloads")
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        (root / "BENCHMARK.json").write_text(f.read())
    real = manifest.load_cell(CELL)
    cfg = json.loads(json.dumps(real.config))
    cfg["keys"]["count"] = 20_000
    cfg["engine"]["min_group_capacity"] = 4096
    cfg["query"]["slide_ms"] = slide_ms
    tr = dict(real.traffic, events_per_second=20_000, lead_events=40_000,
              warmup_s=0.3, warmup_timeout_s=120.0, encoders=1)
    (root / "benchmark" / "configs" / "keyed_40m.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "workloads" / (CELL + ".json")).write_text(
        json.dumps(tr))
    return manifest.load_cell(
        CELL, str(root / "BENCHMARK.json"), str(root / "benchmark"))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four virtual devices")
@pytest.mark.parametrize("slide_ms", [10_000, 5_000],
                         ids=["tumbling", "sliding"])
def test_twin_rehearsal_on_four_devices(tmp_path_factory, slide_ms):
    twin = _twin(tmp_path_factory, slide_ms)
    assert twin.chips == 4
    logged = []
    text = runner.run_cell(twin, SEED, 4.0, False, require_tpu=False,
                           control=True, log=logged.append)
    assert lastline.check_text(text, twin.end_to_end, False) == []
    line = json.loads(text)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    c = line["compared"]
    for exact in ("missing_rows", "unexpected_rows", "count_mismatch",
                  "minmax_mismatch", "windows_undelivered", "late_rows",
                  "decode_fallback_rows"):
        assert c[exact]["value"] == 0, exact
    assert 0 < c["rel_err_max"]["value"] <= c["rel_err_max"]["limit"]
    run = json.loads(next(m for m in logged if m.startswith('{"workload"')))
    # the engine's own 'auto' took the mesh: nothing in the files names it
    assert run["strategy_resolved"] == "partial_merge/key_sharded"
    assert run["rows_compared"] > 5_000 and run["blocks_compared"] >= 1
    obs = {"window_s": run["window_s"], "counters": run["counters"]}
    cells = [obs["counters"][f"merge_cells_shard_{i}"] for i in range(4)]
    assert sum(cells) == obs["counters"]["stripe_cells_active"] > 0
    # group ids are dealt in order of first sight and a device owns a
    # block of them: a ring grown past its keys (doubling does that) fills
    # block 0 first and leaves the last ones short
    assert cells == sorted(cells, reverse=True) and cells[0] > cells[-1]
    assert _parked("shard_cells_max_share.drain")(obs) == pytest.approx(
        100.0 * cells[0] / sum(cells))
    # every packed byte went to one device, once (the two counters are
    # read while the pull thread may be inside a flush: a stripe is counted
    # as packed before its matrices are counted as sent)
    assert _parked("stripe_copies_per_flush.drain")(obs) == pytest.approx(
        1.0, abs=0.1)
    control = next(m for m in logged if m.startswith("control"))
    assert "correct=False" in control
