"""The ``sliding_10m`` deployment and its cell ``sliding_10m.drain``: the
files load by name and say what ISSUE 33 fixed; the two per-layer readers
written for the cell read counters the parent commit already has — they
wait under the fixtures with their entries, because an accepted test pins
the manifest's per-layer metrics to the ten it has
(``test_benchmark_keyed_40m.py``; PERF.md, section 7) —; and a twin of
the cell cut to a size a test can hold — the same files with fewer keys and
events, the ring still so wide that a host stripe spans one slide unit —
runs through the real engine on the CPU and comes out correct, and not
correct under the control."""

import json
import os

import pytest

from benchmark.harness import lastline, manifest, runner

CELL = "sliding_10m.drain"
SEED = 3_300_000_033  # above 2**31, as the driver's are
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
with open(os.path.join(FIXTURES, "sliding_entries.json")) as _f:
    PARKED = json.load(_f)
NEW_READERS = ("merge_steps_per_window.drain", "device_busy_ns_per_event.drain")


def _parked(name):
    return manifest.load_reader(
        os.path.join(FIXTURES, "benchmark", "metrics", name + ".py"))


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_cell_and_configuration_load_by_name(cell):
    assert cell.name == CELL and cell.chips == 1
    assert cell.config["name"] == "sliding_10m"
    assert cell.traffic["config"] == "sliding_10m"
    assert cell.traffic["traffic"] == "drain" == cell.traffic["mode"]
    assert set(cell.end_to_end) == {"events_per_s", "setup_s"}
    # every list the keyed_10m cell is on: all ten per-layer metrics
    assert set(manifest.load_cell("keyed_10m.drain").per_layer) == set(
        cell.per_layer)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(cell.per_layer) == len(bench["per_layer"]) == 10
    # appended, not put in the middle
    assert bench["configs"][-1]["name"] == "sliding_10m"
    assert bench["workloads"][-1]["name"] == CELL
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert "workloads" not in m or m["workloads"][-1] == CELL or (
            CELL not in m["workloads"])


def test_the_two_parked_entries_keep_to_the_contract():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    taken = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert [m["name"] for m in PARKED] == list(NEW_READERS)
    for m in PARKED:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in taken and m["layer"] in layers
        assert m["workloads"] == [CELL] and m["moves"] == "events_per_s"
        assert m["better"] == "lower" and " " not in m["unit"]
        _parked(m["name"])  # the reader is there and loads


def test_configuration_states_the_deployment(cell):
    cfg = cell.config
    assert 0 < len(cfg["source"]) <= 200 and "\n" not in cfg["source"]
    # the job is the upstream sliding configuration, the population TSBS's
    assert "configs[1]" in cfg["source"] and "tsbs" in cfg["source"]
    assert "--scale=10000000" in cfg["source"]
    big, sliding = _config("keyed_10m"), _config("emit_sliding")
    for key in ("topic", "partitions", "records_per_batch", "keys", "record",
                "engine"):
        assert cfg[key] == big[key], key
    # no strategy, flag or size chosen by hand
    assert cfg["engine"] == {
        "min_group_capacity": 10_000_000, "source_idle_timeout_ms": 1000}
    q = cfg["query"]
    assert q == dict(sliding["query"], length_ms=10_000, slide_ms=2_000)
    assert q["length_ms"] == 5 * q["slide_ms"]
    assert q["filter"] == {"column": "avg", "gt": 45.0}
    assert cfg["guarantees"] == sliding["guarantees"]
    assert cfg["precision"] == sliding["precision"]
    assert cfg["reduced"] == ["events_per_key_per_window"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert {"record_and_job", "window", "arrivals", "group_capacity",
            "per_key_means"} <= set(cfg["assumed"])
    assert cfg["deployment"] and len(cfg["source_notes"]) >= 2
    assert "from memory" in cfg["source_notes"][0]


def test_traffic_is_keyed_10m_drains_feed(cell):
    with open(os.path.join(manifest.BENCH_DIR, "workloads",
                           "keyed_10m.drain.json")) as f:
        twin = json.load(f)
    tr = cell.traffic
    for key in twin:
        if key not in ("config", "check"):
            assert tr[key] == twin[key], key
    # a block holds two whole windows
    chk, q = tr["check"], cell.config["query"]
    assert chk == {"block_ms": 12_000, "max_blocks": 2, "every": 3}
    assert (chk["block_ms"] - q["length_ms"]) // q["slide_ms"] + 1 == 2


@pytest.mark.parametrize("name,obs,want", [
    ("merge_steps_per_window.drain",
     {"counters": {"device_steps": 96, "windows_emitted": 24}, "trace": None},
     4.0),
    ("device_busy_ns_per_event.drain",
     {"counters": {"rows_in": 60_000_000},
      "trace": {"busy_s": 18.0, "window_s": 40.0}},
     300.0),
])
def test_the_two_new_readers_read_the_parents_counters(name, obs, want):
    assert _parked(name)(dict(obs, window_s=40.0)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize("obs", [
    {"counters": {}, "trace": None},
    # a window without a close, or without a row, or a run without a trace
    {"counters": {"device_steps": 3, "windows_emitted": 0, "rows_in": 0},
     "trace": None},
], ids=["no_counters", "no_closes"])
def test_a_reader_with_nothing_to_read_reports_nothing(name, obs):
    assert _parked(name)(dict(obs, window_s=40.0)) is None


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    """The cell's own files with the scale cut: 20,000 keys, 2,000 events
    an event-second (one event a key a 10 s window, as in the cell), a ring
    of 300,000 groups — past half of the stripe's cell cap, so a stripe
    spans one slide unit as it does at 10M groups."""
    root = tmp_path_factory.mktemp("sliding_10m_twin")
    os.makedirs(root / "benchmark" / "configs")
    os.makedirs(root / "benchmark" / "workloads")
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = f.read()
    (root / "BENCHMARK.json").write_text(bench)
    real = manifest.load_cell(CELL)
    cfg = json.loads(json.dumps(real.config))
    cfg["keys"]["count"] = 20_000
    cfg["engine"]["min_group_capacity"] = 300_000
    tr = dict(real.traffic, events_per_second=2_000, lead_events=8_000,
              warmup_s=0.3, warmup_timeout_s=120.0, encoders=1)
    (root / "benchmark" / "configs" / "sliding_10m.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "workloads" / (CELL + ".json")).write_text(
        json.dumps(tr))
    return manifest.load_cell(
        CELL, str(root / "BENCHMARK.json"), str(root / "benchmark"))


def test_twin_rehearsal_is_correct_and_the_control_is_not(twin):
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
    # two windows a block, about three fifths of 63 % of the keys in each
    assert run["rows_compared"] > 10_000 and run["blocks_compared"] >= 1
    assert run["windows_delivered"] >= 2
    counters = run["counters"]
    assert counters["grow_events"] == 0
    # the operator's own counters of what the deployment works, over the
    # measured window: the reasons add up to the flushes, a merge folds
    # five ring rows, and the filter let fewer rows out than were emitted
    # (deltas between two reads from another thread: a flush under way at
    # either read is counted in part, so equal within a flush each)
    reasons = [counters[k] for k in counters if k.startswith("flush_reason_")]
    steps = counters["device_steps"]
    assert len(reasons) == 5 and steps >= 4
    assert abs(sum(reasons) - steps) <= 2
    assert abs(counters["merge_window_folds"] - 5 * steps) <= 10
    assert counters["merge_fold_entries"] == pytest.approx(
        5 * counters["stripe_cells_active"], rel=0.05)
    # 63 % of the keys live in a window, a window closing every fifth of
    # its rows: about three rows out a row in, before the filter
    # (a slow run closes few windows in its four seconds: wide bounds)
    assert 1.5 < counters["emit_rows"] / counters["rows_in"] < 5.0
    control = next(m for m in logged if m.startswith("control"))
    assert "correct=False" in control
