"""The ``keyed_10m`` deployment and its cell ``keyed_10m.drain``: the files
load by name and say what ISSUE 27 fixed, and a twin of the cell cut to a
size a test can hold — the same files with fewer keys and events, the ring
still fifty times wider than the cells a stripe touches — runs through the
real engine on the CPU and comes out correct, and not correct under the
control."""

import json
import os

import pytest

from benchmark.harness import lastline, manifest, runner

CELL = "keyed_10m.drain"
SEED = 3_000_000_027  # above 2**31, as the driver's are


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_cell_and_configuration_load_by_name(cell):
    assert cell.name == CELL and cell.chips == 1
    assert cell.config["name"] == "keyed_10m"
    assert cell.traffic["config"] == "keyed_10m"
    assert cell.traffic["traffic"] == "drain" == cell.traffic["mode"]
    assert set(cell.end_to_end) == {"events_per_s", "setup_s"}
    assert {"stripe_flush_share.drain", "emit_d2h_wait_share.drain",
            "emit_finalize_share.drain", "d2h_bytes_per_window.drain",
            "h2d_bytes_per_event.drain", "device_step_ms.drain",
            "device_idle_share.drain"} <= set(cell.per_layer)


def test_configuration_states_the_deployment(cell):
    cfg = cell.config
    assert 0 < len(cfg["source"]) <= 200 and "\n" not in cfg["source"]
    assert cfg["keys"] == {"count": 10_000_000, "prefix": "key_"}
    assert cfg["partitions"] == 4 and cfg["records_per_batch"] == 512
    q = cfg["query"]
    assert q["length_ms"] == q["slide_ms"] == 10_000 and q["filter"] is None
    assert [a[1] for a in q["aggregates"]] == [
        "count", "sum", "min", "max", "avg"]
    # no strategy, flag or size chosen by hand
    assert cfg["engine"] == {
        "min_group_capacity": 10_000_000, "source_idle_timeout_ms": 1000}
    assert cfg["reduced"] and set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert "uniform" in cfg["assumed"]["key_names"]
    # the source is public and states this size; what BASELINE.json states
    # (100K keys) and what was set here are said to be so
    assert "tsbs" in cfg["source"] and "--scale=10000000" in cfg["source"]
    assert "100K" in cfg["source"] and "SOAK" not in cfg["source"]
    assert {"record_and_job", "arrivals", "group_capacity"} <= set(
        cfg["assumed"])
    # guarantees and precision are keyed_100k's, word for word
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "keyed_100k.json")) as f:
        small = json.load(f)
    assert cfg["guarantees"] == small["guarantees"]
    assert cfg["precision"] == small["precision"]


def test_traffic_is_keyed_100k_drains_feed(cell):
    with open(os.path.join(manifest.BENCH_DIR, "workloads",
                           "keyed_100k.drain.json")) as f:
        small = json.load(f)
    tr = cell.traffic
    for key in ("mode", "events_per_second", "chunk_ms", "lead_events",
                "ahead_chunks", "encoders", "warmup_timeout_s"):
        assert tr[key] == small[key], key
    assert tr["warmup_s"] == 10.0
    assert tr["check"]["block_ms"] == cell.config["query"]["length_ms"]


def test_the_four_new_readers_read_the_parents_counters(cell):
    obs = {"window_s": 40.0, "windows_delivered": 9, "counters": {
        "phase_ms_flush": 4000.0, "phase_ms_d2h_wait": 400.0,
        "phase_ms_finalize": 8000.0, "bytes_d2h": 2.0e9,
        "windows_emitted": 10}}
    assert cell.readers["stripe_flush_share.drain"](obs) == pytest.approx(10.0)
    assert cell.readers["emit_d2h_wait_share.drain"](obs) == pytest.approx(1.0)
    assert cell.readers["emit_finalize_share.drain"](obs) == pytest.approx(20.0)
    assert cell.readers["d2h_bytes_per_window.drain"](obs) == pytest.approx(2.0e8)
    # nothing to read: nothing reported, and nothing raised
    empty = {"window_s": 40.0, "windows_delivered": 0, "counters": {}}
    for name in ("stripe_flush_share.drain", "emit_d2h_wait_share.drain",
                 "emit_finalize_share.drain", "d2h_bytes_per_window.drain"):
        assert cell.readers[name](empty) is None


def test_parked_padding_reader():
    read = manifest.load_reader(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures", "benchmark",
        "metrics", "stripe_padding_factor.drain.py"))
    assert read({"counters": {"stripe_cells_active": 3_300_000,
                              "stripe_cells_shipped": 4_194_304}}
                ) == pytest.approx(1.271, abs=1e-3)
    # the parent commit has no such counters; a window without a flush has
    # no active cell
    assert read({"counters": {"bytes_h2d": 1}}) is None
    assert read({"counters": {"stripe_cells_active": 0,
                              "stripe_cells_shipped": 0}}) is None


@pytest.fixture(scope="module")
def twin(tmp_path_factory):
    """The cell's own files with the scale cut: 20,000 keys, 20,000 events
    an event-second (one event a key a window, as in the cell), a ring of
    640,000 groups — fifty times the ~12,600 cells a window's stripe
    touches."""
    root = tmp_path_factory.mktemp("keyed_10m_twin")
    os.makedirs(root / "benchmark" / "configs")
    os.makedirs(root / "benchmark" / "workloads")
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = f.read()
    (root / "BENCHMARK.json").write_text(bench)
    real = manifest.load_cell(CELL)
    cfg = json.loads(json.dumps(real.config))
    cfg["keys"]["count"] = 20_000
    cfg["engine"]["min_group_capacity"] = 640_000
    tr = dict(real.traffic, events_per_second=20_000, lead_events=40_000,
              warmup_s=0.3, warmup_timeout_s=120.0, encoders=1)
    (root / "benchmark" / "configs" / "keyed_10m.json").write_text(
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
    # about 63 % of the keys are live in a window: thousands of rows a block
    assert run["rows_compared"] > 10_000 and run["blocks_compared"] >= 1
    counters = run["counters"]
    # the ring never grew, and the stripe shipped what it touched
    assert counters["grow_events"] == 0
    assert 0 < counters["stripe_cells_active"] <= counters["stripe_cells_shipped"]
    assert counters["stripe_cells_shipped"] <= 2 * counters["stripe_cells_active"] + 1024 * counters["partial_merges"]
    control = next(m for m in logged if m.startswith("control"))
    assert "correct=False" in control
