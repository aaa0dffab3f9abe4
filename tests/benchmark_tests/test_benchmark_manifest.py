"""BENCHMARK.json agrees with the files under its paths, keeps to the
contract's shapes, and the harness finds a cell, a configuration and a
per-layer metric that exist only under the fixtures, by name."""

import json
import os
import re

import pytest

from benchmark.harness import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert all(not w.startswith("/") and ".." not in w for w in bench["command"])


def test_every_configuration_has_its_file_and_a_cell(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert held["guarantees"] and held["assumed"] and held["precision"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        files.add(c["file"])
    assert len(files) == len(bench["configs"])


def test_every_cell_has_its_files_and_loads(bench):
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
        cell = manifest.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer and set(cell.readers) == set(cell.per_layer)
        assert cell.traffic["mode"] in ("drain", "paced")
    assert len(pairs) == len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics_keep_to_the_contract(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            # the cell reports the end-to-end metric this one moves
            assert "workloads" not in moved or w in moved["workloads"]
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", m["name"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


def test_layers_are_spelled_as_in_perf_md(bench):
    with open(os.path.join(manifest.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in bench["per_layer"]:
        assert "| " + m["layer"] + " |" in perf, m["layer"]


def test_files_under_paths_are_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(manifest.ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(root, f), manifest.ROOT)
                assert ok.match(rel), rel


def test_peaks_table_names_the_v5e():
    with open(os.path.join(manifest.BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_fixture_only_cell_config_and_metric_are_found_by_name():
    """Nothing under benchmark/ names these: they exist only in the
    fixtures' manifest and files."""
    cell = manifest.load_cell(
        "tiny_sliding.drain", os.path.join(FIXTURES, "BENCHMARK.json"),
        os.path.join(FIXTURES, "benchmark"))
    assert cell.config["name"] == "tiny_sliding"
    assert cell.traffic["events_per_second"] == 100000
    assert "rows_in_per_window.fixture" in cell.per_layer
    obs = {"windows_delivered": 4, "counters": {"rows_in": 1000}}
    assert cell.readers["rows_in_per_window.fixture"](obs) == 250.0
    # a reader that finds nothing to read returns nothing
    assert cell.readers["rows_in_per_window.fixture"](
        {"windows_delivered": 0, "counters": {}}) is None
    for root, _dirs, files in os.walk(manifest.BENCH_DIR):
        for f in files:
            assert "tiny_" not in f and "rows_in_per_window" not in f, f


def test_unknown_cell_and_disagreeing_file_are_errors(tmp_path):
    with pytest.raises(KeyError):
        manifest.load_cell("no_such.cell")
    fx = json.load(open(os.path.join(FIXTURES, "BENCHMARK.json")))
    fx["workloads"][0]["traffic"] = "other"
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(fx))
    with pytest.raises(ValueError):
        manifest.load_cell("tiny_sliding.drain", str(p),
                           os.path.join(FIXTURES, "benchmark"))


READER_CASES = {
    "feeder_backlog_min_events.drain": ({"feeder": {"backlog_min": 1200}}, 1200),
    "generator_late_p95_ms.paced": ({"feeder": {"late_ms": [1.0] * 99 + [9.0]}}, 1.0),
    "window_input_wait_share.drain": (
        {"window_s": 10.0, "counters": {"dnz_op_input_wait_ms.window": 2500.0}}, 25.0),
    "window_op_busy_share.drain": (
        {"window_s": 10.0, "counters": {"dnz_op_batch_ms.window": 7000.0}}, 70.0),
    "h2d_bytes_per_event.drain": (
        {"counters": {"rows_in": 1000, "bytes_h2d": 3500}}, 3.5),
    "device_step_ms.drain": (
        {"counters": {"device_steps": 4}, "trace": {"busy_s": 0.02, "window_s": 5.0}}, 5.0),
    "d2h_bytes_per_window.paced": (
        {"windows_delivered": 10, "counters": {"bytes_d2h": 5000}}, 500.0),
    "compiles_in_window.paced": ({"compiles": 0}, 0),
    "device_idle_share.drain": ({"trace": {"busy_s": 0.5, "window_s": 50.0}}, 99.0),
    "device_idle_share.paced": ({"trace": {"busy_s": 5.0, "window_s": 50.0}}, 90.0),
}


def reader(name):
    """A reader of the benchmark, or one of the paced readers that wait
    under the fixtures until a paced cell is admitted (PERF.md, section 7)."""
    own = os.path.join(manifest.BENCH_DIR, "metrics", name + ".py")
    return manifest.load_reader(own if os.path.exists(own) else os.path.join(
        FIXTURES, "benchmark", "metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(READER_CASES))
def test_reader_arithmetic(name):
    obs, want = READER_CASES[name]
    assert reader(name)(obs) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "device_step_ms.drain", "device_idle_share.drain", "h2d_bytes_per_event.drain",
    "generator_late_p95_ms.paced", "d2h_bytes_per_window.paced",
])
def test_reader_with_nothing_to_read_returns_nothing(name):
    read = reader(name)
    empty = {"window_s": 1.0, "counters": {}, "feeder": {}, "trace": None,
             "windows_delivered": 0, "compiles": 0}
    assert read(empty) is None
