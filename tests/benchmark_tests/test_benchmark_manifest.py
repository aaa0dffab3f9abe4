"""BENCHMARK.json agrees with the files under its paths, keeps to the
contract's shapes and to the rules that let a cell, a configuration and a
per-layer metric enter by new files and entries alone, and the harness
finds a cell, a configuration and a per-layer metric that exist only under
the fixtures, by name.  The rule tests take ``tree`` (``conftest.py``): the
real manifest, and one with the fixtures' entering deployment appended."""

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
#: the per-layer metrics of the mesh: they list the four-chip cells alone
MESH_ONLY = ("shard_cells_max_share.drain", "stripe_copies_per_flush.drain")


@pytest.fixture
def bench(tree):
    return tree.bench


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert all(not w.startswith("/") and ".." not in w for w in bench["command"])


def test_every_configuration_has_its_file_and_a_cell(tree, bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"] == "benchmark/configs/" + c["name"] + ".json"
        with open(os.path.join(tree.root, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert held["guarantees"] and held["assumed"] and held["precision"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        # the deployment module it names lies under the benchmark's paths
        top = held["module"].split("/")[0]
        assert top in bench["paths"] and ".." not in held["module"]
        assert os.path.isfile(os.path.join(tree.root, held["module"]))
        files.add(c["file"])
    assert len(files) == len(bench["configs"])


def test_every_cell_has_its_files_and_loads(tree, bench):
    pairs = set()
    one_chip = tree.cells(chips=1)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
        cell = tree.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer and set(cell.readers) == set(cell.per_layer)
        # a cell's per-layer metrics are exactly those that list it, and
        # every metric that lists every one-chip cell lists every cell
        assert set(cell.per_layer) == {
            m["name"] for m in bench["per_layer"] if w["name"] in m["workloads"]}
        assert {m["name"] for m in bench["per_layer"]
                if set(one_chip) <= set(m["workloads"])} <= set(cell.per_layer)
        # of the three that read the trace one needs nothing else
        assert cell.from_trace == {"device_idle_share.drain"}
        assert cell.traffic["mode"] in ("drain", "paced")
    assert len(pairs) == len(bench["workloads"])
    # no more than half the cells, rounded down, on four chips (one always)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_each_metric_lists_every_cell_the_mesh_or_one_configuration(tree, bench):
    """The rules a cell or a metric enters by.  Each per-layer metric lists
    every cell, or the four-chip cells alone (the mesh's), or the cells of
    one configuration alone (a deployment's own); every list names cells
    that exist, in the manifest's order (entries are appended)."""
    cells, four = tree.cells(), tree.cells(chips=4)
    config_of = {w["name"]: w["config"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", cells)
        assert listed == [c for c in cells if c in listed], m["name"]
    for m in bench["per_layer"]:
        listed = m["workloads"]
        if m["name"] in MESH_ONLY:
            assert listed == four
        else:
            assert listed == cells or len({config_of[c] for c in listed}) == 1, (
                m["name"])


def test_metrics_keep_to_the_contract(tree, bench):
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
            tree.bench_dir, "metrics", m["name"] + ".py"))
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


def test_files_under_paths_are_named_from_name_characters(tree, bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(tree.root, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(root, f), tree.root)
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


SHARE = {"window_s": 10.0}  # a share reader's 100 x ms / window ms
READER_CASES = {
    "feeder_backlog_min_events.drain": ({"feeder": {"backlog_min": 1200}}, 1200),
    "generator_late_p95_ms.paced": ({"feeder": {"late_ms": [1.0] * 99 + [9.0]}}, 1.0),
    "window_input_wait_share.drain": (
        {**SHARE, "counters": {"dnz_op_input_wait_ms.window": 2500.0}}, 25.0),
    "window_op_busy_share.drain": (
        {**SHARE, "counters": {"dnz_op_batch_ms.window": 7000.0}}, 70.0),
    "h2d_bytes_per_event.drain": (
        {"counters": {"rows_in": 1000, "bytes_h2d": 3500}}, 3.5),
    "device_step_ms.drain": (
        {"counters": {"device_steps": 4}, "trace": {"busy_s": 0.02, "window_s": 5.0}}, 5.0),
    "d2h_bytes_per_window.paced": (
        {"windows_delivered": 10, "counters": {"bytes_d2h": 5000}}, 500.0),
    "compiles_in_window.paced": ({"compiles": 0}, 0),
    "device_idle_share.drain": ({"trace": {"busy_s": 0.5, "window_s": 50.0}}, 99.0),
    "device_idle_share.paced": ({"trace": {"busy_s": 5.0, "window_s": 50.0}}, 90.0),
    "stripe_flush_share.drain": ({**SHARE, "counters": {"phase_ms_flush": 1000.0}}, 10.0),
    "emit_d2h_wait_share.drain": (
        {**SHARE, "counters": {"phase_ms_d2h_wait": 100.0}}, 1.0),
    "emit_finalize_share.drain": (
        {**SHARE, "counters": {"phase_ms_finalize": 2000.0}}, 20.0),
    "d2h_bytes_per_window.drain": (
        {"counters": {"bytes_d2h": 2.0e9, "windows_emitted": 10}}, 2.0e8),
    "window_project_share.drain": (
        {**SHARE, "counters": {"phase_ms_project": 400.0}}, 4.0),
    "window_intern_share.drain": (
        {**SHARE, "counters": {"phase_ms_intern": 1800.0}}, 18.0),
    "window_reduce_share.drain": (
        {**SHARE, "counters": {"phase_ms_reduce": 800.0}}, 8.0),
    "window_other_share.drain": (
        {**SHARE, "counters": {
            "phase_ms_other": 100.0, "phase_ms_trigger": 50.0,
            "phase_ms_gather": 30.0, "phase_ms_acc_wait": 0.0,
            "phase_ms_update": 20.0}}, 2.0),
    "window_statewatch_share.drain": (
        {**SHARE, "counters": {"phase_ms_statewatch": 350.0}}, 3.5),
    "prefetch_read_load.drain": (
        {**SHARE, "counters": {"prefetch_read_ms": 8000.0}}, 80.0),
    "prefetch_blocked_load.drain": (
        {**SHARE, "counters": {"prefetch_blocked_ms": 32000.0}}, 320.0),
    "source_queue_wait_share.drain": (
        {**SHARE, "counters": {"queue_wait_ms": 5.0}}, 0.05),
    "stripe_padding_factor.drain": (
        {"counters": {"stripe_cells_active": 3_300_000,
                      "stripe_cells_shipped": 4_194_304}}, 4_194_304 / 3_300_000),
    "shard_cells_max_share.drain": (
        {"counters": {"merge_cells_shard_0": 1_200_000, "merge_cells_shard_1": 1_000_000,
                      "merge_cells_shard_2": 1_000_000, "merge_cells_shard_3": 800_000}},
        30.0),
    "stripe_copies_per_flush.drain": (
        {"counters": {"bytes_h2d": 3.0e8, "stripe_bytes_packed": 1.0e8}}, 3.0),
    "merge_steps_per_window.drain": (
        {"counters": {"device_steps": 96, "windows_emitted": 24}}, 4.0),
    "device_busy_ns_per_event.drain": (
        {"counters": {"rows_in": 60_000_000},
         "trace": {"busy_s": 18.0, "window_s": 40.0}}, 300.0),
    "consumer_hold_share.drain": ({**SHARE, "consumer_hold_ms": 2500.0}, 25.0),
}
MANIFEST_READERS = sorted(n for n in READER_CASES if n.endswith(".drain"))


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


def test_every_per_layer_metric_of_the_manifest_has_its_case(tree, bench):
    """A case here, or a case file of the metric's own under
    ``reader_cases/`` (how a later metric brings its case: a new file),
    for every per-layer metric and no other; the files' cases hold."""
    files = tree.cases()
    assert not set(files) & set(READER_CASES)
    assert sorted(MANIFEST_READERS + list(files)) == sorted(
        m["name"] for m in bench["per_layer"])
    for name, (obs, want) in files.items():
        read = manifest.load_reader(os.path.join(
            tree.bench_dir, "metrics", name + ".py"))
        assert read(obs) == pytest.approx(want), name


@pytest.mark.parametrize("name", MANIFEST_READERS + [
    "generator_late_p95_ms.paced", "d2h_bytes_per_window.paced",
])
def test_reader_with_nothing_to_read_returns_nothing(name):
    read = reader(name)
    empty = {"window_s": 1.0, "counters": {}, "feeder": {}, "trace": None,
             "windows_delivered": 0, "compiles": 0}
    assert read(empty) is None
