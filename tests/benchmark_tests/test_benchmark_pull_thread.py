"""The nine per-layer readers that name the rest of the pull thread and the
``-d2h`` worker: ``window.finalize``'s three children, the window operator
suspended at its yields, the collector's pauses, the pull thread's time
off the CPU, the source's own work and watermarks, and the worker's wait
for the programs ahead of its copy.

They are the manifest's, under ``benchmark/metrics/``, each listing every
cell, with a case file each under ``reader_cases/``.  Here: each reader's
arithmetic, a reader with nothing to read (the counters of a program
without their spans too) returns nothing, two of the spans name gaps, and
a tiny run through the real harness with a one-chip cell's entries reads
eight of the nine (no emission block crosses on a ``-d2h`` worker on the
CPU)."""

import json
import math
import os

import pytest

from benchmark.harness import host_spans, lastline, manifest, runner, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
CASES = os.path.join(HERE, "reader_cases")
XPLANE = os.path.join(FIXTURES, "small.xplane.pb")
SEED = 3_000_000_036

NAMES = [
    "emit_finalize_keys_share.drain", "emit_finalize_predicate_share.drain",
    "emit_finalize_columns_share.drain", "window_downstream_share.drain",
    "gc_pause_share.drain", "pull_thread_offcpu_share.drain",
    "source_watermark_share.drain", "source_self_share.drain",
    "d2h_fetch_ready_share.drain",
]
with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
ENTRIES = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
#: what the nine are read beside: every metric of a one-chip cell
ONE_CHIP = [m for m in BENCH["per_layer"] if "emit_sliding.drain" in m["workloads"]]


def reader(name):
    return manifest.load_reader(
        os.path.join(manifest.BENCH_DIR, "metrics", name + ".py"))


# -- the entries ----------------------------------------------------------------


def test_every_parked_entry_has_its_reader_and_none_is_in_the_manifest():
    """Admitted: each of the nine is a manifest entry listing every cell,
    with its reader under ``benchmark/metrics/``, and none waits under the
    fixtures any more."""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert [e["name"] for e in ENTRIES] == NAMES
    assert all(e["workloads"] == cells for e in ENTRIES)
    own = {f[:-3] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "metrics"))}
    assert set(NAMES) <= own
    assert not os.path.exists(os.path.join(FIXTURES, "pull_thread"))


# -- the readers ----------------------------------------------------------------


SHARE = {"window_s": 10.0}


def _case(name):
    with open(os.path.join(CASES, name + ".json")) as f:
        case = json.load(f)
    return case["obs"], case["want"]


READER_CASES = {name: _case(name) for name in NAMES}
#: the window operator's and the source's counters on the parent commit
PARENT = {
    "rows_in": 10, "dnz_op_batch_ms.window": 5.0, "phase_ms_finalize": 2.0,
    "phase_ms_d2h_wait": 1.0, "d2h_fetch_ms": 3.0, "queue_wait_ms": 0.5,
    "prefetch_read_ms": 4.0, "bytes_d2h": 100, "windows_emitted": 2,
}


def test_every_parked_reader_has_its_case():
    assert sorted(READER_CASES) == sorted(NAMES)


@pytest.mark.parametrize("name", sorted(READER_CASES))
def test_reader_arithmetic(name):
    obs, want = READER_CASES[name]
    assert reader(name)(obs) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READER_CASES))
def test_reader_with_nothing_or_the_parents_counters_returns_nothing(name):
    read = reader(name)
    empty = {"window_s": 1.0, "counters": {}, "feeder": {}, "trace": None,
             "windows_delivered": 0, "compiles": 0}
    assert read(empty) is None
    assert read({**empty, "counters": PARENT}) is None


def test_a_window_without_a_fetched_block_has_no_ready_share():
    read = reader("d2h_fetch_ready_share.drain")
    assert read({"counters": {"d2h_fetch_ms": 0.0, "d2h_ready_ms": 0.0}}) is None


def test_the_three_children_and_the_self_time_make_the_finalize_share():
    counters = {"phase_ms_finalize_keys": 1500.0, "phase_ms_finalize_predicate": 250.0,
                "phase_ms_finalize_columns": 400.0, "phase_ms_finalize_self": 50.0}
    counters["phase_ms_finalize"] = sum(counters.values())
    obs = {**SHARE, "counters": counters}
    whole = manifest.load_reader(os.path.join(
        manifest.BENCH_DIR, "metrics", "emit_finalize_share.drain.py"))(obs)
    parts = sum(reader(n)(obs) for n in NAMES if n.startswith("emit_finalize_"))
    assert whole == pytest.approx(parts + 0.5)


# -- the spans name gaps --------------------------------------------------------

MS = 1e6  # ns


def ev(line, name, a_ms, b_ms):
    return (line, name, a_ms * MS, b_ms * MS, {})


def test_downstream_and_a_collection_inside_it_name_gaps(monkeypatch):
    events = [
        # a batch, then the operator suspended while the consumer holds the
        # thread, with a collection in the middle of it
        ev("t#0", "window.process_batch", 0, 100),
        ev("t#0", "window.intern", 10, 60),
        ev("t#0", "window.downstream", 100, 190),
        ev("t#0", "window.gc", 120, 130),
        # a batch that a full collection holds nearly whole
        ev("t#0", "window.process_batch", 300, 400),
        ev("t#0", "window.gc", 305, 395),
        # the source's own work nested in its batch span, and a worker
        ev("t#0", "source.batch", 200, 210),
        ev("t#0", "source.watermark", 202, 204),
        ev("t#1", "prefetch.read", 0, 400),
    ]
    modules = [("jit_a(1)", 0 * MS, 5 * MS), ("jit_b(2)", 195 * MS, 1 * MS),
               ("jit_a(1)", 285 * MS, 5 * MS), ("jit_b(2)", 400 * MS, 1 * MS)]
    monkeypatch.setattr(host_spans, "host_events", lambda path: events)
    named, rows = host_spans.gaps_by_span("x", modules, k=2)
    assert [n for n, _s in named] == [
        "window.downstream before b", "window.gc before b"]
    assert dict(rows[0]["lead"]) == pytest.approx({
        "window.downstream": 100 * 80 / 190, "window.intern": 100 * 50 / 190,
        "window.process_batch": 100 * 45 / 190, "window.gc": 100 * 10 / 190,
        host_spans.NO_SPAN: 100 * 5 / 190})
    assert dict(rows[1]["lead"]) == pytest.approx({
        "window.gc": 100 * 90 / 110, "window.process_batch": 100 * 10 / 110,
        host_spans.NO_SPAN: 100 * 10 / 110})
    self_s = host_spans.self_times(events)
    assert self_s["source.batch"] == pytest.approx(0.008)
    assert self_s["window.downstream"] == pytest.approx(0.080)
    # every new span of the pull thread is one the harness names gaps by
    for name in ("window.downstream", "window.gc", "window.finalize_keys", "window.finalize_predicate",
                 "window.finalize_columns", "window.d2h_ready",
                 "source.batch", "source.watermark"):
        assert host_spans.ENGINE_SPAN.match(name), name


# -- a tiny run through the real harness ------------------------------------


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """``tiny_sliding.drain`` of the fixtures with a one-chip cell's entries
    of the real manifest, the nine among them, laid over its own."""
    with open(os.path.join(FIXTURES, "BENCHMARK.json")) as f:
        fx = json.load(f)
    listed = {m["name"] for m in ONE_CHIP}
    assert set(NAMES) <= listed
    fx["per_layer"] = [m for m in fx["per_layer"] if m["name"] not in listed]
    for e in ONE_CHIP:
        fx["per_layer"].append({**e, "workloads": ["tiny_sliding.drain"]})
    path = tmp_path_factory.mktemp("pull_thread") / "BENCHMARK.json"
    path.write_text(json.dumps(fx))
    return manifest.load_cell(
        "tiny_sliding.drain", str(path), os.path.join(FIXTURES, "benchmark"))


def test_a_tiny_traced_run_reads_the_nine_but_the_d2h_worker(tiny_cell):
    mp = pytest.MonkeyPatch()
    logged = []
    try:
        mp.setattr(runner, "start_trace", lambda d: None)
        mp.setattr(runner, "stop_trace", lambda: None)
        mp.setattr(trace_reduce, "find_xplane", lambda d: XPLANE)
        text = runner.run_cell(tiny_cell, SEED, 1.0, True, require_tpu=False,
                               log=logged.append)
    finally:
        mp.undo()
    run = next(json.loads(m) for m in logged if m.startswith('{"workload"'))
    line = json.loads(text)
    assert line["correct"] is True
    assert lastline.check_text(text, tiny_cell.per_layer, True) == []
    # no emission block crosses on a -d2h worker on the CPU (no emit lag)
    assert line["not_read"] == run["per_layer_not_read"] == [
        "d2h_fetch_ready_share.drain"]
    got = {n: line["metrics"][n]["value"] for n in NAMES if n in line["metrics"]}
    assert len(got) == 8
    assert all(math.isfinite(v) and v >= 0 for v in got.values()), got
    assert got["pull_thread_offcpu_share.drain"] <= 100.0
    # the fixture's window has an emission predicate (avg > 45)
    assert got["emit_finalize_predicate_share.drain"] > 0
    assert got["source_self_share.drain"] > 0
    assert got["window_downstream_share.drain"] > 0
    finalize = line["metrics"]["emit_finalize_share.drain"]["value"]
    parts = sum(got[n] for n in got if n.startswith("emit_finalize_"))
    assert parts <= finalize + 1e-9
    # the same readers over the run's record give the line's numbers
    obs = {"counters": run["counters"], "window_s": run["window_s"]}
    for name, value in got.items():
        assert reader(name)(obs) == pytest.approx(value), name
