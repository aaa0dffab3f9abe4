"""Host spans on the profiler's clock (``benchmark/harness/host_spans.py``)
and the eleven per-phase readers that wait under the fixtures: the
arithmetic on handmade intervals, the recorded chip trace, and a tiny CPU
run through the real harness with the readers listed in a manifest."""

import json
import math
import os

import pytest

from benchmark.harness import (
    host_spans,
    lastline,
    manifest,
    runner,
    trace_reduce,
)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
XPLANE = os.path.join(FIXTURES, "small.xplane.pb")
SEED = 3_000_000_023

with open(os.path.join(FIXTURES, "phase_share_entries.json")) as _f:
    ENTRIES = json.load(_f)
NAMES = [e["name"] for e in ENTRIES]
MS = 1e6  # ns


def ev(line, name, a_ms, b_ms, **stats):
    return (line, name, a_ms * MS, b_ms * MS, stats)


HANDMADE = [
    # the pull thread: one batch with nested phases, then a hint
    ev("t#0", "window.process_batch", 0, 100),
    ev("t#0", "window.project", 10, 60),
    ev("t#0", "window.intern", 20, 40),
    ev("t#0", "window.reduce", 60, 90),
    ev("t#0", "window.hint", 150, 200),
    ev("t#0", "window.flush", 160, 200),
    # a prefetch worker
    ev("t#1", "prefetch.read", 0, 50),
    ev("t#1", "prefetch.blocked", 50, 250),
]


def test_self_times_subtract_the_children_per_line():
    got = host_spans.self_times(HANDMADE)
    assert got == pytest.approx({
        "window.process_batch": 0.020, "window.project": 0.030,
        "window.intern": 0.020, "window.reduce": 0.030,
        "window.hint": 0.010, "window.flush": 0.040,
        "prefetch.read": 0.050, "prefetch.blocked": 0.200,
    })
    # exclusive times add up to what the outermost spans cover
    assert sum(v for k, v in got.items() if k.startswith("window")) == (
        pytest.approx(0.150))


def test_attribute_gaps_on_handmade_intervals():
    modules = [
        ("jit_merge_partials(1)", 0 * MS, 5 * MS),
        ("jit__finals_and_reset(2)", 205 * MS, 1 * MS),   # gap of 200 ms
        ("jit_merge_partials(1)", 250 * MS, 5 * MS),       # gap of 44 ms
    ]
    gaps = host_spans.attribute_gaps(modules, HANDMADE, k=10)
    assert [g["before"] for g in gaps] == [
        "jit__finals_and_reset", "jit_merge_partials"]
    first = gaps[0]
    assert first["gap_s"] == pytest.approx(0.200)
    assert first["at_s"] == pytest.approx(0.005)
    lead = dict(first["lead"])
    # the gap is [5, 205] ms; the lead line is the one with the most names
    assert lead == pytest.approx({
        "window.process_batch": 100 * 15 / 200,   # 5..10 and 90..100
        "window.project": 100 * 30 / 200, "window.intern": 100 * 20 / 200,
        "window.reduce": 100 * 30 / 200, "window.hint": 100 * 10 / 200,
        "window.flush": 100 * 40 / 200,
        host_spans.NO_SPAN: 100 * 55 / 200,               # 100..150, 200..205
    })
    assert sum(lead.values()) == pytest.approx(100.0)
    assert first["lead"][-1][0] == host_spans.NO_SPAN
    assert dict(first["others"]) == pytest.approx({
        "prefetch.read": 100 * 45 / 200, "prefetch.blocked": 100 * 155 / 200})
    # nothing of the lead line covers the second gap
    assert dict(gaps[1]["lead"]) == pytest.approx({host_spans.NO_SPAN: 100.0})
    assert host_spans.attribute_gaps(modules, HANDMADE, k=1) == gaps[:1]
    assert host_spans.attribute_gaps([], [], k=10) == []


def test_attribute_gaps_on_the_recorded_chip_trace():
    """``record_trace_fixture`` wrapped each step in a ``bench_step``
    annotation and slept 50 ms between steps: the annotations cover the
    steps, the sleeps read ``(no span)``."""
    events = host_spans.host_events(XPLANE, match=lambda n: n == "bench_step")
    assert len(events) == 6 and {e[4]["i"] for e in events} == set(range(6))
    assert len({e[0] for e in events}) == 1  # one thread, one line
    assert host_spans.host_events(XPLANE) == []  # no engine span in there
    modules = trace_reduce.device_lines(XPLANE)[0][trace_reduce.MODULES_LINE]
    gaps = host_spans.attribute_gaps(modules, events, k=10)
    sleeps = [g for g in gaps if g["gap_s"] > 0.045]
    assert len(sleeps) == 5
    for g in sleeps:
        lead = dict(g["lead"])
        assert lead[host_spans.NO_SPAN] > 90.0
        assert 0.0 < lead["bench_step"] < 10.0
    # inside the first step (57 ms long: it compiled), between its two
    # programs, the annotation covers the gap.  The later steps' programs
    # read 0.7 ms EARLIER than the 1 ms annotations that dispatched them:
    # host and device clocks of one trace agree to about a millisecond
    inside = [g for g in gaps if 0.0003 < g["gap_s"] < 0.005]
    assert len(inside) == 1
    assert dict(inside[0]["lead"])["bench_step"] == pytest.approx(100.0)
    # self time of a lone annotation is its duration
    total = sum(e[3] - e[2] for e in events) / 1e9
    assert host_spans.self_times(events)["bench_step"] == pytest.approx(total)


# -- the readers, through the harness ------------------------------------------


def test_the_parked_entries_keep_to_the_contract():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(NAMES) == set(host_spans.PHASE_SHARES) and len(NAMES) == 11
    taken = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for e in ENTRIES:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert e["name"] not in taken and set(e["workloads"]) <= cells
        assert (e["source"], e["moves"], e["better"], e["unit"]) == (
            "program_span", "events_per_s", "lower", "%")
        assert os.path.exists(os.path.join(
            FIXTURES, "benchmark", "metrics", e["name"] + ".py"))


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """``tiny_sliding.drain`` of the fixtures with the eleven entries
    appended to its manifest, as a ``benchmark`` PR will append them to the
    real one."""
    with open(os.path.join(FIXTURES, "BENCHMARK.json")) as f:
        fx = json.load(f)
    for e in ENTRIES:
        fx["per_layer"].append({**e, "workloads": ["tiny_sliding.drain"]})
    path = tmp_path_factory.mktemp("phase_shares") / "BENCHMARK.json"
    path.write_text(json.dumps(fx))
    return manifest.load_cell(
        "tiny_sliding.drain", str(path), os.path.join(FIXTURES, "benchmark"))


@pytest.fixture(scope="module")
def traced_line(tiny_cell):
    """One traced run (the trace itself is the recorded fixture, as in
    ``test_benchmark_rehearsal``) and what it logged."""
    logged = []
    mp = pytest.MonkeyPatch()
    mp.setattr(runner, "start_trace", lambda d: None)
    mp.setattr(runner, "stop_trace", lambda: None)
    mp.setattr(trace_reduce, "find_xplane", lambda d: XPLANE)
    try:
        text = runner.run_cell(tiny_cell, SEED, 1.0, True, require_tpu=False,
                               log=logged.append)
    finally:
        mp.undo()
    run = next(json.loads(m) for m in logged if m.startswith('{"workload"'))
    return text, run


def test_a_traced_run_prints_the_eleven_beside_the_old_ones(tiny_cell, traced_line):
    text, _run = traced_line
    assert set(NAMES) <= set(tiny_cell.per_layer)
    assert lastline.check_text(text, tiny_cell.per_layer, True) == []
    line = json.loads(text)
    assert line["correct"] is True
    assert set(tiny_cell.per_layer) <= set(line["metrics"])


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_tiny_run_and_on_nothing(name, tiny_cell, traced_line):
    _text, run = traced_line
    obs = {"counters": run["counters"], "window_s": run["window_s"]}
    value = tiny_cell.readers[name](obs)
    assert value is not None and math.isfinite(value) and value >= 0
    assert value == run["metrics"][name]
    empty = {"window_s": 1.0, "counters": {}, "feeder": {}, "trace": None,
             "windows_delivered": 0, "compiles": 0}
    assert tiny_cell.readers[name](empty) is None
    # the parent of this change has the operator's old counters and none of
    # the new ones: nothing to read there either
    old = {"window_s": 1.0, "counters": {
        "rows_in": 10, "dnz_op_batch_ms.window": 5.0, "bytes_h2d": 3}}
    assert tiny_cell.readers[name](old) is None


def test_the_tiny_run_fills_every_counter_the_shares_read(traced_line):
    """The readers are Kafka ones here, so fetch and decode count too.  The
    two identities (window shares = busy + hint path; read + blocked = 100 x
    partitions) are checked where they can hold: on a whole run in
    ``tests/test_phase_spans.py`` and on the chip's 40 s windows (PERF.md) —
    a one-second window read mid-flight is off by a batch at each edge."""
    _text, run = traced_line
    c = run["counters"]
    for key in ("phase_ms_project", "phase_ms_intern", "phase_ms_statewatch",
                "phase_ms_reduce", "phase_ms_other", "prefetch_read_ms",
                "prefetch_blocked_ms", "kafka_fetch_ms", "kafka_decode_ms"):
        assert c[key] > 0, key
    shares = host_spans.phase_shares(
        {"counters": c, "window_s": run["window_s"]})
    assert set(shares) == set(NAMES) and None not in shares.values()


def test_every_reader_of_the_real_manifest_reads_the_tiny_run(traced_line):
    """The six readers ``emit_sliding.drain`` lists today, on the same run."""
    _text, run = traced_line
    cell = manifest.load_cell("emit_sliding.drain")
    obs = {"counters": run["counters"], "window_s": run["window_s"],
           "feeder": {"backlog_min": run["backlog_min"]},
           "trace": {"busy_s": 0.5, "window_s": run["window_s"]},
           "windows_delivered": run["windows_delivered"], "compiles": 0}
    for name, read in cell.readers.items():
        value = read(obs)
        assert value is not None and math.isfinite(value) and value >= 0, name


def test_a_line_that_lacks_a_listed_metric_is_refused():
    """Why the eleven entries are not in ``BENCHMARK.json`` yet: laid over
    the parent commit, whose program has none of the counters, every reader
    returns nothing, and the harness refuses a traced line that lacks a
    metric its cell lists (``runner.run_cell``: ``wanted = cell.per_layer``)."""
    wanted = {"window_intern_share.drain": "%"}
    with pytest.raises(lastline.Malformed, match="is missing"):
        lastline.build(
            correct=True, attempted=1, failed=0, metrics={},
            device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                    "memory_peak_bytes": 1, "busy_s": 0.1, "window_s": 1.0},
            wanted=wanted, traced=True)
