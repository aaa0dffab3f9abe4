"""Host spans on the profiler's clock (``benchmark/harness/host_spans.py``)
and the per-layer readers of the manifest: the arithmetic on handmade
intervals, the recorded chip trace, and a tiny CPU run through the real
harness with every reader a one-chip cell lists — a gap named by the span
that held the pull thread, an operation by its program, a reader that finds
nothing left out of the line and one that reads the trace held to."""

import dataclasses
import io
import json
import math
import os
import re

import pytest

from benchmark.harness import (
    host_spans,
    lastline,
    manifest,
    runner,
    trace_reduce,
)
from benchmark.tools import check_lastline

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
XPLANE = os.path.join(FIXTURES, "small.xplane.pb")
SEED = 3_000_000_023

with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
#: what a one-chip cell lists
ENTRIES = [m for m in BENCH["per_layer"] if "emit_sliding.drain" in m["workloads"]]
LISTED = [e["name"] for e in ENTRIES]
#: what reads nothing on the CPU: no emission block crosses on the ``-d2h``
#: worker where the emit lag is 0
NO_D2H_WORKER = ["d2h_fetch_ready_share.drain"]
#: the share readers that were parked under these names (PR 25): five of the
#: eight window shares and the three of the sources
NAMES = [
    "prefetch_blocked_load.drain", "prefetch_read_load.drain",
    "source_queue_wait_share.drain", "window_intern_share.drain",
    "window_other_share.drain", "window_project_share.drain",
    "window_reduce_share.drain", "window_statewatch_share.drain",
]
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
        "_finals_and_reset", "merge_partials"]
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


def test_every_entry_has_its_reader_and_every_reader_its_entry(tree):
    bench = tree.bench
    listed = {m["name"] for m in bench["per_layer"]}
    assert len(listed) == len(bench["per_layer"])
    held = {f[:-3] for f in os.listdir(os.path.join(tree.bench_dir, "metrics"))
            if f.endswith(".py")}
    assert held == listed
    # nothing of the manifest waits under the fixtures any more: what is left
    # there is the paced readers (no paced cell yet) and the fixture-only one
    waiting = {f[:-3] for f in os.listdir(
        os.path.join(FIXTURES, "benchmark", "metrics")) if f.endswith(".py")}
    assert not waiting & listed
    assert {n.rsplit(".", 1)[1] for n in waiting} == {"paced", "fixture"}
    for gone in ("phase_share_entries.json", "mesh_entries.json",
                 "sliding_entries.json"):
        assert not os.path.exists(os.path.join(FIXTURES, gone))
    # the share readers: spans of the program, in percent of one thread; the
    # three that were parked twice are one entry each, under PR 27's names;
    # each lists every cell
    assert set(NAMES) <= listed and len(NAMES) == 8
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES + ["stripe_flush_share.drain", "emit_d2h_wait_share.drain",
                         "emit_finalize_share.drain"]:
        m = by_name[name]
        assert (m["source"], m["moves"], m["better"], m["unit"]) == (
            "program_span", "events_per_s", "lower", "%")
        assert m["workloads"] == tree.cells()
    for merged in ("window_flush_share.drain", "window_d2h_wait_share.drain",
                   "window_finalize_share.drain"):
        assert merged not in listed and merged not in held
    hold = by_name["consumer_hold_share.drain"]
    assert (hold["source"], hold["layer"]) == (
        "host_clock", "consumer (the harness's)")


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """``tiny_sliding.drain`` of the fixtures with the entries of a one-chip
    cell of the real manifest laid over its own: the readers are the real
    ones, found under ``benchmark/metrics`` by name."""
    with open(os.path.join(FIXTURES, "BENCHMARK.json")) as f:
        fx = json.load(f)
    fx["per_layer"] = [m for m in fx["per_layer"] if m["name"] not in LISTED]
    for e in ENTRIES:
        fx["per_layer"].append({**e, "workloads": ["tiny_sliding.drain"]})
    path = tmp_path_factory.mktemp("per_layer") / "BENCHMARK.json"
    path.write_text(json.dumps(fx))
    return manifest.load_cell(
        "tiny_sliding.drain", str(path), os.path.join(FIXTURES, "benchmark"))


def traced_run(cell, mp):
    """One traced run (the trace itself is the recorded fixture, as in
    ``test_benchmark_rehearsal``; its host plane holds ``bench_step``
    annotations where a run on the chip holds the engine's spans) and what
    it logged."""
    logged = []
    mp.setattr(runner, "start_trace", lambda d: None)
    mp.setattr(runner, "stop_trace", lambda: None)
    mp.setattr(trace_reduce, "find_xplane", lambda d: XPLANE)
    read = host_spans.host_events
    mp.setattr(host_spans, "host_events",
               lambda path: read(path, match=lambda n: n == "bench_step"))
    text = runner.run_cell(cell, SEED, 1.0, True, require_tpu=False,
                           log=logged.append)
    run = next(json.loads(m) for m in logged if m.startswith('{"workload"'))
    return text, run


@pytest.fixture(scope="module")
def traced_line(tiny_cell):
    mp = pytest.MonkeyPatch()
    try:
        return traced_run(tiny_cell, mp)
    finally:
        mp.undo()


def test_a_traced_run_prints_every_listed_metric(tiny_cell, traced_line):
    text, run = traced_line
    assert set(LISTED) <= set(tiny_cell.per_layer)
    assert lastline.check_text(text, tiny_cell.per_layer, True) == []
    line = json.loads(text)
    assert line["correct"] is True
    assert set(tiny_cell.per_layer) - set(NO_D2H_WORKER) <= set(line["metrics"])
    assert run["per_layer_not_read"] == line["not_read"] == NO_D2H_WORKER
    # of the three that read the trace, one needs nothing else
    assert tiny_cell.from_trace == {"device_idle_share.drain"}


def test_gaps_are_named_by_span_and_operations_by_program(traced_line):
    text, run = traced_line
    breakdown = json.loads(text)["breakdown"]
    gaps, rows = breakdown["idle_gaps"], run["idle_gaps_by_span"]
    assert len(gaps) == len(rows) == 10
    shape = re.compile(r"^(\(no span\)|[\w.]+) before (step|add)$")
    for (name, seconds), row in zip(gaps, rows):
        assert shape.match(name), name
        assert seconds == row["gap_s"] > 0
        lead = max(row["lead"], key=lambda r: r[1])[0]
        assert name == f"{lead} before {row['before']}"
        assert sum(v for _n, v in row["lead"]) == pytest.approx(100.0)
    # the five sleeps between steps read (no span), the gap inside the first
    # step the annotation that covers it
    assert [g[0] for g in gaps[:5]] == ["(no span) before add"] * 5
    assert "bench_step before step" in {g[0] for g in gaps}
    # a program is spelt one way in the whole breakdown
    programs = {name.split("/")[0] for name, _s in breakdown["device_ops"]}
    assert {g[0].rsplit(" before ", 1)[1] for g in gaps} <= programs
    for name, seconds in breakdown["device_ops"]:
        assert re.match(r"^(step|add)/[\w.\-]+$", name) and seconds > 0, name
    took = run["trace_reduce_s"]
    assert took["device"] >= 0 and took["host_spans"] >= 0


def test_gaps_keep_their_old_names_where_the_host_plane_has_no_engine_span(
        tiny_cell, monkeypatch):
    """The recorded trace holds no span of the engine (no ``window.*``)."""
    monkeypatch.setattr(runner, "start_trace", lambda d: None)
    monkeypatch.setattr(runner, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: XPLANE)
    logged = []
    text = runner.run_cell(tiny_cell, SEED, 0.6, True, require_tpu=False,
                           log=logged.append)
    run = next(json.loads(m) for m in logged if m.startswith('{"workload"'))
    assert run["idle_gaps_by_span"] == []
    gaps = json.loads(text)["breakdown"]["idle_gaps"]
    assert gaps and all(g[0] in ("before step", "before add") for g in gaps)


def test_gaps_by_span_names_a_gap_by_what_held_most_of_it(monkeypatch):
    modules = [("jit_a(1)", 0 * MS, 5 * MS), ("jit_b(2)", 205 * MS, 1 * MS),
               ("jit_a(1)", 250 * MS, 5 * MS)]
    # every engine span of the trace is read: what lies outside the gaps too
    events = HANDMADE + [ev("t#0", "window.emit", 300, 310)]
    monkeypatch.setattr(host_spans, "host_events", lambda path: events)
    named, rows = host_spans.gaps_by_span("x", modules, k=1)
    # no span held as much of the gap as the 55 ms outside every span did
    assert named == [["(no span) before b", pytest.approx(0.200)]]
    assert rows == host_spans.attribute_gaps(modules, events, k=1)
    assert dict(rows[0]["lead"]) == pytest.approx({
        host_spans.NO_SPAN: 27.5, "window.flush": 20.0, "window.project": 15.0,
        "window.reduce": 15.0, "window.intern": 10.0,
        "window.process_batch": 7.5, "window.hint": 5.0})
    named, _rows = host_spans.gaps_by_span("x", modules)
    assert [n for n, _s in named] == ["(no span) before b", "(no span) before a"]
    # a host plane without a span of the engine: the caller keeps the old names
    monkeypatch.setattr(host_spans, "host_events", lambda path: [])
    assert host_spans.gaps_by_span("x", modules) is None


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
    # a commit before the spans has the operator's old counters and none of
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
    for name in NAMES:
        assert run["metrics"][name] >= 0, name
    # the consumer's hold is the harness's own clock, beside the counters
    assert 0 < run["consumer_hold_ms"] < run["window_s"] * 1000.0
    assert run["metrics"]["consumer_hold_share.drain"] == pytest.approx(
        100.0 * run["consumer_hold_ms"] / (run["window_s"] * 1000.0))


def test_every_reader_of_the_real_manifest_reads_the_tiny_run(traced_line):
    """The readers ``emit_sliding.drain`` lists, on the same run: every one
    but the ``-d2h`` worker's reads a number."""
    _text, run = traced_line
    cell = manifest.load_cell("emit_sliding.drain")
    assert set(cell.readers) == set(LISTED) == {
        m["name"] for m in BENCH["per_layer"]
        if cell.name in m["workloads"]}
    obs = {"counters": run["counters"], "window_s": run["window_s"],
           "feeder": {"backlog_min": run["backlog_min"]},
           "trace": {"busy_s": 0.5, "window_s": run["window_s"]},
           "windows_delivered": run["windows_delivered"], "compiles": 0,
           "consumer_hold_ms": run["consumer_hold_ms"]}
    for name, read in cell.readers.items():
        value = read(obs)
        if name in NO_D2H_WORKER:
            assert value is None, name
            continue
        assert value is not None and math.isfinite(value) and value >= 0, name


@pytest.mark.parametrize("counter,left_out", [
    ("phase_ms_intern", ["window_intern_share.drain"]),
    # two of the three that read the trace divide it by a counter: without
    # the counter they go the same way, the trace being sound
    ("rows_in", ["rows_in_per_window.fixture", "h2d_bytes_per_event.drain",
                 "device_busy_ns_per_event.drain"]),
    ("device_steps", ["device_step_ms.drain", "merge_steps_per_window.drain"]),
])
def test_a_reader_that_finds_no_counter_leaves_its_metric_out(
        counter, left_out, tiny_cell, monkeypatch, capsys):
    """Laid over a commit whose program lacks a counter, the reader returns
    nothing: the traced run still prints a line, without that metric, and
    names it in the line and in the record."""
    real = runner.engine_counters

    def without(ctx):
        out = real(ctx)
        out.pop(counter)
        return out

    monkeypatch.setattr(runner, "engine_counters", without)
    left_out = left_out + NO_D2H_WORKER
    text, run = traced_run(tiny_cell, monkeypatch)
    line = json.loads(text)
    assert run["per_layer_not_read"] == line["not_read"] == left_out
    assert not set(left_out) & set(line["metrics"])
    assert set(tiny_cell.per_layer) - set(left_out) <= set(line["metrics"])
    # the tool that reads a line as the driver does goes by the same rule
    assert lastline.check_text(
        text, tiny_cell.per_layer, True, tiny_cell.from_trace) == []
    monkeypatch.setattr(manifest, "load_cell", lambda name: tiny_cell)
    monkeypatch.setattr("sys.stdin", io.StringIO("noise\n" + text + "\n"))
    assert check_lastline.main(["--workload", tiny_cell.name, "--trace", "1"]) == 0
    assert capsys.readouterr().out.splitlines()[-1] == (
        f"lastline ok, not read: {left_out}")
    # and refuses the same line once it no longer says what it left out
    del line["not_read"]
    assert lastline.check_text(
        json.dumps(line), tiny_cell.per_layer, True, tiny_cell.from_trace
    ) == [f"metric {n!r} is missing" for n in left_out]
    monkeypatch.setattr("sys.stdin", io.StringIO(json.dumps(line) + "\n"))
    assert check_lastline.main(["--workload", tiny_cell.name, "--trace", "1"]) == 1


def test_a_traced_line_without_a_trace_metric_is_still_refused(
        tiny_cell, monkeypatch):
    """``device_idle_share.drain`` comes off the trace: where its reader
    finds nothing the trace is broken, and no line is printed."""
    broken = dataclasses.replace(tiny_cell, readers={
        **tiny_cell.readers, "device_idle_share.drain": lambda obs: None})
    with pytest.raises(
            lastline.Malformed, match="'device_idle_share.drain' is read off "
            "the trace alone and may not be left out"):
        traced_run(broken, monkeypatch)
