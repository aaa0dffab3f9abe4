"""Each traffic mode end to end at a tiny size, through the real engine, on
the CPU: the feeder process, ``ctx.from_topic`` → window → filter →
``stream()``, the measured window, the comparison and the last line.  The
harness's look for a chip is skipped through a Python argument; everything
else is the path a chip run takes.  Then the same run with the timed path
broken underneath: ``correct`` has to come out false.  Runs are short and
mostly paced (little CPU): the suite runs beside load-sensitive tests."""

import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import lastline, manifest, runner, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
SEED = 3_000_000_019  # above 2**31, as the driver's are


def tiny(name):
    return manifest.load_cell(
        name, os.path.join(FIXTURES, "BENCHMARK.json"),
        os.path.join(FIXTURES, "benchmark"))


def run(name, seconds=0.6, trace=False, seed=SEED):
    cell = tiny(name)
    text = runner.run_cell(cell, seed, seconds, trace, require_tpu=False,
                           log=lambda msg: None)
    wanted = cell.per_layer if trace else cell.end_to_end
    assert lastline.check_text(text, wanted, trace) == []
    return cell, json.loads(text)


@pytest.mark.parametrize("name", ["tiny_sliding.drain", "tiny_keyed.drain"])
def test_drain_cell_rehearsal(name):
    cell, line = run(name)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["metrics"]) == set(cell.end_to_end)
    c = line["compared"]
    assert list(line)[-1] == "compared"
    assert c["late_rows"]["value"] == 0 and c["decode_fallback_rows"]["value"] == 0
    assert 0 < c["rel_err_max"]["value"] <= c["rel_err_max"]["limit"]


def test_paced_cell_rehearsal():
    cell, line = run("tiny_sliding.paced", seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    # five window ends a second fall due inside the window
    assert 13 <= line["attempted"] <= 16
    p50 = line["metrics"]["window_latency_p50_ms"]["value"]
    p95 = line["metrics"]["window_latency_p95_ms"]["value"]
    assert 0 < p50 <= p95 < 10000
    assert "events_per_s" not in line["metrics"]


def test_traced_run_reports_every_per_layer_metric(monkeypatch):
    """No chip here, so the trace itself is the recorded fixture; the rest
    of a traced run is real."""
    monkeypatch.setattr(runner, "start_trace", lambda d: None)
    monkeypatch.setattr(runner, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: os.path.join(
        FIXTURES, "small.xplane.pb"))
    cell, line = run("tiny_sliding.drain", trace=True)
    assert set(cell.per_layer) <= set(line["metrics"])
    assert "rows_in_per_window.fixture" in line["metrics"]  # fixture-only metric
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["metrics"]["device_idle_share.drain"]["value"] < 100.0
    assert line["metrics"]["h2d_bytes_per_event.drain"]["value"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_trace_without_a_device_event_gives_no_result(monkeypatch):
    def nothing(path, chips):
        raise trace_reduce.NoDeviceEvents("no /device:TPU plane")

    monkeypatch.setattr(runner, "start_trace", lambda d: None)
    monkeypatch.setattr(runner, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x")
    monkeypatch.setattr(trace_reduce, "reduce_trace", nothing)
    with pytest.raises(runner.RunFailed):
        runner.run_cell(tiny("tiny_sliding.paced"), SEED, 1.0, True,
                        require_tpu=False, log=lambda msg: None)


def test_the_command_fails_without_a_chip_and_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "emit_sliding.drain", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_unknown_workload_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


# -- the timed path broken underneath ---------------------------------------


def test_fault_an_answer_altered_where_it_is_produced(monkeypatch):
    from denormalized_tpu.common.record_batch import RecordBatch

    real = runner.build_stream

    class Altered:
        def __init__(self, ds):
            self._ds = ds

        def stream(self):
            for b in self._ds.stream():
                i = b.schema.index_of("avg")
                cols = list(b.columns)
                avg = np.array(cols[i], dtype=np.float64)
                avg[0] *= 1.001
                cols[i] = avg
                yield RecordBatch(b.schema, cols, list(b.masks))

    def build(cell, bootstrap):
        ctx, ds = real(cell, bootstrap)
        return ctx, Altered(ds)

    monkeypatch.setattr(runner, "build_stream", build)
    _cell, line = run("tiny_sliding.paced", seconds=2.0)
    assert line["correct"] is False and line["failed"] > 0
    c = line["compared"]["rel_err_max"]
    assert c["value"] > c["limit"]


def test_fault_half_of_each_batch_left_out(monkeypatch):
    from denormalized_tpu.sources import kafka

    real = kafka.parse_fetch_arena

    def half(parser, n, bptr, optr, ts):
        batch, ts = real(parser, n, bptr, optr, ts)
        if batch is None:
            return batch, ts
        keep = batch.num_rows - batch.num_rows // 2
        return batch.slice(0, keep), ts[:keep]

    monkeypatch.setattr(kafka, "parse_fetch_arena", half)
    _cell, line = run("tiny_sliding.paced", seconds=2.0)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["count_mismatch"]["value"] > 0


def test_fault_a_step_that_returns_its_state_unchanged(monkeypatch):
    """Every second stripe merge hands its state back untouched (were it
    every one, no window would ever be delivered and the run would end with
    no result line at all)."""
    from denormalized_tpu.ops import segment_agg

    real = segment_agg.merge_partials
    calls = [0]

    def every_other(spec, sub, a_pad, lean, dense, state, packed):
        calls[0] += 1
        if calls[0] % 2:
            return state
        return real(spec, sub, a_pad, lean, dense, state, packed)

    monkeypatch.setattr(segment_agg, "merge_partials", every_other)
    _cell, line = run("tiny_sliding.paced", seconds=2.0)
    assert calls[0] > 4
    assert line["correct"] is False and line["failed"] > 0
    c = line["compared"]
    assert c["count_mismatch"]["value"] + c["missing_rows"]["value"] > 0
