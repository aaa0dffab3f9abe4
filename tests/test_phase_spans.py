"""The span primitive's four sinks and where the engine opens its spans:
per-phase self times that add up, the profiler's clock, the ring, and the
counters the fetch + decode layer keeps on its worker threads."""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from denormalized_tpu import Context, col, obs
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.obs import spans as obs_spans
from denormalized_tpu.obs.registry import MetricsRegistry
from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.physical.simple_execs import SOURCE_PHASE_KEYS, SourceExec
from denormalized_tpu.physical.window_exec import WINDOW_PHASES
from denormalized_tpu.runtime import tracing
from denormalized_tpu.runtime.tracing import collect_metrics
from denormalized_tpu.sources.base import (
    PartitionReader,
    Source,
    attach_canonical_timestamp,
    canonicalize_schema,
)

T0 = 1_700_000_000_000
SCH = Schema([
    Field("occurred_at_ms", DataType.INT64, nullable=False),
    Field("sensor_name", DataType.STRING, nullable=False),
    Field("reading", DataType.FLOAT64),
])


@pytest.fixture
def registry():
    reg = MetricsRegistry(enabled=True)
    prev = obs.use_registry(reg)
    yield reg
    obs.use_registry(prev)


@pytest.fixture
def ring():
    rec = obs_spans.enable_span_recording(4096)
    yield rec
    obs_spans.disable_span_recording()


# -- the primitive -----------------------------------------------------------


def test_nested_phases_record_exclusive_self_times(ring):
    clock = tracing.PhaseClock("unit", ("outer", "a", "b"))
    t0 = time.perf_counter()
    with clock.phase("root", "outer", batch=7):
        time.sleep(0.01)
        with clock.phase("a", batch=7):
            time.sleep(0.02)
            with clock.phase("b", batch=7):
                time.sleep(0.03)
        with clock.phase("a", batch=7):
            time.sleep(0.01)
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert clock and clock.n == {"outer": 1, "a": 2, "b": 1}
    # exclusive: each key holds its own sleeps only (10, 30 and 30 ms of
    # 70: were children not subtracted, outer would hold it all), and they
    # add up
    assert 9 <= clock.ms["outer"] < 0.6 * wall_ms
    assert 29 <= clock.ms["a"] < 0.8 * wall_ms
    assert 29 <= clock.ms["b"] < 0.8 * wall_ms
    assert sum(clock.ms.values()) == pytest.approx(wall_ms, rel=0.10)
    by_name = {}
    for _i, _ph, name, _t, dur, _tid, args in ring.events():
        by_name.setdefault(name, []).append((dur, args))
    assert set(by_name) == {"unit.root", "unit.a", "unit.b"}
    # the ring keeps whole durations and the fields: the root spans it all
    assert by_name["unit.root"][0][0] * 1e3 == pytest.approx(wall_ms, rel=0.10)
    assert by_name["unit.b"][0][1] == {"batch": 7}


def test_an_exception_closes_the_phase_and_marks_error(ring):
    clock = tracing.PhaseClock("unit", ("outer", "a"))
    with pytest.raises(KeyError):
        with clock.phase("outer"):
            with clock.phase("a", window=3):
                raise KeyError("boom")
    assert clock.n == {"outer": 1, "a": 1}
    failed = {e[2]: e[6] for e in ring.events()}
    assert failed["unit.a"] == {"window": 3, "error": "KeyError"}
    assert failed["unit.outer"] == {"error": "KeyError"}
    # the stack unwound: a later phase is a root again, not a's child
    with clock.phase("outer"):
        time.sleep(0.005)
    assert clock.ms["outer"] >= 5


def test_phases_on_two_threads_keep_their_own_stacks():
    clock = tracing.PhaseClock("unit", ("pull", "acc"))
    started, release = threading.Event(), threading.Event()

    def worker():
        with clock.phase("acc"):
            started.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    with clock.phase("pull"):
        t.start()
        assert started.wait(5)
        time.sleep(0.02)
        release.set()
        t.join(5)
    assert not t.is_alive()
    # the worker's phase is no child of the pull thread's: nothing subtracted
    assert clock.ms["pull"] >= 20 and clock.ms["acc"] >= 20


def test_with_no_sink_on_a_phase_is_a_reused_lap_and_may_nest_in_itself():
    clock = tracing.PhaseClock("unit", ("a", "b"))
    first = clock.phase("a", batch=1)
    with first:
        time.sleep(0.005)
        inner = clock.phase("a", batch=1)
        assert inner is first  # nothing is made per phase
        with inner:
            time.sleep(0.01)
        with clock.phase("b"):
            time.sleep(0.005)
    assert clock.n == {"a": 2, "b": 1}
    assert clock.ms["a"] >= 15 and clock.ms["b"] >= 5


def test_a_sink_is_seen_at_the_next_outermost_phase(monkeypatch):
    monkeypatch.setattr(tracing, "_SINK_POLL_S", 0.0)
    clock = tracing.PhaseClock("unit", ("a", "b"))
    rec = None
    try:
        with clock.phase("a"):
            rec = obs_spans.enable_span_recording(16)
            with clock.phase("b"):  # same unit of work: all or none
                pass
        assert rec.events() == []
        with clock.phase("a", batch=2):
            with clock.phase("b", batch=2):
                pass
        assert [e[2] for e in rec.events()] == ["unit.b", "unit.a"]
    finally:
        obs_spans.disable_span_recording()
    assert clock.n == {"a": 2, "b": 2}


def test_disabled_metrics_give_the_falsy_null_clock_and_an_empty_ring(ring):
    with obs.bound_registry(obs.disabled_registry()):
        clock = tracing.phase_clock("unit", ("a",))
    assert clock is tracing.NULL_CLOCK and not clock
    with clock.phase("a", batch=1):
        pass
    assert ring.events() == [] and dict(clock.ms) == {}


def test_plain_span_without_a_sink_takes_no_timestamp():
    s = tracing.span("unit.plain", k=1)
    with s:
        assert s._t0 is None


# -- a query, on the profiler's clock ----------------------------------------


class _Reader(PartitionReader):
    """Scripted batches, then the end of the partition."""

    def __init__(self, batches, read_sleep_s=0.0):
        self._batches = list(batches)
        self._sleep = read_sleep_s

    def read(self, timeout_s=None):
        if self._sleep:
            time.sleep(self._sleep)
        return self._batches.pop(0) if self._batches else None


class _Source(Source):
    name = "spans"

    def __init__(self, readers):
        self._readers = readers
        self._schema = canonicalize_schema(SCH)

    @property
    def schema(self):
        return self._schema

    def partitions(self):
        return self._readers

    @property
    def unbounded(self):
        return True


def _batch(b, part, rows=256):
    rng = np.random.default_rng([b, part])
    ts = np.sort(T0 + b * 500 + rng.integers(0, 500, size=rows))
    names = rng.choice([f"sensor_{i}" for i in range(7)], size=rows).astype(object)
    return attach_canonical_timestamp(
        RecordBatch(SCH, [ts, names, rng.normal(50, 10, rows)]),
        "occurred_at_ms", fallback_ms=T0,
    )


def _query(n_batches=8, **engine):
    readers = [
        _Reader([_batch(b, p) for b in range(n_batches)]) for p in range(2)
    ]
    ctx = Context(EngineConfig(
        min_batch_bucket=256, source_idle_timeout_ms=1000, **engine))
    ds = ctx.from_source(_Source(readers)).window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count"),
         F.avg(col("reading")).alias("avg")],
        1000,
    )
    return ctx, ds


def _by_class(ctx):
    out = {}
    for node_id, m in collect_metrics(ctx._last_physical).items():
        out.setdefault(node_id.split("_", 1)[1], {}).update(m)
    return out


def test_phase_counters_add_up_to_the_operators_brackets(registry):
    ctx, ds = _query()
    assert ds.collect().num_rows > 0
    per = _by_class(ctx)
    win, src = per["StreamingWindowExec"], per["SourceExec"]
    assert {f"phase_ms_{k}" for k in WINDOW_PHASES} <= set(win)
    assert set(SOURCE_PHASE_KEYS) <= set(src)
    for key in ("project", "intern", "statewatch", "reduce", "flush",
                "d2h_wait", "finalize", "trigger", "other"):
        assert win[f"phase_ms_{key}"] > 0, key
    # no -acc worker and no row shipping in this query
    assert win["phase_ms_acc_wait"] == 0 and win["phase_ms_update"] == 0
    phases = sum(win[f"phase_ms_{k}"] for k in WINDOW_PHASES)
    busy = registry.histogram("dnz_op_batch_ms", op="window").sum
    assert win["hint_path_ms"] > 0  # partition hints and the end of stream
    assert phases == pytest.approx(busy + win["hint_path_ms"], rel=0.10)
    # two workers, each reading or blocked nearly all of its life
    assert src["prefetch_read_ms"] > 0 and src["queue_wait_ms"] >= 0
    assert src["kafka_fetch_ms"] == 0 and src["kafka_decode_ms"] == 0


def test_host_pipeline_reduces_on_the_worker_and_waits_on_the_pull_thread(
        registry):
    ctx, ds = _query(host_pipeline=True)
    assert ds.collect().num_rows > 0
    win = _by_class(ctx)["StreamingWindowExec"]
    assert win["phase_ms_reduce"] > 0 and win["phase_ms_acc_wait"] > 0


def test_disabled_metrics_keep_the_keys_at_zero():
    ctx, ds = _query(metrics_enabled=False)
    assert ds.collect().num_rows > 0
    per = _by_class(ctx)
    assert all(per["StreamingWindowExec"][f"phase_ms_{k}"] == 0
               for k in WINDOW_PHASES)
    assert all(per["SourceExec"][k] == 0 for k in SOURCE_PHASE_KEYS)
    obs.set_enabled(True)


def test_spans_land_on_the_profilers_host_plane(tmp_path, registry):
    """Under the harness's ProfileOptions the engine's spans are on
    ``/host:CPU`` of the xplane, with the identifier of their unit of work."""
    from jax.profiler import ProfileData

    ctx, ds = _query()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert ds.collect().num_rows > 0
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0]
    found: dict[str, dict] = {}
    lines: dict[str, set] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.split(".")[0] in ("window", "prefetch", "source"):
                    found.setdefault(e.name, dict(e.stats))
                    lines.setdefault(e.name, set()).add(i)
    for name, key in [
        ("window.process_batch", "batch"), ("window.project", "batch"),
        ("window.intern", "batch"), ("window.statewatch", "batch"),
        ("window.reduce", "batch"), ("window.trigger", "batch"),
        ("window.flush", "rows"), ("window.gather", "window"),
        ("window.d2h_wait", "window"), ("window.finalize", "window"),
        ("window.hint", "batch"), ("window.eos", "batch"),
        ("prefetch.read", "partition"), ("prefetch.blocked", "seq"),
    ]:
        assert name in found, (name, sorted(found))
        assert key in found[name], (name, found[name])
    assert "n" in found["window.finalize"]
    # one line a thread: the workers' reads are not on the pull thread's
    assert lines["prefetch.read"]
    assert not lines["prefetch.read"] & lines["window.intern"]


# -- who waits for whom --------------------------------------------------------


def _drive_source(read_sleep_s, consume_sleep_s, n=12):
    readers = [
        _Reader([_batch(b, p, rows=32) for b in range(n)], read_sleep_s)
        for p in range(2)
    ]
    op = SourceExec(_Source(readers), queue_size=4)
    for item in op.run():
        if isinstance(item, RecordBatch) and item.num_rows and consume_sleep_s:
            time.sleep(consume_sleep_s)
    return op.metrics()


def test_a_slow_consumer_blocks_the_workers_and_never_starves(registry):
    m = _drive_source(read_sleep_s=0.0, consume_sleep_s=0.02)
    assert m["prefetch_blocked_ms"] > 200  # 24 batches x 20 ms, two workers
    assert m["prefetch_blocked_ms"] > 10 * m["prefetch_read_ms"]
    assert m["queue_wait_ms"] < 0.5 * m["prefetch_blocked_ms"]


def test_a_slow_reader_starves_the_pull_thread_and_is_never_blocked(registry):
    m = _drive_source(read_sleep_s=0.02, consume_sleep_s=0.0)
    assert m["prefetch_read_ms"] > 400  # 2 x 13 reads x 20 ms
    assert m["queue_wait_ms"] > 150
    assert m["prefetch_blocked_ms"] < 0.5 * m["queue_wait_ms"]


# -- the device programs' names ------------------------------------------------


def _spec():
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for([("count", None), ("avg", 0)])),
        num_value_cols=1, window_slots=16, group_capacity=128,
        length_ms=1000, slide_ms=200, accum_dtype=jax.numpy.float32,
        compensated=False,
    )


def _lower_update_state(spec, state):
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jax.numpy.int32)  # noqa: E731
    return sa.update_state.lower(
        spec, state, jax.ShapeDtypeStruct((256, 1), jax.numpy.float32),
        jax.ShapeDtypeStruct((256, 1), bool), i32(256), i32(256), i32(256),
        jax.ShapeDtypeStruct((256,), bool), i32(),
    )


def _lower_merge_partials(spec, state):
    n_planes = 3  # row count, sum hi, sum lo: the lean compact layout
    packed = jax.ShapeDtypeStruct((n_planes + 1, 1024 + 2), jax.numpy.int32)
    return sa.merge_partials.lower(spec, 1, 1024, True, False, state, packed)


def _lower_gather(spec, state):
    return sa._gather_and_reset.lower(
        spec, 2, 128, state, jax.ShapeDtypeStruct((), jax.numpy.int32), False)


def _lower_finals(spec, state):
    return sa._finals_and_reset.lower(
        spec, (("count", None), ("avg", 0)), 2, 128, state,
        jax.ShapeDtypeStruct((), jax.numpy.int32))


@pytest.mark.parametrize("scope,lower", [
    ("dnz.update_state", _lower_update_state),
    ("dnz.merge_partials", _lower_merge_partials),
    ("dnz.gather_and_reset", _lower_gather),
    ("dnz.finals_and_reset", _lower_finals),
])
def test_device_program_carries_its_scope(scope, lower):
    spec = _spec()
    state = {
        c.label: jax.ShapeDtypeStruct(
            (spec.window_slots, spec.group_capacity), spec.accum_dtype)
        for c in spec.components
    }
    text = lower(spec, state).as_text(debug_info=True)
    assert f"/{scope}/" in text
