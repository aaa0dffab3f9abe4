"""Deferred emission (``emit_lag_ms`` above 0, the accelerators' default):
an emission block is brought to the host by the ``-d2h`` worker beside
ingest and taken by the pull thread at the first trigger after it landed;
the pull thread waits for one only where something needs it out first — the
next close, an idle hint, a marker, the end of the stream.  Whatever the
timing, every window leaves once, whole, in ascending order."""

import threading
import time

import jax
import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.logical import plan as lp
from denormalized_tpu.physical.base import EOS, Marker, WatermarkHint
from denormalized_tpu.physical.simple_execs import CollectSink
from denormalized_tpu.physical.window_exec import StreamingWindowExec
from denormalized_tpu.runtime.executor import build_physical
from denormalized_tpu.sources.base import attach_canonical_timestamp
from denormalized_tpu.sources.memory import MemorySource

T0 = 1_700_000_000_000
KEYS = ["a", "b", "c"]
#: a trigger with a closable window acts at once (the stripe always holds
#: the row cap), so a test decides when a window closes by the batch it sends
DEFERRED = dict(emit_lag_ms=200, partial_merge_rows=1)


class _Script:
    """Stands in for the operator's input: yields the scripted items, and
    calls the scripted callables when the operator asks for the next item —
    everything it made of the earlier ones has left it by then."""

    def __init__(self, schema, steps):
        self.schema = schema
        self.steps = steps

    def run(self):
        for step in self.steps:
            if callable(step):
                step()
            else:
                yield step


class _Drive:
    """A tumbling 1 s count/sum/avg by sensor over scripted input, its
    ``read_reset_block_finish`` held at a gate (the first ``held`` calls)."""

    def __init__(self, make_batch, held=1, **config):
        self.make_batch = make_batch
        ctx = Context(EngineConfig(**config))
        ds = ctx.from_source(
            MemorySource.from_batches(
                [make_batch([T0], ["a"], [0.0])],
                timestamp_column="occurred_at_ms",
            )
        ).window(
            ["sensor_name"],
            [F.count(col("reading")).alias("cnt"),
             F.sum(col("reading")).alias("s"),
             F.avg(col("reading")).alias("a")],
            1000,
        )
        op = build_physical(lp.Sink(ds._plan, CollectSink()), ctx)
        while not isinstance(op, StreamingWindowExec):
            op = op.input_op
        self.op = op
        self.gate = threading.Event()
        self.fetches = []  # thread names, one a call
        self.out = []
        real = op._backend.read_reset_block_finish

        def finish(handle):
            self.fetches.append(threading.current_thread().name)
            if len(self.fetches) <= held:
                assert self.gate.wait(30)
            return real(handle)

        op._backend.read_reset_block_finish = finish

    def batch(self, window, offset=0):
        """Six rows of window ``window``, two a key; readings are whole
        numbers, so every sum is exact however the stripe is cut."""
        ts = T0 + 1000 * window + offset + np.arange(6)
        return attach_canonical_timestamp(
            self.make_batch(ts, KEYS * 2, np.arange(6.0) + 10 * window),
            "occurred_at_ms", fallback_ms=0,
        )

    def run(self, *steps):
        self.op.input_op = _Script(self.op.input_op.schema, list(steps))
        for item in self.op.run():
            self.out.append(item)
        return self.out

    def windows(self):
        """(window start, rows) of every emitted batch so far, in order."""
        return [
            (int(b.column(WINDOW_START_COLUMN)[0]) - T0, b.num_rows)
            for b in self.out if isinstance(b, RecordBatch)
        ]

    def release_when_asked(self, after_s=0.0):
        def step():
            threading.Timer(after_s, self.gate.set).start()
        return step

    def land(self):
        """Open the gate and stand by until every block in flight is on
        the host."""
        self.gate.set()
        deadline = time.monotonic() + 30
        while not all(p[4].done() for p in self.op._pending_emit):
            assert time.monotonic() < deadline
            time.sleep(0.005)


def test_a_block_in_flight_holds_nothing_up_and_leaves_when_it_has_landed(
        make_batch):
    d = _Drive(make_batch, **DEFERRED)
    op = d.op
    seen = {}

    def while_in_flight():
        # window 0 closed two batches ago; its block is still in the gate
        m = op.metrics()
        seen["in_flight"] = (
            d.windows(), len(op._pending_emit), m["batches_in"],
            m["emit_blocks_overlapped"], m["emit_blocks_waited"],
            m["phase_ms_d2h_wait"], m["bytes_d2h"], m["windows_emitted"],
        )
        d.land()

    def after_landing():
        m = op.metrics()
        seen["taken"] = (
            d.windows(), len(op._pending_emit),
            m["emit_blocks_overlapped"], m["emit_blocks_waited"],
            m["phase_ms_d2h_wait"], m["windows_emitted"],
        )
        seen["bytes_d2h"] = m["bytes_d2h"]

    d.run(
        d.batch(0), d.batch(1), d.batch(1, 100), d.batch(1, 200),
        while_in_flight, d.batch(1, 300), after_landing, EOS,
    )
    # the operator took three more batches and yielded nothing of window 0
    assert seen["in_flight"] == ([], 1, 4, 0, 0, 0.0, 0, 0)
    # the first trigger after the release yields it, whole, without a wait
    assert seen["taken"] == ([(0, 3)], 0, 1, 0, 0.0, 1)
    assert seen["bytes_d2h"] > 0
    # the end of the stream reads the open window by slot
    assert d.windows() == [(0, 3), (1000, 3)] and d.out[-1] is EOS
    m = op.metrics()
    assert m["d2h_fetch_ms"] > 0
    assert len(d.fetches) == 1 and d.fetches[0].endswith("-d2h_0")
    w0 = d.out[0]
    assert sorted(w0.column("sensor_name")) == KEYS
    assert list(w0.column("cnt")) == [2, 2, 2]
    assert sorted(w0.column("s")) == [3.0, 5.0, 7.0]
    # nothing of the worker outlives the stream
    assert op._emit_exec is None
    assert not any(t.name.endswith("-d2h_0") for t in threading.enumerate())


def test_the_next_close_waits_for_the_block_in_flight(make_batch):
    d = _Drive(make_batch, **DEFERRED)
    seen = {}

    def after_second_close():
        m = d.op.metrics()
        seen["closed"] = (
            d.windows(), [p[0] for p in d.op._pending_emit],
            m["emit_blocks_overlapped"], m["emit_blocks_waited"],
        )
        seen["wait_ms"] = m["phase_ms_d2h_wait"]

    d.run(
        d.batch(0), d.batch(1), d.release_when_asked(0.25),
        d.batch(2), after_second_close, EOS,
    )
    # window 1's close stood still until window 0's block was out, then
    # dispatched its own: one close's blocks in flight, never two
    windows, pending, overlapped, waited = seen["closed"]
    assert windows == [(0, 3)] and (overlapped, waited) == (0, 1)
    assert pending == [T0 // 1000 + 1]
    assert seen["wait_ms"] > 150
    assert d.windows() == [(0, 3), (1000, 3), (2000, 3)]
    m = d.op.metrics()
    assert m["emit_blocks_overlapped"] + m["emit_blocks_waited"] == 2


def _idle_hint(d):
    return [WatermarkHint(T0 + 1500)], [(0, 3)]


def _marker(d):
    # checkpointing on, as far as _on_marker looks: the snapshot is taken
    # where the drain has left no block pending
    d.op._ckpt = ("coord", "key")
    d.op._snapshot = lambda epoch: d.snapshots.append(
        (epoch, len(d.op._pending_emit), d.op.metrics()["windows_emitted"])
    )
    return [Marker(7)], [(0, 3)]


def _end_of_stream(d):
    return [], [(0, 3), (1000, 3)]


@pytest.mark.parametrize("forcing", [_idle_hint, _marker, _end_of_stream])
def test_what_cannot_wait_for_a_next_batch_waits_for_the_block(
        make_batch, forcing):
    d = _Drive(make_batch, **DEFERRED)
    d.snapshots = []
    items, want_after = forcing(d)
    seen = {}

    def after_forcing():
        m = d.op.metrics()
        seen["after"] = (
            d.windows(), len(d.op._pending_emit),
            m["emit_blocks_overlapped"], m["emit_blocks_waited"],
        )
        seen["wait_ms"] = m["phase_ms_d2h_wait"]

    d.run(
        d.batch(0), d.batch(1), d.release_when_asked(0.2),
        *items, *([after_forcing] if items else []), EOS,
    )
    if not items:
        after_forcing()
    assert seen["after"] == (want_after, 0, 0, 1)
    assert seen["wait_ms"] > 100
    assert d.windows() == [(0, 3), (1000, 3)]
    if forcing is _marker:
        assert d.snapshots == [(7, 0, 1)]
        assert [i for i in d.out if isinstance(i, Marker)] == [Marker(7)]
    if forcing is _idle_hint:
        hints = [i for i in d.out if isinstance(i, WatermarkHint)]
        assert len(hints) == 1
        # the hint leaves after the window it closed the wait for
        assert d.out.index(hints[0]) == 1


def test_a_worker_failure_is_raised_on_the_pull_thread_once(make_batch):
    d = _Drive(make_batch, held=0, **DEFERRED)
    pull_thread = threading.current_thread().name

    def broken(handle):
        d.fetches.append(threading.current_thread().name)
        raise OSError("link down")

    d.op._backend.read_reset_block_finish = broken
    worker_done = []

    def wait_for_worker():
        deadline = time.monotonic() + 30
        while not d.op._pending_emit[0][4].done():
            assert time.monotonic() < deadline
            time.sleep(0.005)
        worker_done.append(True)

    with pytest.raises(OSError, match="link down"):
        d.run(d.batch(0), d.batch(1), wait_for_worker, d.batch(1, 100), EOS)
    assert worker_done and d.fetches and d.fetches[0] != pull_thread
    # the failed block went with the failure: nothing is left to raise again
    assert d.op._pending_emit == []
    assert list(d.op._drain_pending()) == []
    assert d.windows() == []


def _stream(make_batch, config, slide_ms=None):
    """A seeded replay — 24 batches, 200 keys, whole-number readings — pulled
    batch by batch; returns the operator and what was delivered, in order."""
    rng = np.random.default_rng(32)
    batches = []
    for b in range(24):
        n = 512
        ts = np.sort(T0 + b * 250 + rng.integers(0, 250, n))
        keys = np.array(
            [f"s{i}" for i in rng.integers(0, 200, n)], dtype=object
        )
        batches.append(
            make_batch(ts, keys, rng.integers(0, 100, n).astype(np.float64))
        )
    ctx = Context(config)
    ds = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(
        ["sensor_name"],
        [F.count(col("reading")).alias("cnt"),
         F.sum(col("reading")).alias("s"),
         F.min(col("reading")).alias("mn"),
         F.max(col("reading")).alias("mx"),
         F.avg(col("reading")).alias("a")],
        1000, slide_ms,
    )
    delivered = [b for b in ds.stream() if b.num_rows]
    op = ctx._last_physical
    while not isinstance(op, StreamingWindowExec):
        op = op.children[0]
    return op, delivered


@pytest.mark.parametrize("slide_ms", [None, 500], ids=["tumbling", "sliding"])
@pytest.mark.parametrize("mesh_devices", [None, 4], ids=["one_device", "mesh4"])
def test_deferred_and_prompt_emission_deliver_the_same_bytes(
        make_batch, mesh_devices, slide_ms):
    if mesh_devices and len(jax.devices()) < mesh_devices:
        pytest.skip("needs four virtual devices")
    base = dict(partial_merge_rows=700)
    if mesh_devices:
        base["mesh_devices"] = mesh_devices
    _op, prompt = _stream(
        make_batch, EngineConfig(emit_lag_ms=0, **base), slide_ms)
    op, deferred = _stream(
        make_batch, EngineConfig(emit_lag_ms=200, **base), slide_ms)
    m = op.metrics()
    assert m["strategy_resolved"] == (
        "partial_merge/key_sharded" if mesh_devices else "partial_merge")
    # the deferred run did go through the worker, block after block
    assert m["emit_blocks_overlapped"] + m["emit_blocks_waited"] >= 3
    assert _op.metrics()["emit_blocks_overlapped"] == 0
    assert _op.metrics()["emit_blocks_waited"] == 0
    assert m["bytes_d2h"] > 0 and m["windows_emitted"] == len(deferred)
    starts = [int(b.column(WINDOW_START_COLUMN)[0]) for b in deferred]
    assert starts == sorted(set(starts)) and len(starts) >= 5
    assert len(prompt) == len(deferred)
    for want, got in zip(prompt, deferred):
        assert got.schema.names == want.schema.names
        for name in want.schema.names:
            a, b = np.asarray(want.column(name)), np.asarray(got.column(name))
            assert a.dtype == b.dtype and a.shape == b.shape, name
            if a.dtype == object:
                assert a.tolist() == b.tolist(), name
            else:
                assert a.tobytes() == b.tobytes(), name
