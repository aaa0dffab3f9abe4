"""Lightweight tracing/metrics.

Observability mirror of the reference: `tracing`/`tracing-subscriber` span
events wired in the rideshare example (kafka_rideshare.rs:16-22) and the
per-operator DataFusion `BaselineMetrics` exposed through
``ExecutionPlan::metrics`` (streaming_window.rs:211,491).  Here:

- every physical operator already keeps a metrics dict (rows_in,
  device_steps, late_rows, ...) exposed via ``ExecOperator.metrics()``;
- :func:`collect_metrics` aggregates them over a plan tree;
- :func:`enable_tracing` turns on span logging: :class:`span` context
  managers emit enter/close events with wall-time, like tracing-subscriber's
  span events;
- :class:`PhaseClock` gives an operator always-on per-phase self times
  through the same :class:`span`.
"""

from __future__ import annotations

import contextlib
import logging
import sys
import threading
import time
from types import MappingProxyType

from denormalized_tpu import obs
from denormalized_tpu.obs import spans as obs_spans

logger = logging.getLogger("denormalized_tpu")

_TRACING = False
_now = time.perf_counter
_ANNOTATION = None  # jax.profiler.TraceAnnotation, resolved on first use


def enable_tracing(level: int = logging.INFO) -> None:
    global _TRACING
    _TRACING = True
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=level,
            format="%(asctime)s %(levelname)s %(name)s %(message)s",
        )
    logger.setLevel(level)


def tracing_enabled() -> bool:
    return _TRACING


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once some other module has imported
    JAX, else None: a process that never imported JAX has no profiler
    session to write into, and is not made to import it here."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        import jax.profiler

        _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


def _sinks_on() -> bool:
    """Is any sink besides the phase counters on right now?"""
    if _TRACING or obs_spans.recorder() is not None:
        return True
    annotation = _ANNOTATION or _annotation_class()
    return annotation is not None and annotation.is_enabled()


class span:
    """Span with enter/close events (tracing-subscriber
    `with_span_events(ENTER|CLOSE)` analog).  The only way the engine
    opens a span; four sinks, independently enabled:

    - log lines when :func:`enable_tracing` is on — the close line
      carries the entry fields AND the error status (a span that exits
      via exception logs ``status=ExcType``, not a plain close that
      reads like success);
    - the structured ring recorder
      (:func:`denormalized_tpu.obs.spans.enable_span_recording`), which
      dumps Perfetto-loadable Chrome trace JSON for whole-pipeline
      profiling.  Failed spans carry ``args.error`` there;
    - the JAX profiler, while a session is live
      (``jax.profiler.start_trace``): the span enters a
      ``TraceAnnotation(name, **fields)`` and lands on ``/host:CPU`` of the
      same ``.xplane.pb`` as the device's ``XLA Ops``, on one clock, one
      line per OS thread;
    - a :class:`PhaseClock` (spans opened through
      :meth:`PhaseClock.phase`): always-on self-time counters.

    A plain span with no sink enabled takes no timestamp."""

    __slots__ = ("name", "fields", "_lap", "_t0", "_rec", "_ta")

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields
        self._lap = None
        self._t0 = None

    def __enter__(self):
        rec = obs_spans.recorder()
        ta = None
        annotation = _ANNOTATION or _annotation_class()
        if annotation is not None and annotation.is_enabled():
            ta = annotation(self.name, **self.fields)
            ta.__enter__()
        lap = self._lap
        if lap is None and rec is None and ta is None and not _TRACING:
            return self
        self._rec = rec
        self._ta = ta
        if _TRACING:
            logger.info("enter %s %s", self.name, self.fields or "")
        if lap is not None:
            lap.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb):
        t0 = self._t0
        if t0 is None:
            return False
        dur = _now() - t0
        self._t0 = None
        if self._lap is not None:
            self._lap.__exit__(exc_type, exc, tb)
        if self._ta is not None:
            self._ta.__exit__(exc_type, exc, tb)
        # record, never swallow: the span must report failure (a plain
        # `close` would be indistinguishable from success)
        err = exc_type.__name__ if exc_type is not None else None
        if _TRACING:
            logger.info(
                "close %s time.busy=%.3fms status=%s %s",
                self.name, dur * 1e3, err or "ok", self.fields or "",
            )
        if self._rec is not None:
            self._rec.record(
                self.name, t0, dur, self.fields or None, error=err
            )
        return False


class _Thread:
    """One thread's open phases of one clock: the key that is running, since
    when, the keys suspended under it, and this thread's laps by name."""

    __slots__ = ("cur", "last", "stack", "laps", "sinks", "checked")

    def __init__(self):
        self.cur = None
        self.last = 0.0
        self.stack: list = []
        self.laps: dict = {}
        self.sinks = False
        self.checked = -1.0


class _Lap:
    """The counting half of a phase, and all of it while no other sink is
    on: one per (thread, span name), reused — its state is the thread's
    stack, so a phase may nest in itself.  Entering charges the time since
    the last boundary to the phase that was running and suspends it;
    leaving charges this phase and resumes the one under it: every moment
    inside the outermost phase goes to exactly one key."""

    __slots__ = ("_ms", "_n", "_t", "_key", "name")

    def __init__(self, clock: "PhaseClock", t: _Thread, name: str, key: str):
        self._ms = clock.ms
        self._n = clock.n
        self._t = t
        self._key = key
        self.name = f"{clock.owner}.{name}"

    def __enter__(self):
        t = self._t
        now = _now()
        cur = t.cur
        if cur is not None:
            self._ms[cur] += (now - t.last) * 1e3
        t.stack.append(cur)
        t.cur = self._key
        t.last = now

    def __exit__(self, exc_type, exc, tb):
        t = self._t
        now = _now()
        key = t.cur
        self._ms[key] += (now - t.last) * 1e3
        self._n[key] += 1
        t.cur = t.stack.pop()
        t.last = now
        return False


#: how stale a clock's view of the other sinks may get, in seconds: a
#: profiler session or a recorder that starts is seen by the next outermost
#: phase after this long
_SINK_POLL_S = 0.02


class PhaseClock:
    """Always-on self-time counters of one owner (an operator, a prefetch
    worker, a reader).  ``phase(name)`` opens the span ``<owner>.<name>``;
    on exit the span's **self time** — its duration minus what its child
    phases of this clock on the same thread covered — has been added to
    ``ms[key]`` and 1 to ``n[key]`` (``key`` defaults to ``name``; several
    span names may feed one key, a name always the same one).  Exclusive
    times, so the keys of one clock add up to the wall its outermost
    phases covered.

    While no other sink is on, a phase is a reused :class:`_Lap`: no
    object is made and nothing but the clock is read.  Whether one is on
    is looked at when a thread opens an outermost phase (at most every
    ``_SINK_POLL_S``), so the spans of one unit of work are all written or
    none.

    Each key has one writer thread at a time (the owner's thread; a second
    thread may run phases of OTHER keys), so the adds take no lock.
    Phases of one thread must close in the order they opened — a generator
    suspended inside a phase must be driven to its end before the thread
    opens a sibling."""

    __slots__ = ("owner", "ms", "n", "_tls")

    def __init__(self, owner: str, keys):
        self.owner = owner
        self.ms = dict.fromkeys(keys, 0.0)
        self.n = dict.fromkeys(keys, 0)
        self._tls = threading.local()

    def phase(self, name: str, key: str | None = None, **fields):
        try:
            t = self._tls.t
        except AttributeError:
            t = self._tls.t = _Thread()
        lap = t.laps.get(name)
        if lap is None:
            lap = t.laps[name] = _Lap(self, t, name, key or name)
        if t.cur is None:
            now = _now()
            if now - t.checked > _SINK_POLL_S:
                t.checked = now
                t.sinks = _sinks_on()
        if not t.sinks:
            return lap
        s = span(lap.name, **fields)
        s._lap = lap
        return s


class _NullClock:
    """The clock of an owner bound under ``metrics_enabled=False``: falsy,
    counts nothing, opens no span."""

    __slots__ = ()
    ms = n = MappingProxyType({})
    _span = contextlib.nullcontext()

    def __bool__(self) -> bool:
        return False

    def phase(self, name: str, key: str | None = None, **fields):
        return self._span


NULL_CLOCK = _NullClock()


def phase_clock(owner: str, keys) -> PhaseClock | _NullClock:
    """Bind a phase clock like every other instrument: against the
    registry current on this thread, the shared falsy null when that
    registry is disabled."""
    return PhaseClock(owner, keys) if obs.enabled() else NULL_CLOCK


def collect_metrics(root) -> dict[str, dict]:
    """Per-operator metrics over a physical plan tree, keyed by the same
    DFS ids used for checkpoint node ids."""
    from denormalized_tpu.state.checkpoint import assign_node_ids, walk

    ids = assign_node_ids(root)
    out = {}
    for op in walk(root):
        m = op.metrics()
        if m:
            out[ids[id(op)]] = m
    return out


def log_metrics(root) -> None:
    if _TRACING:
        for node, m in collect_metrics(root).items():
            logger.info("metrics %s %s", node, m)
