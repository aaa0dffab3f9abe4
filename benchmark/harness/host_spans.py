"""The engine's own spans, read off the profiler's trace and off its
counters: what the host did while the device was idle.

While a profiler session is live, every span the engine opens
(``denormalized_tpu/runtime/tracing.py``) is also a ``TraceAnnotation``:
it lands on ``/host:CPU`` of the same ``.xplane.pb`` as the device's
``XLA Modules``, one line per OS thread, on one clock.  Thread names do not
survive (every line reads ``python3``), so a line is told by the spans on
it: the **lead** line is the one that opens the widest variety of spans —
the thread that pulls the stream and drives the window operator; the
others are the prefetch workers (and the ``-acc`` worker, if any).

With the profiler off the same spans still feed always-on self-time
counters (``phase_ms_*`` of the window operator's ``metrics()``,
``prefetch_*_ms`` / ``queue_wait_ms`` of the source's): ``phase_shares``
turns their deltas over a window into the per-layer shares.

Nothing under ``benchmark/harness`` imports this file: ``trace_reduce.
idle_gaps`` still names a gap by the program that ended it, until a
``benchmark`` PR wires ``attribute_gaps`` in (PERF.md, section 7).
"""

from __future__ import annotations

import re

from benchmark.harness.trace_reduce import short_name

HOST_PLANE = "/host:CPU"
ENGINE_SPAN = re.compile(r"^(window|prefetch|kafka|source|slice_window)\.\w+$")
NO_SPAN = "(no span)"

#: per-layer share -> the counters (milliseconds) it sums.  ``window_other``
#: is all that the seven named window shares do not cover.
PHASE_SHARES = {
    "window_project_share.drain": ("phase_ms_project",),
    "window_intern_share.drain": ("phase_ms_intern",),
    "window_reduce_share.drain": ("phase_ms_reduce",),
    "window_statewatch_share.drain": ("phase_ms_statewatch",),
    "window_flush_share.drain": ("phase_ms_flush",),
    "window_d2h_wait_share.drain": ("phase_ms_d2h_wait",),
    "window_finalize_share.drain": ("phase_ms_finalize",),
    "window_other_share.drain": (
        "phase_ms_other", "phase_ms_trigger", "phase_ms_gather",
        "phase_ms_acc_wait", "phase_ms_update",
    ),
    "prefetch_read_load.drain": ("prefetch_read_ms",),
    "prefetch_blocked_load.drain": ("prefetch_blocked_ms",),
    "source_queue_wait_share.drain": ("queue_wait_ms",),
}


def share(obs: dict, *counters: str) -> float | None:
    """100 x the counters' summed delta over the window's milliseconds:
    percent of one thread.  None where the program has no such counter."""
    held = obs.get("counters", {})
    if any(c not in held for c in counters) or not obs.get("window_s"):
        return None
    return 100.0 * sum(held[c] for c in counters) / (obs["window_s"] * 1000.0)


def phase_shares(obs: dict) -> dict[str, float | None]:
    return {name: share(obs, *cs) for name, cs in PHASE_SHARES.items()}


# -- the trace -------------------------------------------------------------


def host_events(path: str, match=ENGINE_SPAN.match) -> list[tuple]:
    """``(line, name, start_ns, end_ns, stats)`` of every span on
    ``/host:CPU`` whose name ``match`` accepts (default: the engine's)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            key = f"{line.name}#{i}"
            for e in line.events:
                if match(e.name):
                    start = float(e.start_ns)
                    out.append((key, e.name, start,
                                start + float(e.duration_ns), dict(e.stats)))
    return out


def self_segments(events: list[tuple]) -> dict[str, list[tuple]]:
    """Per line, the stretches ``(name, start_ns, end_ns)`` each span held
    alone: its interval minus what the spans nested in it cover."""
    by_line: dict[str, list] = {}
    for line, name, a, b, _stats in events:
        by_line.setdefault(line, []).append((a, b, name))
    out = {}
    for line, evs in by_line.items():
        evs.sort(key=lambda e: (e[0], -e[1]))
        segs, stack, cursor = [], [], 0.0

        def close(until: float) -> None:
            nonlocal cursor
            while stack and stack[-1][1] <= until:
                _a, b, name = stack.pop()
                if b > cursor:
                    segs.append((name, cursor, b))
                    cursor = b

        for a, b, name in evs:
            close(a)
            if stack and a > cursor:
                segs.append((stack[-1][2], cursor, a))
            cursor = max(cursor, a)
            stack.append((a, b, name))
        close(float("inf"))
        out[line] = segs
    return out


def self_times(events: list[tuple]) -> dict[str, float]:
    """Seconds of self time per span name, over all lines."""
    out: dict[str, float] = {}
    for segs in self_segments(events).values():
        for name, a, b in segs:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def lead_line(segments: dict[str, list[tuple]]) -> str | None:
    """The line with the most span names (then the most time on it)."""
    def rank(line):
        segs = segments[line]
        return (len({n for n, _a, _b in segs}), sum(b - a for _n, a, b in segs))

    return max(segments, key=rank, default=None)


def attribute_gaps(modules: list, events: list[tuple], k: int = 10) -> list[dict]:
    """For the ``k`` longest gaps between program runs (``XLA Modules`` of
    the first chip, ``(name, start_ns, duration_ns)``): the spans that filled
    it.  ``lead`` gives each span's share of the gap on the lead line, in
    percent, with ``(no span)`` for the rest (they add up to 100);
    ``others`` the load of the spans on every other line, in percent of one
    thread."""
    runs = sorted((s, s + d, n) for n, s, d in modules)
    gaps = sorted(
        ((start - prev_end, prev_end, start, name)
         for (_s, prev_end, _n), (start, _e, name) in zip(runs, runs[1:])
         if start > prev_end),
        reverse=True,
    )[:k]
    segments = self_segments(events)
    lead = lead_line(segments)
    origin = runs[0][0] if runs else 0.0
    out = []
    for length, a, b, name in gaps:
        held: dict[str, float] = {}
        others: dict[str, float] = {}
        for line, segs in segments.items():
            into = held if line == lead else others
            for span, sa, sb in segs:
                cover = min(b, sb) - max(a, sa)
                if cover > 0:
                    into[span] = into.get(span, 0.0) + cover
        rows = sorted(held.items(), key=lambda kv: -kv[1])
        rows.append((NO_SPAN, length - sum(held.values())))
        out.append({
            "gap_s": length / 1e9,
            "at_s": (a - origin) / 1e9,
            "before": short_name(name),
            "lead": [[n, 100.0 * v / length] for n, v in rows],
            "others": [[n, 100.0 * v / length]
                       for n, v in sorted(others.items(), key=lambda kv: -kv[1])],
        })
    return out
