"""From a profiler trace (``.xplane.pb``) to device busy time and the
breakdown the last line carries.  Reads with ``jax.profiler.ProfileData``
and nothing else; checked against a small trace recorded on the chip
(``tests/benchmark_tests/fixtures/small.xplane.pb``).

On a v5e the device plane is ``/device:TPU:<n>`` and its ``XLA Ops`` line
holds one event per HLO operation executed (``XLA Modules`` one per
program run; ``Async XLA Ops`` the copies in flight beside them).  Busy
time is the union of the ``XLA Ops`` intervals, so overlapping events count
once and the gaps between a program's operations count as idle.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class NoDeviceEvents(RuntimeError):
    """The trace holds no device plane, or no operation ran on it."""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not paths:
        raise NoDeviceEvents(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def device_lines(path: str) -> dict[int, dict[str, list]]:
    """``{chip: {line name: [(name, start_ns, duration_ns)]}}`` of the
    device planes."""
    from jax.profiler import ProfileData

    out: dict[int, dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        out[int(m.group(1))] = {
            line.name: [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ]
            for line in plane.lines
        }
    return out


def short_name(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``."""
    m = re.match(r"^%?([\w.\-]+)", op)
    return m.group(1) if m else op[:40]


def reduce_trace(path: str, chips: int) -> dict:
    """Busy seconds (averaged over the chips used), program runs, and the
    ten device operations that took most time."""
    planes = device_lines(path)
    if not planes:
        raise NoDeviceEvents(f"no /device:TPU plane in {path}")
    busy = []
    per_op: dict[str, float] = {}
    modules = 0
    for chip in sorted(planes)[:chips]:
        ops = planes[chip].get(OPS_LINE, [])
        busy.append(union_ns([(s, s + d) for _n, s, d in ops]) / 1e9)
        for name, _s, d in ops:
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + d / 1e9
        modules += len(planes[chip].get(MODULES_LINE, []))
    if len(busy) < chips:
        raise NoDeviceEvents(
            f"{len(busy)} device plane(s) in {path}, the cell uses {chips}"
        )
    busy_s = sum(busy) / chips
    if not busy_s > 0:
        raise NoDeviceEvents(f"no device operation in {path}")
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "module_runs": modules,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": idle_gaps(planes[min(planes)].get(MODULES_LINE, [])),
    }


def idle_gaps(modules: list) -> list:
    """The ten longest gaps between program runs on the first chip, each
    named by the program that ended it.  What the host did meanwhile is not
    in the trace yet: the engine writes no ``TraceAnnotation``."""
    runs = sorted((s, s + d, n) for n, s, d in modules)
    gaps = [
        ["before " + short_name(name), (start - prev_end) / 1e9]
        for (_s, prev_end, _n), (start, _e, name) in zip(runs, runs[1:])
        if start > prev_end
    ]
    return sorted(gaps, key=lambda g: -g[1])[:10]
