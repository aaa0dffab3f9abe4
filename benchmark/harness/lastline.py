"""The last line of standard output: built and checked by one function,
so a malformed line stops the run here and not in the driver's check.

The line is one JSON object with the keys ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device``.  ``metrics`` gives every metric the
cell lists for this kind of run (``--trace 0``: its end-to-end metrics,
``--trace 1``: its per-layer metrics; a traced run prints the end-to-end
ones beside them, which the driver ignores) as ``{"value": number, "unit":
text}``.  ``device`` gives ``platform``, ``kind``, ``count`` and
``memory_peak_bytes`` and, in a traced run, ``busy_s`` and ``window_s``
with ``0 < busy_s <= window_s``.  ``breakdown`` (traced runs) and
``compared`` (every number beside its limit, last) are optional.
"""

from __future__ import annotations

import json
import math

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class Malformed(ValueError):
    """The result line would not be read by the driver."""


def _number(x) -> bool:
    return (
        isinstance(x, (int, float)) and not isinstance(x, bool)
        and math.isfinite(x)
    )


def problems(line: dict, wanted: dict[str, str], traced: bool) -> list[str]:
    """What is wrong with ``line``; ``wanted`` maps each metric the run has
    to report to its unit."""
    found = []
    if not isinstance(line, dict):
        return ["not a JSON object"]
    for key in REQUIRED:
        if key not in line:
            found.append(f"key {key!r} is missing")
    if found:
        return found
    if not isinstance(line["correct"], bool):
        found.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not (isinstance(line[key], int) and not isinstance(line[key], bool)
                and line[key] >= 0):
            found.append(f"{key} is not a count")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        found.append("metrics is not an object")
        metrics = {}
    for name, unit in wanted.items():
        m = metrics.get(name)
        if m is None:
            found.append(f"metric {name!r} is missing")
        elif not (isinstance(m, dict) and "value" in m and "unit" in m):
            found.append(f"metric {name!r} lacks value or unit")
        elif not _number(m["value"]):
            found.append(f"metric {name!r} has no finite number")
        elif m["unit"] != unit:
            found.append(f"metric {name!r} has unit {m['unit']!r}, not {unit!r}")
    for name, m in metrics.items():
        if name not in wanted and not (
            isinstance(m, dict) and _number(m.get("value"))
            and isinstance(m.get("unit"), str)
        ):
            found.append(f"metric {name!r} lacks value or unit")
    device = line["device"]
    if not isinstance(device, dict):
        return found + ["device is not an object"]
    for key in DEVICE_KEYS:
        if key not in device:
            found.append(f"device.{key} is missing")
    if not (isinstance(device.get("count"), int) and device.get("count", 0) > 0):
        found.append("device.count is not a positive count")
    if "memory_peak_bytes" in device and not (
        _number(device["memory_peak_bytes"]) and device["memory_peak_bytes"] >= 0
    ):
        found.append("device.memory_peak_bytes is not a count of bytes")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not (_number(busy) and _number(window)):
            found.append("device.busy_s and device.window_s are not both numbers")
        elif not 0 < busy <= window:
            found.append(
                f"device.busy_s {busy} is outside (0, window_s {window}]"
            )
    if "breakdown" in line:
        b = line["breakdown"]
        for key in ("device_ops", "idle_gaps"):
            rows = b.get(key, []) if isinstance(b, dict) else None
            if not isinstance(rows, list) or len(rows) > 10 or any(
                not (isinstance(r, list) and len(r) == 2
                     and isinstance(r[0], str) and _number(r[1]))
                for r in rows
            ):
                found.append(f"breakdown.{key} is not at most 10 [name, seconds]")
    return found


def build(*, correct: bool, attempted: int, failed: int, metrics: dict,
          device: dict, wanted: dict[str, str], traced: bool,
          breakdown: dict | None = None, compared: dict | None = None) -> str:
    """The line as text, or ``Malformed``.  ``metrics`` maps names to
    ``(value, unit)``; ``compared`` goes last."""
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            k: {"value": v, "unit": u} for k, (v, u) in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = compared
    found = problems(line, wanted, traced)
    if found:
        raise Malformed("; ".join(found))
    text = json.dumps(line)
    if "\n" in text:
        raise Malformed("the line spans more than one line")
    return text


def check_text(text: str, wanted: dict[str, str], traced: bool) -> list[str]:
    """Problems of a line as printed (what the driver reads)."""
    try:
        line = json.loads(text)
    except ValueError as e:
        return [f"not JSON: {e}"]
    return problems(line, wanted, traced)
