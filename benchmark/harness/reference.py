"""The plain reference, and the comparison that decides ``correct``.

``Reference`` is ``chip_smoke.py``'s (copied): a numpy f64 fold of seeded
events into every window they touch.  Here it folds one *span* of event
time, made again from the seed chunk by chunk (``events.chunk_arrays``), and
answers for the windows that lie wholly inside the span.  It imports nothing
of the engine and takes nothing the engine made.

``compare`` holds the rows the engine delivered for those windows against
it and returns numbers, each with a limit of its own (``LIMITS``); PERF.md
gives the readings every limit was set from.  ``verdict`` holds every
deployment's numbers to ``LIMITS``, and a number ``LIMITS`` does not name
to the limit its deployment module gives it.

``bf16_fold`` is the control: the same fold with readings, partial sums and
the running sum held in bfloat16, the precision below the float32 the
configurations state.  Put in the engine's place it has to come out not
correct.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import events

# name -> limit; a number above its limit makes the run not correct.
# Exact comparisons have the limit 0; rel_err_max from measured readings.
LIMITS = {
    "missing_rows": 0,
    "unexpected_rows": 0,
    "count_mismatch": 0,
    "minmax_mismatch": 0,
    "rel_err_max": 2e-5,
    "windows_undelivered": 0,
    "late_rows": 0,
    "decode_fallback_rows": 0,
}
# what a comparison counts beside its numbers: no limit, never compared
# (``bad_windows`` goes to the line's ``failed``)
TALLIES = ("rows_compared", "blocks_compared", "bad_windows")
# |avg - threshold| under this share of the threshold: f32 state may fall
# on either side of the filter, so such a row may be there or not
FILTER_BAND = 1e-4


class Reference:
    """f64 fold of the events of ``[start_ms, end_ms)``: a row at time t
    belongs to each window ``[j*slide, j*slide + length)`` that contains t.
    Cells are (window, key) pairs, flat-indexed."""

    def __init__(self, feed: events.Feed, length_ms: int, slide_ms: int,
                 start_ms: int, end_ms: int):
        self.feed, self.length, self.slide = feed, length_ms, slide_ms
        self.start_ms, self.end_ms = start_ms, end_ms
        self.n_keys = feed.n_keys
        self.chunks = events.chunks_covering(feed, start_ms, end_ms)
        parts = [events.chunk_arrays(feed, c) for c in self.chunks]
        self.ts = np.concatenate([p[0] for p in parts])
        self.kid = np.concatenate([p[1] for p in parts])
        self.micro = np.concatenate([p[2] for p in parts])
        self.reading = events.reading_of(self.micro)
        # rows per chunk, for folds that go chunk by chunk
        self.chunk_rows = [len(p[0]) for p in parts]
        unit = self.ts // slide_ms
        self.fan = -(-length_ms // slide_ms)
        self.w0 = -(-start_ms // slide_ms)  # first window wholly inside
        last = (end_ms - length_ms) // slide_ms
        self.n_windows = max(0, last - self.w0 + 1)
        rows, wins = [], []
        for i in range(self.fan):
            j = unit - i
            inside = (
                (self.ts < j * slide_ms + length_ms)
                & (j >= self.w0) & (j <= last)
            )
            rows.append(np.flatnonzero(inside))
            wins.append(j[inside])
        self._rows = np.concatenate(rows)
        self._cell = (
            (np.concatenate(wins) - self.w0) * self.n_keys
            + self.kid[self._rows]
        )
        self.n_cells = self.n_windows * self.n_keys
        self.rows_per_cell = np.bincount(self._cell, minlength=self.n_cells)
        self._fold: dict | None = None
        self._key_ids: dict | None = None

    def window_starts(self) -> np.ndarray:
        return (self.w0 + np.arange(self.n_windows)) * self.slide

    def fold(self) -> dict:
        """count/sum/min/max/avg per cell.  Readings are never null, so
        count == rows."""
        if self._fold is None:
            x = self.reading[self._rows]
            total = np.bincount(self._cell, weights=x, minlength=self.n_cells)
            lo = np.full(self.n_cells, np.inf)
            hi = np.full(self.n_cells, -np.inf)
            np.minimum.at(lo, self._cell, x)
            np.maximum.at(hi, self._cell, x)
            with np.errstate(invalid="ignore", divide="ignore"):
                avg = total / self.rows_per_cell
            self._fold = {
                "count": self.rows_per_cell.astype(np.float64), "sum": total,
                "min": lo, "max": hi, "avg": avg,
            }
        return self._fold

    def bf16_fold(self) -> dict:
        """The control: readings rounded to bfloat16, each chunk's partial
        sum rounded to bfloat16 and added into a bfloat16 running sum."""
        from ml_dtypes import bfloat16

        x = self.reading.astype(bfloat16).astype(np.float64)
        bounds = np.cumsum([0] + self.chunk_rows)
        chunk_of_row = np.searchsorted(bounds, self._rows, side="right") - 1
        acc = np.zeros(self.n_cells, bfloat16)
        for k in range(len(self.chunk_rows)):
            sel = chunk_of_row == k
            part = np.bincount(
                self._cell[sel], weights=x[self._rows[sel]],
                minlength=self.n_cells,
            )
            acc = (
                acc.astype(np.float32) + part.astype(bfloat16).astype(np.float32)
            ).astype(bfloat16)
        total = acc.astype(np.float64)
        xr = x[self._rows]
        lo = np.full(self.n_cells, np.inf)
        hi = np.full(self.n_cells, -np.inf)
        np.minimum.at(lo, self._cell, xr)
        np.maximum.at(hi, self._cell, xr)
        with np.errstate(invalid="ignore", divide="ignore"):
            avg = (total / self.rows_per_cell).astype(bfloat16).astype(np.float64)
        return {
            "count": self.rows_per_cell.astype(np.float64), "sum": total,
            "min": lo, "max": hi, "avg": avg,
        }

    def cells_of(self, ws: np.ndarray, keys) -> np.ndarray:
        """Flat cell index of delivered rows of this span's windows."""
        if self._key_ids is None:
            self._key_ids = {
                n: i for i, n in enumerate(self.feed.key_names().tolist())
            }
        kid = np.fromiter((self._key_ids[k] for k in keys), np.int64, len(ws))
        return (ws // self.slide - self.w0) * self.n_keys + kid


def rows_of(fold: dict, ref: Reference, aggs, flt) -> dict:
    """A fold put in the engine's place: the rows it would deliver, as
    ``{"cells": ..., <output column>: values}``."""
    keep = ref.rows_per_cell > 0
    if flt is not None:
        column, threshold = flt
        kind = dict(aggs)[column]
        keep &= fold[kind] > threshold
    cells = np.flatnonzero(keep)
    out = {"cells": cells}
    for name, kind in aggs:
        out[name] = fold[kind][cells]
    return out


def compare(ref: Reference, got: dict, aggs, flt) -> dict:
    """Numbers for one span.  ``got`` holds ``cells`` (flat indices of the
    delivered rows) and one array per output column; ``aggs`` is ``[(output
    column, kind)]``; ``flt`` is ``(output column, threshold)`` of the
    post-aggregation filter, or None."""
    want = ref.fold()
    cells = got["cells"]
    present = ref.rows_per_cell > 0
    optional = np.zeros(ref.n_cells, bool)
    if flt is not None:
        column, threshold = flt
        kind = dict(aggs)[column]
        with np.errstate(invalid="ignore"):
            optional = present & (
                np.abs(want[kind] - threshold) < FILTER_BAND * abs(threshold)
            )
            present = present & (want[kind] > threshold)
    seen = np.zeros(ref.n_cells, bool)
    seen[cells] = True
    missing = present & ~seen & ~optional
    unexpected = seen & ~present & ~optional
    out = {
        "rows_compared": int(len(cells)),
        "missing_rows": int(missing.sum()),
        "unexpected_rows": int(unexpected.sum()),
        "count_mismatch": 0, "minmax_mismatch": 0, "rel_err_max": 0.0,
    }
    bad = missing | unexpected
    for name, kind in aggs:
        g = np.asarray(got[name], dtype=np.float64)
        w = want[kind][cells]
        if kind == "count":
            off = g != w
            out["count_mismatch"] += int(off.sum())
        elif kind in ("min", "max"):
            off = g.astype(np.float32) != w.astype(np.float32)
            out["minmax_mismatch"] += int(off.sum())
        else:
            with np.errstate(invalid="ignore", divide="ignore"):
                rel = np.abs(g - w) / np.abs(w)
            rel = np.where(np.isfinite(rel), rel, np.inf)
            if len(rel):
                out["rel_err_max"] = max(out["rel_err_max"], float(rel.max()))
            off = rel > LIMITS["rel_err_max"]
        bad[cells[off]] = True
    # windows of this span with a row missing, unexpected or wrong
    out["bad_windows"] = int(len(np.unique(np.flatnonzero(bad) // ref.n_keys)))
    return out


def merge(numbers: list[dict]) -> dict:
    """Counts add up over spans; the widest gap is the widest of any."""
    out: dict = {}
    for n in numbers:
        for k, v in n.items():
            out[k] = max(out.get(k, 0.0), v) if k == "rel_err_max" else (
                out.get(k, 0) + v
            )
    return out


class Unlimited(ValueError):
    """A number that no limit holds: the run has no verdict."""


def verdict(numbers: dict, own_limits: dict | None = None) -> tuple[bool, dict]:
    """``correct``, and every number compared beside its limit: ``LIMITS``
    for the names it has, ``own_limits`` (the deployment module's) for the
    others.  A number that neither names, and that is no tally, raises
    ``Unlimited``: it would otherwise go unread."""
    limits = {**(own_limits or {}), **LIMITS}
    unlimited = sorted(set(numbers) - set(limits) - set(TALLIES))
    if unlimited:
        raise Unlimited(f"no limit holds the numbers {unlimited}")
    compared = {
        k: {"value": numbers[k], "limit": limits[k]}
        for k in [*LIMITS, *limits] if k in numbers
    }
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    return ok and numbers.get("rows_compared", 0) > 0, compared
