"""The window operator's batch time arithmetic: event times → slide units,
remainders and the few numbers ``StreamingWindowExec._process_batch``
decides by (the batch's extremes, how many rows are late, whether a kept
row reaches back into a closable window).

One algorithm, two implementations held bit-equal by
``tests/test_window_project_native.py``.  The native one
(``native/partial_agg.cpp``) is two passes: :meth:`~WindowProjector.units`
scans the timestamps once; once the operator has settled ``first_open``
and the closable count from that pass's extremes,
:meth:`~WindowProjector.rebase` scans the units once.  Both calls release
the interpreter lock.  The NumPy body runs where the library is missing,
the timestamps are not a contiguous int64 array or the slide is not
positive.  ``native_batches`` counts the batches whose timestamps the
native pass took (``project_native_batches`` in the operator's
``metrics()``).
"""

from __future__ import annotations

import numpy as np

from denormalized_tpu.ops.host_partial import _native


class WindowProjector:
    """``reuse_buffers``: write the native passes' outputs into buffers this
    object keeps, sized to the largest batch seen, instead of fresh arrays —
    a batch's arrays are then valid until the next batch is projected.  The
    operator asks for it only where a batch is folded before the next one
    arrives (a host-reducing backend without ``host_pipeline``): the
    pipeline's worker may still be reading batch k while k+1 is projected,
    and a row-shipping backend hands the arrays to an asynchronous device
    program, so both get fresh arrays."""

    __slots__ = (
        "slide_ms", "span", "native_batches", "_lib", "_reuse", "_stats",
        "_units", "_rem", "_win_rel", "_keep",
    )

    def __init__(self, slide_ms: int, length_units: int, reuse_buffers: bool):
        self.slide_ms = int(slide_ms)
        # windows a unit reaches back into, besides its own
        self.span = int(length_units) - 1
        self.native_batches = 0
        # False until the first batch: the library is loaded (on a fresh
        # checkout: built) where the host reducer's is, not at plan time
        self._lib = False
        self._reuse = reuse_buffers
        self._stats = np.zeros(3, np.int64)
        self._units = self._win_rel = np.empty(0, np.int64)
        self._rem = np.empty(0, np.int32)
        self._keep = np.empty(0, np.bool_)

    def _buf(self, name: str, n: int) -> np.ndarray:
        buf = getattr(self, name)
        if not self._reuse:
            return np.empty(n, buf.dtype)
        if n > len(buf):
            buf = np.empty(max(n, 2 * len(buf)), buf.dtype)
            setattr(self, name, buf)
        return buf[:n]

    def _library(self):
        if self._lib is False:
            self._lib = _native()
        return self._lib

    def units(self, ts) -> tuple[np.ndarray, np.ndarray, int, int, int]:
        """``(units, rem, u_min, u_max, ts_min)`` of a non-empty batch's
        event times: ``units`` int64 = floor(ts / slide), ``rem`` int32 =
        ts − units·slide, and the least unit, greatest unit and least event
        time as Python ints."""
        lib = self._library()
        n = len(ts)
        if (
            lib is not None
            and self.slide_ms > 0
            and isinstance(ts, np.ndarray)
            and ts.dtype == np.int64
            and ts.ndim == 1
            and ts.flags.c_contiguous
        ):
            units = self._buf("_units", n)
            rem = self._buf("_rem", n)
            stats = self._stats
            lib.window_project_units(
                ts.ctypes.data, n, self.slide_ms, units.ctypes.data,
                rem.ctypes.data, stats.ctypes.data,
            )
            self.native_batches += 1
            return units, rem, int(stats[0]), int(stats[1]), int(stats[2])
        ts = np.asarray(ts, dtype=np.int64)
        units, rem64 = np.divmod(ts, self.slide_ms)
        return (
            units, rem64.astype(np.int32), int(units.min()),
            int(units.max()), int(ts.min()),
        )

    def rebase(
        self, units: np.ndarray, u_min: int, first: int, closable: int,
        mask: bool = True,
    ) -> tuple[np.ndarray, int, int, bool, np.ndarray | None]:
        """``(win_rel, n_late, n_behind, straddle, keep)`` of ``units``
        (as :meth:`units` returned them, ``u_min`` their least) against the
        operator's lowest open window ``first`` and the ``closable`` >= 0
        windows the watermark has closed from there: ``win_rel`` = units −
        first; rows with ``win_rel < 0``; rows with ``win_rel < closable``
        (what a host-reducing backend drops); whether a kept row reaches
        back into a closable window (``win_rel − span < closable``, and
        there is one); and ``keep`` = ``win_rel >= closable``, or None
        where no row is dropped or the caller wants no ``mask`` (a
        row-shipping backend drops by ``win_rel`` itself)."""
        n = len(units)
        lib = self._library()
        if (
            lib is not None
            and units.dtype == np.int64
            and units.flags.c_contiguous
        ):
            win_rel = self._buf("_win_rel", n)
            # the least unit says whether a row will be dropped: the mask
            # is written only then
            keep = (
                self._buf("_keep", n)
                if mask and u_min - first < closable else None
            )
            stats = self._stats
            lib.window_project_rebase(
                units.ctypes.data, n, first, closable, self.span,
                win_rel.ctypes.data,
                None if keep is None else keep.ctypes.data,
                stats.ctypes.data,
            )
            return win_rel, int(stats[0]), int(stats[1]), bool(stats[2]), keep
        win_rel = units - first
        n_late = int((win_rel < 0).sum())
        keep = win_rel >= closable
        straddle = closable > 0 and bool(
            (keep & (win_rel - self.span < closable)).any()
        )
        n_behind = n - int(keep.sum())
        return (
            win_rel, n_late, n_behind, straddle,
            keep if mask and n_behind else None,
        )
