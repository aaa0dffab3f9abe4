"""Host-side partial-aggregation stripe for the ``partial_merge`` device
strategy.

The streaming window operator can ship every decoded row to the device
(``scatter`` / ``pallas_dense``) or reduce each batch on the host first and
ship only sufficient statistics (this module).  The host keeps a *stripe*:
per-(slide-unit, sub, group) accumulators covering the slide units touched
since the last device merge.  ``flush()`` hands the stripe to the device
merge op (:func:`denormalized_tpu.ops.segment_agg.merge_partials`) which
folds it into the HBM window ring — sliding fan-out happens there, so the
host never replicates rows per overlapping window.

This is the Partial/Final split of the reference
(planner/streaming_window.rs:133-153) applied across the host↔accelerator
boundary: the right architecture whenever the link to the accelerator is
narrow relative to the ingest rate — partials scale with group cardinality
and window span, not with row count.

The hot loop is the native single-pass reducer ``native/partial_agg.cpp``;
a vectorized numpy fallback keeps no-compiler environments working.
"""

from __future__ import annotations

import ctypes

import numpy as np

from denormalized_tpu.ops import segment_agg as sa

_LIB = None
_LIB_TRIED = False


def _native():
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB_TRIED = True
        try:
            from denormalized_tpu.native.build import load

            lib = load("partial_agg")
            lib.partial_window_agg.restype = ctypes.c_int64
            lib.partial_window_agg.argtypes = [
                ctypes.c_void_p,  # win_rel int64
                ctypes.c_void_p,  # sub uint8 | NULL
                ctypes.c_void_p,  # gid int32
                ctypes.c_void_p,  # values f64
                ctypes.c_void_p,  # colvalid uint8 | NULL
                ctypes.c_int64,   # n
                ctypes.c_int32,   # V
                ctypes.c_int32,   # U
                ctypes.c_int32,   # SUB
                ctypes.c_int32,   # G
                ctypes.c_void_p,  # row_cnt int64
                ctypes.c_void_p,  # cnt int64
                ctypes.c_void_p,  # sum f64
                ctypes.c_void_p,  # mn f64
                ctypes.c_void_p,  # mx f64
            ]
            _LIB = lib
        except Exception as e:  # dnzlint: allow(broad-except) numpy partial-agg is the designed fallback on no-compiler boxes; logged so the downgrade is visible, gated by test_native_build_gate where g++ exists
            from denormalized_tpu.runtime.tracing import logger

            logger.warning(
                "native partial_agg unavailable (%s: %s) — host partial "
                "aggregation runs the numpy path",
                type(e).__name__, e,
            )
            _LIB = None
    return _LIB


def _ptr(a: np.ndarray | None):
    return None if a is None else a.ctypes.data_as(ctypes.c_void_p)


# fold-neutral int32 bit patterns for min/max planes in the DENSE packed
# layout (which has no validity mask): shared by the real pack and the
# prewarm no-op so the two can never diverge
NEUTRAL_BITS = {
    "min": np.float32(np.inf).view(np.int32),
    "max": np.float32(-np.inf).view(np.int32),
}


class HostPartialStripe:
    """Accumulates per-(slide-unit, sub, group) partials between device
    merges.

    ``u_base`` is the absolute slide index of stripe row 0; rows hold units
    ``u_base .. u_base + U - 1``.  ``SUB`` is 2 when ``length % slide != 0``
    (rows near the end of a unit belong to one fewer window — see
    partial_agg.cpp), else 1.
    """

    # stripe capacity in slide units; a span wider than this forces a flush
    U_MAX = 16

    def __init__(self, spec: sa.WindowKernelSpec, group_capacity: int):
        self.spec = spec
        self.G = group_capacity
        self.V = max(spec.num_value_cols, 1)
        self.SUB = 1 if spec.length_ms % spec.slide_ms == 0 else 2
        self.u_base: int | None = None
        self.u_hi = 0  # highest stripe-relative unit written (span - 1)
        self.rows = 0
        # True once ANY value column in this stripe had a null: decides
        # between the lean packed layout (per-column count planes aliased
        # to the row-count plane — valid because no-null means they are
        # equal) and the full layout
        self.nulls_seen = False
        self._alloc()

    def _alloc(self):
        U, S, G, V = self.U_MAX, self.SUB, self.G, self.V
        self.row_cnt = np.zeros((U, S, G), np.int64)
        self.cnt = np.zeros((V, U, S, G), np.int64)
        self.sum = np.zeros((V, U, S, G), np.float64)
        self.mn = np.full((V, U, S, G), np.inf)
        self.mx = np.full((V, U, S, G), -np.inf)

    # -- ingestion -----------------------------------------------------
    def add_batch(
        self,
        units: np.ndarray,      # (n) int64 absolute slide indices
        rem: np.ndarray,        # (n) int32 ts - unit*slide
        gid: np.ndarray,        # (n) int32
        values64: np.ndarray,   # (n, V) f64
        colvalid: np.ndarray | None,  # (n, V) bool or None (all valid)
        keep: np.ndarray | None,      # (n) bool rows to fold (None = all)
    ) -> None:
        n = len(units)
        if n == 0:
            return
        if keep is not None and not keep.all():
            units = units[keep]
            rem = rem[keep]
            gid = gid[keep]
            values64 = values64[keep]
            if colvalid is not None:
                colvalid = colvalid[keep]
            n = len(units)
            if n == 0:
                return
        if colvalid is not None and not self.nulls_seen and not colvalid.all():
            self.nulls_seen = True
        if self.u_base is None:
            self.u_base = int(units.min())
        # units is int64 (accumulate() normalizes), so the subtraction
        # already yields a fresh contiguous int64 array — no astype copy
        rel = units - self.u_base
        self.u_hi = max(self.u_hi, int(rel.max()))
        sub = None
        if self.SUB == 2:
            # rows with rem >= L - (k-1)*S miss the oldest overlapping
            # window (see partial_agg.cpp header)
            edge = self.spec.length_ms - (self.spec.length_units - 1) * self.spec.slide_ms
            sub = (np.asarray(rem) >= edge).astype(np.uint8)
        lib = _native()
        if lib is not None:
            rel = np.ascontiguousarray(rel, np.int64)
            gid_c = np.ascontiguousarray(gid, np.int32)
            vals_c = np.ascontiguousarray(values64, np.float64)
            cv = (
                None
                if colvalid is None
                else np.ascontiguousarray(colvalid, np.uint8)
            )
            lib.partial_window_agg(
                _ptr(rel), _ptr(sub), _ptr(gid_c), _ptr(vals_c), _ptr(cv),
                n, self.V, self.U_MAX, self.SUB, self.G,
                _ptr(self.row_cnt), _ptr(self.cnt), _ptr(self.sum),
                _ptr(self.mn), _ptr(self.mx),
            )
        else:
            self._add_numpy(rel, sub, gid, values64, colvalid)
        self.rows += n

    def _add_numpy(self, rel, sub, gid, values64, colvalid):
        """Vectorized fallback: bincount for counts/sums, sort+reduceat for
        extrema."""
        ok = (rel >= 0) & (rel < self.U_MAX) & (gid >= 0) & (gid < self.G)
        rel = rel[ok]
        gid = np.asarray(gid)[ok]
        vals = values64[ok]
        s = (sub[ok].astype(np.int64) if sub is not None else 0)
        cell = (rel * self.SUB + s) * self.G + gid
        cells = self.U_MAX * self.SUB * self.G
        self.row_cnt.reshape(-1)[:] += np.bincount(cell, minlength=cells)
        cv = colvalid[ok] if colvalid is not None else None
        order = np.argsort(cell, kind="stable")
        cell_s = cell[order]
        for v in range(self.V):
            x = vals[:, v]
            m = cv[:, v] if cv is not None else None
            cm = cell if m is None else cell[m]
            xm = x if m is None else x[m]
            self.cnt[v].reshape(-1)[:] += np.bincount(cm, minlength=cells)
            self.sum[v].reshape(-1)[:] += np.bincount(
                cm, weights=xm, minlength=cells
            )
            xs = x[order]
            ms = None if m is None else m[order]
            if ms is not None:
                cs2, xs2 = cell_s[ms], xs[ms]
            else:
                cs2, xs2 = cell_s, xs
            if len(cs2):
                starts = np.flatnonzero(np.r_[True, cs2[1:] != cs2[:-1]])
                mins = np.minimum.reduceat(xs2, starts)
                maxs = np.maximum.reduceat(xs2, starts)
                uc = cs2[starts]
                flat_mn = self.mn[v].reshape(-1)
                flat_mx = self.mx[v].reshape(-1)
                flat_mn[uc] = np.minimum(flat_mn[uc], mins)
                flat_mx[uc] = np.maximum(flat_mx[uc], maxs)

    # -- hand-off ------------------------------------------------------
    def is_empty(self) -> bool:
        return self.rows == 0

    def _component_plane(self, c: sa.AggComponent) -> np.ndarray:
        if c.kind == "count" and c.col is None:
            return self.row_cnt
        if c.kind == "count":
            return self.cnt[c.col]
        if c.kind == "sum":
            return self.sum[c.col]
        if c.kind == "min":
            return self.mn[c.col]
        if c.kind == "max":
            return self.mx[c.col]
        raise ValueError(c.kind)

    # counts per cell are shipped as exact-in-f32 integers, so a stripe
    # may never exceed 2^24 rows between merges (backend flushes earlier)
    MAX_STRIPE_ROWS = 1 << 24
    # cap on U*SUB*G cells per stripe: bounds the compacted-transfer
    # bucket so high-cardinality stripes converge on ONE compiled merge
    # program instead of walking a ladder of pow2 sizes
    MAX_STRIPE_CELLS = 1 << 19

    def transfer_buckets(self) -> list[int]:
        """The FIXED set of padded transfer sizes this stripe will ever
        use: {1024, bound/4, bound/2, bound} (deduped, pow2) where bound
        covers the largest possible active-cell count.  A fixed spec-
        derived set — instead of pow2-of-observed-A — means every merge
        program can be compiled at construction: observed sizes vary with
        pacing, and an unseen size mid-stream is a multi-second compile."""
        # at least one slide unit's worth of cells: the backend chunks
        # batches so a stripe never exceeds max(one unit, the cell cap)
        bound_cells = min(
            max(self.MAX_STRIPE_CELLS, self.G * self.SUB),
            self.G * self.SUB * self.U_MAX,
        )
        bound = 1 << max(0, (bound_cells - 1)).bit_length()
        out = sorted({1024, max(1024, bound // 4), max(1024, bound // 2), bound})
        return out

    def take_packed(
        self, base_mod: int
    ) -> tuple[np.ndarray, int, int, bool, bool] | None:
        """Compact the stripe into the single int32 matrix the device
        merge op consumes, then reset.

        Returns ``(packed, a_pad, u_base, lean, dense)`` or None when
        empty — ``lean`` says per-column count planes were omitted
        (null-free stripe; the device merge aliases them to the row-count
        plane).  ``packed`` is **int32** — an int32 carrier is immune to
        jnp's x64-off canonicalization, which would silently round an f64
        matrix to f32 and corrupt cell indices beyond 2^24.  Value planes
        are f32 bitcast to int32: one plane per count/min/max component
        (counts are exact in f32 under the MAX_STRIPE_ROWS cap) and TWO
        planes per sum — the f64 host sum split into (hi, lo) f32 so no
        precision is lost in transit.  ``u_base`` and ``base_mod`` ride in
        the two tail slots of row 0.  One matrix → ONE host→device
        transfer per merge.

        Two layouts, chosen per stripe by exact transferred-byte count:

        * **compact** (``dense=False``): ``(P + 1, a_pad + 2)`` — row 0
          holds the active flat cell indices ``((u*SUB)+s)*G + g``
          (pad = −1), value planes follow.  Wins when active cells are
          sparse in the stripe's span.
        * **dense** (``dense=True``): ``(P, a_pad + 2)`` — NO index row;
          cell i is flat index i over the first ``used`` units, pad cells
          carry fold-neutral values (count 0, sum 0, min +inf, max −inf).
          Wins at high density (e.g. 100K live keys in a 131072-wide
          ring: 4 planes × active vs 3 planes × span), and skips the
          host-side gather entirely."""
        if self.rows == 0:
            return None
        used = self.u_hi + 1
        active = np.flatnonzero(self.row_cnt[:used].reshape(-1) > 0)
        A = len(active)
        # lean layout: a null-free stripe's per-column counts equal the
        # row count cell-for-cell, so their planes need not cross the
        # link — the device merge aliases them to the row-count plane
        lean = not self.nulls_seen and sa.lean_possible(self.spec)
        n_planes = self.n_planes(lean)
        # smallest member of the FIXED bucket set that covers A (see
        # transfer_buckets — all merge programs precompiled); the backend's
        # chunking keeps A within the largest bucket, but never crash the
        # stream if an invariant slips — pay a one-off compile instead
        buckets = self.transfer_buckets()
        a_pad = next(
            (b for b in buckets if b >= A),
            1 << (A - 1).bit_length(),
        )
        cells_d = used * self.SUB * self.G
        a_pad_d = next((b for b in buckets if b >= cells_d), None)
        # dense only when a precompiled bucket covers the span AND it
        # moves fewer bytes than compact (index row included)
        if a_pad_d is not None and n_planes * a_pad_d < (n_planes + 1) * a_pad:
            return self._take_packed_dense(
                base_mod, used, a_pad_d, lean, n_planes
            )
        rows: list[np.ndarray] = []
        for c in self.spec.components:
            if c.kind == "sumc":
                continue
            if lean and sa.lean_skippable(c):
                continue
            src = self._component_plane(c)[:used].reshape(-1)[active]
            if c.kind == "sum":
                hi, lo = self._split_sum(src)
                rows.append(hi)
                rows.append(lo)
            else:
                rows.append(
                    np.ascontiguousarray(src, np.float64)
                    .astype(np.float32)
                    .view(np.int32)
                )
        packed = np.zeros((len(rows) + 1, a_pad + 2), np.int32)
        packed[0, :A] = active
        packed[0, A:a_pad] = -1
        packed[0, a_pad] = self.u_base
        packed[0, a_pad + 1] = base_mod
        for i, r in enumerate(rows):
            packed[i + 1, :A] = r
        u_base = self._reset_after_take(used)
        return packed, a_pad, u_base, lean, False

    def n_planes(self, lean: bool) -> int:
        """Value planes in a packed stripe of this spec: two per sum
        (hi/lo split), one per other component; lean omits per-column
        count planes (aliased to row count device-side)."""
        return sum(
            2 if c.kind == "sum" else 1
            for c in self.spec.components
            if c.kind != "sumc" and not (lean and sa.lean_skippable(c))
        )

    def dense_noop(self, a_pad: int, lean: bool) -> np.ndarray:
        """An all-padding DENSE packed matrix (for merge-program prewarm):
        every cell fold-neutral — count/sum planes zero, min/max planes
        +inf/−inf bit patterns.  Must stay in lockstep with
        ``_take_packed_dense``'s plane order (it is derived from the same
        component walk)."""
        packed = np.zeros((self.n_planes(lean), a_pad + 2), np.int32)
        pi = 0
        for c in self.spec.components:
            if c.kind == "sumc" or (lean and sa.lean_skippable(c)):
                continue
            if c.kind == "sum":
                pi += 2
                continue
            if c.kind in NEUTRAL_BITS:
                packed[pi, :a_pad] = NEUTRAL_BITS[c.kind]
            pi += 1
        return packed

    def _split_sum(self, src: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(hi, lo) f32 split of a host f64 sum plane, int32-bitcast —
        exact for f32 accumulators, ~1e-14 relative for f64 ones (a TPU
        has no native f64, so raw-bit f64 transport is not portable)."""
        # overflow-to-inf in the cast and inf - inf below are deliberate
        # (handled by the nonfin branch); suppress the spurious
        # RuntimeWarnings
        with np.errstate(invalid="ignore", over="ignore"):
            hi = src.astype(np.float32)
            lo = (src - hi.astype(np.float64)).astype(np.float32)
        # a finite f64 sum beyond f32 range becomes (±inf, ∓inf) and would
        # fold to NaN; ±inf parity with an overflowed f32 accumulator is
        # right for f32 state, but an f64 accumulator would have held the
        # value — refuse loudly rather than corrupt it
        nonfin = ~np.isfinite(hi)
        if nonfin.any():
            over = nonfin & np.isfinite(src)
            if over.any() and self.spec.accum_dtype == sa.jnp.float64:
                raise OverflowError(
                    "partial_merge cannot transport f64 sums "
                    "beyond float32 range (~3.4e38); use "
                    "device_strategy='scatter' for this workload"
                )
            # overflow (finite src) and genuine ±inf/NaN sums both leave
            # lo meaningless (inf - inf = NaN): zero it so the device fold
            # yields ±inf/NaN parity with the scatter path instead of
            # poisoning cells with NaN
            lo[nonfin] = 0.0
        return hi.view(np.int32), lo.view(np.int32)

    def _take_packed_dense(
        self, base_mod: int, used: int, a_pad: int, lean: bool, n_planes: int
    ) -> tuple[np.ndarray, int, int, bool, bool]:
        """Dense (index-free) pack: plane p at row p, cell i = flat index
        i over the first ``used`` units, pad cells fold-neutral.  No host
        gather — straight reshape + dtype conversion."""
        cells = used * self.SUB * self.G
        packed = np.zeros((n_planes, a_pad + 2), np.int32)
        pi = 0
        for c in self.spec.components:
            if c.kind == "sumc":
                continue
            if lean and sa.lean_skippable(c):
                continue
            src = self._component_plane(c)[:used].reshape(-1)
            if c.kind == "sum":
                hi, lo = self._split_sum(src)
                packed[pi, :cells] = hi
                packed[pi + 1, :cells] = lo
                pi += 2
                continue
            packed[pi, :cells] = (
                np.ascontiguousarray(src, np.float64)
                .astype(np.float32)
                .view(np.int32)
            )
            if c.kind in NEUTRAL_BITS and cells < a_pad:
                packed[pi, cells:a_pad] = NEUTRAL_BITS[c.kind]
            pi += 1
        packed[0, a_pad] = self.u_base
        packed[0, a_pad + 1] = base_mod
        u_base = self._reset_after_take(used)
        return packed, a_pad, u_base, lean, True

    def _reset_after_take(self, used: int) -> int:
        """Shared post-pack stripe reset; returns the taken u_base."""
        u_base = self.u_base
        self.u_base = None
        self.u_hi = 0
        self.rows = 0
        # reset in place, touching only the unit rows this stripe used:
        # re-zeroing the full (V, U_MAX, SUB, G) planes costs ~100ms per
        # flush at 100K-key cardinality, while a stripe typically spans
        # 1-2 slide units
        self.row_cnt[:used] = 0
        self.cnt[:, :used] = 0
        self.sum[:, :used] = 0.0
        self.mn[:, :used] = np.inf
        self.mx[:, :used] = -np.inf
        self.nulls_seen = False
        return u_base
