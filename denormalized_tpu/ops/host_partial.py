"""Host-side partial-aggregation stripe for the ``partial_merge`` device
strategy.

The streaming window operator can ship every decoded row to the device
(``scatter``) or reduce each batch on the host first and
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
from denormalized_tpu.runtime.tracing import NULL_CLOCK

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
                ctypes.c_void_p,  # units int64
                ctypes.c_int64,   # u_off: a row's stripe unit = unit - u_off
                ctypes.c_void_p,  # rem int32 | NULL (SUB == 1)
                ctypes.c_int64,   # edge: rem >= edge is sub 1
                ctypes.c_void_p,  # gid int32
                ctypes.c_void_p,  # values f64
                ctypes.c_void_p,  # colvalid uint8 | NULL
                ctypes.c_int64,   # n
                ctypes.c_int32,   # V
                ctypes.c_int32,   # U
                ctypes.c_int32,   # SUB
                ctypes.c_int32,   # G
                ctypes.c_void_p,  # rec f64 (cells, 1 + 4V)
                ctypes.c_void_p,  # touched int64
                ctypes.c_void_p,  # n_touched int64[1]
            ]
            lib.partial_pack_cells.restype = ctypes.c_int64
            lib.partial_pack_cells.argtypes = [
                ctypes.c_void_p,  # cells int64
                ctypes.c_int64,   # n
                ctypes.c_int64,   # cell_base
                ctypes.c_void_p,  # rec f64
                ctypes.c_int32,   # R
                ctypes.c_void_p,  # fields int32
                ctypes.c_void_p,  # split uint8
                ctypes.c_int32,   # n_fields
                ctypes.c_void_p,  # packed int32
                ctypes.c_int64,   # stride
                ctypes.c_void_p,  # neutral f64
            ]
            # the window operator's batch time arithmetic
            # (ops/window_project.py)
            lib.window_project_units.restype = None
            lib.window_project_units.argtypes = [
                ctypes.c_void_p,  # ts int64
                ctypes.c_int64,   # n
                ctypes.c_int64,   # slide_ms > 0
                ctypes.c_void_p,  # units int64 out
                ctypes.c_void_p,  # rem int32 out
                ctypes.c_void_p,  # stats int64[3] out: u_min, u_max, ts_min
            ]
            lib.window_project_rebase.restype = None
            lib.window_project_rebase.argtypes = [
                ctypes.c_void_p,  # units int64
                ctypes.c_int64,   # n
                ctypes.c_int64,   # first
                ctypes.c_int64,   # closable
                ctypes.c_int64,   # span
                ctypes.c_void_p,  # win_rel int64 out
                ctypes.c_void_p,  # keep uint8 out | NULL
                ctypes.c_void_p,  # stats int64[3] out: late, behind, straddle
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

    The partials live as ONE RECORD PER CELL (``rec``, a row of ``1 + 4V``
    f64: rows; per value column valid count, sum, min, max — the layout of
    partial_agg.cpp), so a row folds into one cache line, and what a flush
    costs follows the cells the stripe touched, not the ``G`` it could
    hold: the reducer notes every cell it writes for the first time
    (``_touched``); packing gathers those records and the reset rewrites
    them, and nothing else of the stripe is read or written.

    ``key_blocks``: into how many equal, contiguous blocks of group ids a
    packed unit is split — one a device of a key-sharded mesh, each device
    holding ``G / key_blocks`` groups of the ring.  The touched cells are
    ascending, so a block's share of a unit is one contiguous run of them
    (one run a sub-bucket), found by binary search; it is packed with ids
    local to the block, still ascending and distinct, and a device receives
    and folds its own share only.  One block is the whole unit.
    """

    # most slide units a stripe spans; a wider batch forces a flush
    U_MAX = 16
    # counts per cell are shipped as exact-in-f32 integers, so a stripe
    # may never exceed 2^24 rows between merges (backend flushes earlier)
    MAX_STRIPE_ROWS = 1 << 24
    # cap on the U*SUB*G cells a stripe allocates (40 bytes a cell and
    # value column: 20 MB at the cap), one slide unit at the least: host
    # memory follows the units a stripe really spans, not U_MAX units of
    # any G
    MAX_STRIPE_CELLS = 1 << 19
    # smallest padded transfer: below it a merge costs its dispatch
    MIN_BUCKET = 1024
    # the touched cells are put in order by a sort, or — from one touched
    # cell in MAP_RATIO of the span — by marking a byte a cell and reading
    # the marks back (a byte a cell of the span against ~log2(A) compares
    # a touched cell)
    MAP_RATIO = 256
    # header unit of a pack that must fold into nothing (prewarm no-ops, the
    # padding of the stack of a flush's dense units): far below any ring
    NOOP_UNIT = -(1 << 24)
    F64_OVERFLOW = (
        "partial_merge cannot transport f64 sums beyond float32 range "
        "(~3.4e38); use device_strategy='scatter' for this workload"
    )

    def __init__(
        self, spec: sa.WindowKernelSpec, group_capacity: int,
        key_blocks: int = 1,
    ):
        if group_capacity % key_blocks:
            raise ValueError(
                f"group capacity {group_capacity} is not divisible into "
                f"{key_blocks} key blocks"
            )
        self.spec = spec
        self.G = group_capacity
        self.blocks = key_blocks
        self.V = max(spec.num_value_cols, 1)
        self.SUB = 1 if spec.length_ms % spec.slide_ms == 0 else 2
        # rows with rem >= L - (k-1)*S miss the oldest overlapping window:
        # they are sub 1 (see partial_agg.cpp header)
        self._edge = spec.length_ms - (spec.length_units - 1) * spec.slide_ms
        self.unit_cells = self.SUB * self.G
        # a key block's groups, and a unit's cells in it: what a device
        # is sent at most
        self.G_block = self.G // key_blocks
        self.block_cells = self.SUB * self.G_block
        self.U = max(
            1, min(self.U_MAX, self.MAX_STRIPE_CELLS // self.unit_cells)
        )
        self._buckets = self.buckets_for(self.block_cells)
        self.u_base: int | None = None
        self.u_hi = 0  # highest stripe-relative unit written (span - 1)
        self.rows = 0
        # True once ANY value column in this stripe had a null: decides
        # between the lean packed layout (per-column count planes aliased
        # to the row-count plane — valid because no-null means they are
        # equal) and the full layout
        self.nulls_seen = False
        # work counters (docs/observability.md): cells with rows / cells
        # sent (padding included) / host bytes scanned and rewritten by
        # pack and reset / bytes of the packed matrices handed out, all
        # summed over the stripes taken so far; and the cells with rows
        # again, by the key block they fell in
        self.cells_active = 0
        self.cells_shipped = 0
        self.bytes_touched = 0
        self.bytes_packed = 0
        self.cells_by_block = np.zeros(key_blocks, np.int64)
        # what the device merges of those stripes fold: ring rows (one a
        # window a packed unit feeds: ``length_units`` of them away from
        # the ring's edges) and packed entries x the windows they are
        # folded into — the scatter's work
        self.window_folds = 0
        self.fold_entries = 0
        # a cell without rows holds the fold-neutral record
        self._neutral = np.array(
            [0.0] + [0.0, 0.0, np.inf, -np.inf] * self.V
        )
        self.rec = np.empty(
            (self.U * self.unit_cells, len(self._neutral)), np.float64
        )
        self.rec[:] = self._neutral
        # flat indices of the cells written since the last reset, each
        # once, in the order first met; grown to hold a batch more
        self._touched = np.empty(1 << 16, np.int64)
        self._n_touched = np.zeros(1, np.int64)
        self._dirty: list = []  # (index or slice, cells) still to reset

    #: the work counters above, as ``metrics()`` surfaces them (``stripe_<name>``)
    COUNTERS = (
        "cells_active", "cells_shipped", "bytes_touched", "bytes_packed",
    )
    #: and the two of the device's fold (``merge_<name>``)
    MERGE_COUNTERS = ("window_folds", "fold_entries")

    def carry_counters(self, old: "HostPartialStripe") -> None:
        """Take over the counts of the stripe this one replaces (capacity
        growth, restore), so they stay sums over the operator's life."""
        for name in self.COUNTERS + self.MERGE_COUNTERS:
            setattr(self, name, getattr(self, name) + getattr(old, name))
        if old.blocks == self.blocks:
            self.cells_by_block += old.cells_by_block

    def host_bytes(self) -> int:
        """Bytes of host memory the stripe's records hold."""
        return self.rec.nbytes

    # -- ingestion -----------------------------------------------------
    def add_batch(
        self,
        units: np.ndarray,      # (n) int64 absolute slide indices
        rem: np.ndarray,        # (n) int32 ts - unit*slide
        gid: np.ndarray,        # (n) int32
        values64: np.ndarray,   # (n, V) f64
        colvalid: np.ndarray | None,  # (n, V) bool or None (all valid)
        keep: np.ndarray | None,      # (n) bool rows to fold (None = all)
        u_min: int | None = None,     # least and greatest of ``units``,
        u_max: int | None = None,     # where the caller knows them
    ) -> None:
        """Fold a batch's rows into the stripe.  A caller that knows the
        extremes of ``units`` (the window operator does, from its one pass
        over the timestamps) hands them in and no array is scanned for
        them; they are of the whole batch, so a ``keep`` that drops rows
        has them found again."""
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
            u_min = u_max = None
            n = len(units)
            if n == 0:
                return
        if colvalid is not None and not self.nulls_seen and not colvalid.all():
            self.nulls_seen = True
        if u_min is None:
            u_min, u_max = int(units.min()), int(units.max())
        if self.u_base is None:
            self.u_base = u_min
        self.u_hi = max(self.u_hi, u_max - self.u_base)
        need = int(self._n_touched[0]) + n
        if need > len(self._touched):
            grown = np.empty(max(need, 2 * len(self._touched)), np.int64)
            grown[: self._n_touched[0]] = self._touched[: self._n_touched[0]]
            self._touched = grown
        lib = _native()
        if lib is not None:
            # the pass rebases each unit to the stripe and tells sub 0 from
            # sub 1 itself: no array of n is made here
            units_c = np.ascontiguousarray(units, np.int64)
            rem_c = (
                np.ascontiguousarray(rem, np.int32) if self.SUB == 2 else None
            )
            gid_c = np.ascontiguousarray(gid, np.int32)
            vals_c = np.ascontiguousarray(values64, np.float64)
            cv = (
                None
                if colvalid is None
                else np.ascontiguousarray(colvalid, np.uint8)
            )
            lib.partial_window_agg(
                _ptr(units_c), self.u_base, _ptr(rem_c), self._edge, _ptr(gid_c),
                _ptr(vals_c), _ptr(cv), n, self.V, self.U, self.SUB, self.G,
                _ptr(self.rec), _ptr(self._touched), _ptr(self._n_touched),
            )
        else:
            sub = (
                (np.asarray(rem) >= self._edge).astype(np.uint8)
                if self.SUB == 2 else None
            )
            self._add_numpy(
                np.asarray(units, np.int64) - self.u_base, sub, gid,
                values64, colvalid,
            )
        self.rows += n

    def _add_numpy(self, rel, sub, gid, values64, colvalid):
        """Vectorized fallback: one stable sort of the batch by cell, then
        ``reduceat`` per field over each cell's run of rows."""
        ok = (rel >= 0) & (rel < self.U) & (gid >= 0) & (gid < self.G)
        rel = rel[ok]
        gid = np.asarray(gid)[ok]
        vals = values64[ok]
        s = (sub[ok].astype(np.int64) if sub is not None else 0)
        cell = (rel * self.SUB + s) * self.G + gid
        order = np.argsort(cell, kind="stable")
        cell_s = cell[order]
        starts = np.flatnonzero(np.r_[True, cell_s[1:] != cell_s[:-1]])
        uc = cell_s[starts]  # the batch's distinct cells, ascending
        rec = self.rec
        fresh = uc[rec[uc, 0] == 0]
        nt = int(self._n_touched[0])
        self._touched[nt : nt + len(fresh)] = fresh
        self._n_touched[0] = nt + len(fresh)
        rec[uc, 0] += np.diff(np.r_[starts, len(cell_s)])
        cv = colvalid[ok] if colvalid is not None else None
        for v in range(self.V):
            xs = vals[:, v][order]
            if cv is None:
                cs2, xs2, st2, uc2 = cell_s, xs, starts, uc
            else:
                ms = cv[:, v][order]
                cs2, xs2 = cell_s[ms], xs[ms]
                st2 = np.flatnonzero(np.r_[True, cs2[1:] != cs2[:-1]])
                uc2 = cs2[st2]
            if len(cs2) == 0:
                continue
            f = 1 + 4 * v
            rec[uc2, f] += np.diff(np.r_[st2, len(cs2)])
            rec[uc2, f + 1] += np.add.reduceat(xs2, st2)
            rec[uc2, f + 2] = np.minimum(
                rec[uc2, f + 2], np.minimum.reduceat(xs2, st2)
            )
            rec[uc2, f + 3] = np.maximum(
                rec[uc2, f + 3], np.maximum.reduceat(xs2, st2)
            )

    # -- hand-off ------------------------------------------------------
    def is_empty(self) -> bool:
        return self.rows == 0

    def _field(self, c: sa.AggComponent) -> int:
        """Index of a component's field in a cell's record."""
        if c.kind == "count" and c.col is None:
            return 0
        return 1 + 4 * c.col + ("count", "sum", "min", "max").index(c.kind)

    @classmethod
    def buckets_for(cls, unit_cells: int) -> list[int]:
        out, b = [], cls.MIN_BUCKET
        while b < unit_cells:
            out.append(b)
            b *= 2
        return out

    def transfer_buckets(self) -> list[int]:
        """Every padded size a compact pack of this stripe can have: the
        powers of two from ``MIN_BUCKET`` up to the last one below a slide
        unit's cells in one key block (a pack is of ONE unit, every block
        of it padded alike, and one that would need a bucket as wide as
        the block goes dense — fewer bytes, see ``take_packed``).  A set
        fixed by the spec, so every merge program is compiled at
        construction: the sizes a run meets vary with its pacing, and an
        unseen one mid-stream is a compile.  Padding is under two cells a
        cell above ``MIN_BUCKET``."""
        return list(self._buckets)

    def layout_for(self, A: int, n_planes: int) -> tuple[int, bool]:
        """``(a_pad, dense)`` of a unit whose fullest key block has ``A``
        active cells: the compact bucket that covers them, or the dense
        layout (``a_pad`` = the block's cells) where that moves fewer
        bytes — the index row counted — or no bucket covers them."""
        a_pad = next((b for b in self._buckets if b >= A), None)
        if a_pad is None or (
            n_planes * self.block_cells < (n_planes + 1) * a_pad
        ):
            return self.block_cells, True
        return a_pad, False

    def _planes_walk(self, lean: bool):
        """(component, plane index) of the packed value planes, in order:
        the one walk the real packs and the prewarm no-ops share."""
        pi = 0
        for c in self.spec.components:
            if c.kind == "sumc" or (lean and sa.lean_skippable(c)):
                continue
            yield c, pi
            pi += 2 if c.kind == "sum" else 1

    def n_planes(self, lean: bool) -> int:
        """Value planes in a packed stripe of this spec: two per sum
        (hi/lo split), one per other component; lean omits per-column
        count planes (aliased to row count device-side)."""
        return sum(
            2 if c.kind == "sum" else 1 for c, _ in self._planes_walk(lean)
        )

    def take_packed(
        self, base_mod: int, clock=NULL_CLOCK
    ) -> list[tuple[np.ndarray, int, bool, bool]]:
        """Pack the stripe for the device merge op, one int32 matrix per
        slide unit with rows, then reset it (the native pack puts each
        record back to neutral in the pass that packs it).  ``clock`` (the
        operator's phase clock) gets both as ``flush_pack``.

        Returns ``[(packed, a_pad, lean, dense), ...]`` in unit order, empty
        for an empty stripe — ``lean`` says per-column count planes were
        omitted (null-free stripe; the device merge aliases them to the
        row-count plane).  ``packed`` is **int32** — an int32 carrier is
        immune to jnp's x64-off canonicalization, which would silently
        round an f64 matrix to f32.  Value planes are f32 bitcast to
        int32: one plane per count/min/max component (counts are exact in
        f32 under the MAX_STRIPE_ROWS cap) and TWO planes per sum — the
        f64 host sum split into (hi, lo) f32 so no precision is lost in
        transit.  The unit's index (relative to ``first_open``, as the
        operator handed units in) and ``base_mod`` ride in the two tail
        slots of row 0.  One matrix per unit; the backend sends each compact
        one on its own and a flush's dense ones stacked in one call.

        Two layouts, chosen per unit by transferred bytes:

        * **compact** (``dense=False``): ``(P + 1, a_pad + 2)`` — row 0
          holds the active cells' indices in the unit, ``s*G + g``,
          ascending (pad = −1), value planes follow; ``a_pad`` is the
          bucket of ``transfer_buckets`` that covers them.
        * **dense** (``dense=True``): ``(P, SUB*G + 2)`` — NO index row;
          cell i is index i of the unit, cells without rows carry
          fold-neutral values (count 0, sum 0, min +inf, max −inf).  Wins
          once most of a unit is active (e.g. 100K live keys in a ring
          131,072 wide: 5 planes × 131,072 against 6 × 131,072), and the
          device folds it without a scatter.

        With ``key_blocks > 1`` every matrix has a leading axis of that
        length: block ``b`` is the matrix of the unit's cells whose group
        lies in key block ``b``, with ``G / key_blocks`` in ``G``'s place
        (ids local to the block, the header in every block), all blocks
        padded to the ``a_pad`` that covers the fullest."""
        if self.rows == 0:
            return []
        with clock.phase("flush_pack"):
            out = self._pack(base_mod)
            self._reset()
        return out

    def _active_cells(self) -> np.ndarray:
        """Flat indices of the cells with rows — the touched list, put in
        ascending order."""
        nt = int(self._n_touched[0])
        cells = self._touched[:nt]
        span = (self.u_hi + 1) * self.unit_cells
        if nt * self.MAP_RATIO <= span:
            self.bytes_touched += nt * 8
            return np.sort(cells)
        marks = np.zeros(span, np.bool_)
        marks[cells] = True
        self.bytes_touched += nt * 8 + span
        return np.flatnonzero(marks)

    def _pack(self, base_mod: int) -> list[tuple[np.ndarray, int, bool, bool]]:
        used = self.u_hi + 1
        active = self._active_cells()
        self._dirty = []  # what _reset still has to put back to neutral
        # lean layout: a null-free stripe's per-column counts equal the
        # row count cell-for-cell, so their planes need not cross the
        # link — the device merge aliases them to the row-count plane
        lean = not self.nulls_seen and sa.lean_possible(self.spec)
        n_planes = self.n_planes(lean)
        B = self.blocks
        # where each (unit, sub, key block) starts among the active cells:
        # they are ascending, so a block's share of a sub-bucket is one run
        edges = (
            (np.arange(used)[:, None, None] * self.SUB
             + np.arange(self.SUB)[None, :, None]) * self.G
            + np.arange(B + 1)[None, None, :] * self.G_block
        )
        cuts = np.searchsorted(active, edges)  # (used, SUB, B + 1)
        out = []
        for u in range(used):
            lo, hi = int(cuts[u, 0, 0]), int(cuts[u, -1, -1])
            A = hi - lo
            if A == 0:
                continue
            by_block = np.diff(cuts[u], axis=1).sum(axis=0)
            a_pad, dense = self.layout_for(int(by_block.max()), n_planes)
            if dense:
                packed = self._pack_dense(u, lean, n_planes)
            else:
                packed = self._pack_compact(
                    u, active, cuts[u], a_pad, lean, n_planes
                )
            packed[:, 0, a_pad] = self.u_base + u
            packed[:, 0, a_pad + 1] = base_mod
            self._count_folds(self.u_base + u, A, int(cuts[u, 0, -1]) - lo)
            self.cells_active += A
            self.cells_by_block += by_block
            self.cells_shipped += B * a_pad
            self.bytes_packed += packed.nbytes
            out.append((self._handed_out(packed), a_pad, lean, dense))
        return out

    def _count_folds(self, u_rel: int, A: int, A_sub0: int) -> None:
        """Book what ``merge_partials_body`` does with a packed unit of
        ``A`` active cells, ``A_sub0`` of them in sub-bucket 0: the unit
        feeds windows ``u_rel - k + 1 .. u_rel``, those inside the ring
        take a fold each, and the oldest takes sub-bucket 0 alone where a
        unit has two."""
        k, W = self.spec.length_units, self.spec.window_slots
        for i in range(k):
            if 0 <= u_rel - i < W:
                self.window_folds += 1
                self.fold_entries += (
                    A_sub0 if self.SUB == 2 and i == k - 1 else A
                )

    def _handed_out(self, packed: np.ndarray) -> np.ndarray:
        """A matrix a key block as ``take_packed`` hands it out: the block
        axis stays only where there is more than one."""
        return packed if self.blocks > 1 else packed[0]

    def _f32_bits(self, c: sa.AggComponent, src: np.ndarray) -> list[np.ndarray]:
        """A component's cells as the int32-bitcast f32 rows it ships as:
        (hi, lo) for a sum, one row otherwise."""
        if c.kind == "sum":
            return list(self._split_sum(src))
        return [src.astype(np.float32).view(np.int32)]

    def _pack_compact(self, u, active, cuts, a_pad, lean, n_planes) -> np.ndarray:
        """Compact pack of unit ``u``, a matrix a key block: ``cuts[s, b]``
        is where sub-bucket ``s`` of block ``b`` starts in ``active``."""
        B, G_block = self.blocks, self.G_block
        packed = np.empty((B, n_planes + 1, a_pad + 2), np.int32)
        walk = list(self._planes_walk(lean))
        lib = _native()
        if lib is not None:
            fields = np.array([self._field(c) for c, _ in walk], np.int32)
            split = np.array([c.kind == "sum" for c, _ in walk], np.uint8)
        overflowed = 0
        for b in range(B):
            at = 0  # cells of this block packed so far
            for s in range(self.SUB):
                run = active[int(cuts[s, b]) : int(cuts[s, b + 1])]
                n = len(run)
                if n == 0:
                    continue
                # a cell's id in the block: s * G_block + (g - b * G_block)
                base = (
                    u * self.unit_cells + s * (self.G - G_block) + b * G_block
                )
                if lib is not None:
                    # one pass over the records: pack them and put them
                    # back to neutral (nothing is left for _reset to do
                    # for these cells)
                    into = packed[b, :, at:]
                    overflowed += lib.partial_pack_cells(
                        _ptr(run), n, base, _ptr(self.rec),
                        self.rec.shape[1], _ptr(fields), _ptr(split),
                        len(walk), _ptr(into), packed.shape[2],
                        _ptr(self._neutral),
                    )
                    self.bytes_touched += 2 * n * self.rec.shape[1] * 8
                else:
                    packed[b, 0, at : at + n] = run - base
                    recs = self.rec[run]  # one gather: the active records
                    for c, pi in walk:
                        for k, row in enumerate(
                            self._f32_bits(c, recs[:, self._field(c)])
                        ):
                            packed[b, 1 + pi + k, at : at + n] = row
                    self.bytes_touched += recs.nbytes
                    self._dirty.append((run, n))
                at += n
            packed[b, :, at:] = 0
            packed[b, 0, at:a_pad] = -1
        if overflowed and self.spec.accum_dtype == sa.jnp.float64:
            raise OverflowError(self.F64_OVERFLOW)
        return packed

    def _by_block(self, row: np.ndarray) -> np.ndarray:
        """A dense plane of a unit, ``(SUB*G,)``, as ``(blocks, SUB*G /
        blocks)``: row ``b`` holds key block ``b``'s groups of every
        sub-bucket (a view where there is one block)."""
        return row.reshape(self.SUB, self.blocks, self.G_block).transpose(
            1, 0, 2
        ).reshape(self.blocks, self.block_cells)

    def _pack_dense(self, u, lean, n_planes) -> np.ndarray:
        """Dense (index-free) pack of unit ``u``: plane p at row p, cell i
        = index i of the unit (of its key block).  No host gather — a
        strided read of each field; cells without rows already hold the
        fold-neutral values."""
        cells, bc = self.unit_cells, self.block_cells
        unit = self.rec[u * cells : (u + 1) * cells]
        packed = np.empty((self.blocks, n_planes, bc + 2), np.int32)
        packed[:, :, bc:] = 0
        for c, pi in self._planes_walk(lean):
            for k, row in enumerate(self._f32_bits(c, unit[:, self._field(c)])):
                packed[:, pi + k, :bc] = self._by_block(row)
        self.bytes_touched += unit.nbytes
        self._dirty.append((slice(u * cells, (u + 1) * cells), cells))
        return packed

    def _noop(self, packed: np.ndarray, a_pad: int) -> np.ndarray:
        """``packed`` (blocks, rows, a_pad + 2) addressed to no window, in
        the shape ``take_packed`` hands out."""
        packed[:, 0, a_pad] = self.NOOP_UNIT
        return self._handed_out(packed)

    def dense_noop(self, lean: bool) -> np.ndarray:
        """An all-neutral DENSE packed matrix addressed to no window (merge
        prewarm, padding of a stack of dense units): count/sum planes zero,
        min/max planes +inf/−inf bit patterns — the planes of a freshly
        reset unit, by the same walk."""
        bc = self.block_cells
        packed = np.zeros((self.blocks, self.n_planes(lean), bc + 2), np.int32)
        for c, pi in self._planes_walk(lean):
            if c.kind in NEUTRAL_BITS:
                packed[:, pi, :bc] = NEUTRAL_BITS[c.kind]
        return self._noop(packed, bc)

    def compact_noop(self, a_pad: int, lean: bool) -> np.ndarray:
        """An all-padding COMPACT packed matrix (for prewarm)."""
        packed = np.zeros(
            (self.blocks, self.n_planes(lean) + 1, a_pad + 2), np.int32
        )
        packed[:, 0, :a_pad] = -1
        return self._noop(packed, a_pad)

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
                raise OverflowError(self.F64_OVERFLOW)
            # overflow (finite src) and genuine ±inf/NaN sums both leave
            # lo meaningless (inf - inf = NaN): zero it so the device fold
            # yields ±inf/NaN parity with the scatter path instead of
            # poisoning cells with NaN
            lo[nonfin] = 0.0
        return hi.view(np.int32), lo.view(np.int32)

    def _reset(self) -> None:
        """Back to the state of a freshly allocated stripe: every written
        cell fold-neutral again (the native compact pack has already done
        its cells; here go dense units, whole, and the numpy pack's cells),
        the touched list emptied."""
        for where, n in self._dirty:
            self.rec[where] = self._neutral
            self.bytes_touched += n * self.rec.shape[1] * 8
        self._dirty = []
        self._n_touched[0] = 0
        self.u_base = None
        self.u_hi = 0
        self.rows = 0
        self.nulls_seen = False
