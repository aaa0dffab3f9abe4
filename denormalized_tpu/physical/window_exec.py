"""Streaming windowed-aggregation operator.

The TPU re-design of the reference's ``StreamingWindowExec`` + its three
stream implementations (``WindowAggStream`` ungrouped-partial,
``FullWindowAggStream`` final, ``GroupedWindowAggStream`` grouped —
streaming_window.rs:421-482, grouped_window_agg_stream.rs).  One operator
covers grouped and ungrouped: ungrouped is the G=1 degenerate case, and the
partial/final split (a cross-CPU-partition merge in the reference) becomes a
cross-device ``psum`` in the sharded variant (see
:mod:`denormalized_tpu.parallel`), not a separate operator pair.

Per input batch (host side, all vectorized):
1. evaluate group-key and value expressions;
2. intern keys → dense int32 group ids (:class:`GroupInterner`);
3. compute each row's slide-index and rebase against ``first_open``;
4. pad to a power-of-two bucket and dispatch the jitted device step
   (async — the host immediately continues decoding the next batch);
5. advance the watermark (monotonic min-timestamp, mirroring
   ``process_watermark`` at streaming_window.rs:732-744) and emit every
   window whose end ≤ watermark: fetch that ring slot's G-sized accumulator
   rows to host, finalize, reset the slot.

Capacity is elastic by recompilation: group capacity G and ring size W double
when the interner or the event-time skew outgrow them (bucketed static shapes
— the XLA-friendly answer to the reference's unbounded BTreeMap of frames).
"""

from __future__ import annotations

import sys
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from denormalized_tpu.common.constants import (
    CANONICAL_TIMESTAMP_COLUMN,
    WINDOW_END_COLUMN,
    WINDOW_START_COLUMN,
)
from denormalized_tpu.common.errors import PlanError
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.logical.expr import AggregateExpr, Expr
from denormalized_tpu.logical.plan import WindowType
from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe
from denormalized_tpu.ops.interner import INTERN_STATS, GroupInterner
from denormalized_tpu.ops.window_project import WindowProjector
from denormalized_tpu.physical.base import (
    EOS,
    WM_ANNOUNCE,
    EndOfStream,
    ExecOperator,
    Marker,
    StreamItem,
    WatermarkHint,
)
from denormalized_tpu.runtime.tracing import logger, phase_clock, span

#: group ids an emission is built from at a time: the gathers' and casts'
#: temporaries then stay in the cache, and the allocator hands the same
#: pages back, where whole-window temporaries of 25–50 MB each are mapped,
#: and every page of them faulted in, afresh at every window close
EMIT_CHUNK_GROUPS = 1 << 18


class _EmissionColumns:
    """The output columns of past emissions, handed out again once nothing
    else refers to them.

    A window of millions of rows costs eight fresh 50 MB columns, every page
    of them faulted in.  A column goes out as a view of its buffer, and every
    view, slice or memoryview of it that a consumer keeps holds a reference
    to that buffer: a buffer whose reference count says that this pool alone
    holds it can be seen by nobody, and is written again.  ``KEEP`` sets
    alternate, because a consumer usually still holds the last batch while
    the next is built."""

    KEEP = 2

    def __init__(self, dtypes: list[np.dtype]) -> None:
        self._dtypes = dtypes
        self._sets: list[list[np.ndarray]] = []

    def take(self, m: int) -> list[np.ndarray]:
        """One column of ``m`` rows per dtype, contents undefined."""
        for bufs in self._sets:
            # 2 = the set's reference + getrefcount's own argument
            if len(bufs[0]) >= m and all(
                sys.getrefcount(bufs[i]) == 2 for i in range(len(bufs))
            ):
                return [b[:m] for b in bufs]
        bufs = [np.empty(m + m // 8, dt) for dt in self._dtypes]
        self._sets = (self._sets + [bufs])[-self.KEEP:]
        return [b[:m] for b in bufs]


class _EmissionPredicate:
    """A post-aggregation filter the planner handed down (``Planner``'s rule
    for ``lp.Filter``), applied where a window is emitted: to the final
    values of a chunk's active positions, before a row's key string and
    columns are built, so a row that fails is never materialized.

    The predicate is the user's ``Expr``, evaluated by the ``Expr.eval`` a
    ``FilterExec`` would call, over a batch of the columns it names and no
    others — each cast to its output field's dtype first (a float32 final
    to the float64 of its column), the window bounds as the int64 constants
    the emitted batch carries: the same values, so the same rows."""

    def __init__(self, predicate: Expr, schema: Schema, n_keys: int) -> None:
        self.predicate = predicate
        names = sorted(predicate.columns_referenced())
        self._schema = schema.select(names)
        n_finals = len(schema) - n_keys - 3
        # per named column: the index of its aggregate among the finals and
        # the column's dtype, or which of the window's three bounds it is
        self._sources: list[tuple[int, np.dtype | None]] = []
        for name in names:
            at = schema.index_of(name) - n_keys
            if at < 0:
                raise PlanError(
                    f"emission predicate names the group key {name!r}"
                )
            self._sources.append(
                (at, schema.field(name).dtype.to_numpy()) if at < n_finals
                else (at - n_finals, None)
            )

    def keep(
        self, n: int, finals: list[np.ndarray], bounds: tuple[int, int, int]
    ) -> np.ndarray:
        """Boolean mask over the ``n`` positions of ``finals`` (one array
        an aggregate): those the predicate would let out.  ``bounds`` is
        the window's (start, end, canonical timestamp).  The positions may
        include some that hold no row — whatever a fold-neutral cell
        finalizes to (a 0 count, a NaN average) — whose answer the caller
        discards; their arithmetic must not warn."""
        cols = [
            np.full(n, bounds[at], np.int64) if dt is None
            else finals[at].astype(dt, copy=False)
            for at, dt in self._sources
        ]
        with np.errstate(all="ignore"):
            keep = self.predicate.eval(RecordBatch(self._schema, cols))
        return np.asarray(keep, dtype=bool)

#: keys of the window operator's phase clock, surfaced by ``metrics()`` as
#: ``phase_ms_<key>``: exclusive host milliseconds, so they add up to the
#: wall the operator's outer spans covered (docs/observability.md, Spans).
#: ``other`` is the self time of the outer spans (``window.process_batch``,
#: ``window.hint``, ``window.marker``, ``window.eos``).
WINDOW_PHASES = (
    "project", "intern", "statewatch", "reduce", "acc_wait", "update",
    "trigger", "flush_send", "flush_pack", "gather",
    "d2h_wait", "finalize", "other",
)
#: the one key of that clock which is not the pull thread's: the ``-d2h``
#: worker's ``window.d2h_fetch``, surfaced as ``d2h_fetch_ms`` — time beside
#: the phases above, not part of their sum
D2H_FETCH = "d2h_fetch"


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _round_capacity(g: int, n_dev: int) -> int:
    """Round a group capacity up so every device shard is a multiple of 128
    lanes (and the total divides evenly over the mesh)."""
    unit = 128 * n_dev
    return -(-g // unit) * unit


def watermark_floor(wm_ms: int, length_ms: int, slide_ms: int) -> int:
    """First slide index NOT closed by watermark ``wm_ms`` — the exact
    point triggers advance ``first_open`` to, the floor the per-partition
    rebase may lower it back to, and the basis of ``_closable``.  One
    definition for all three so the trigger/rebase parity invariant is
    enforced by code, not comments."""
    return (wm_ms - length_ms) // slide_ms + 1


def window_output_low_watermark(
    first_open: int | None, slide_ms: int, length_ms: int, hint_ts: int,
    wm_ms: int | None = None,
) -> int:
    """Strict lower bound (minus one) on the start of any window a
    slide/length windowed operator can still emit, given no further input
    rows at or before ``hint_ts``.  With open windows that is the first
    open slot's start; with none, the earliest window a future row
    (> hint_ts) could land in.  Shared by StreamingWindowExec and
    UdafWindowExec — the forwarded WatermarkHint clamp must stay
    identical in both.

    Under per-partition watermarks ``first_open`` is NOT monotone: a
    slower partition's earlier windows may rebase it down to the
    watermark floor later, so the promise must already account for that
    — pass ``wm_ms`` and the bound uses min(first_open, floor)."""
    if first_open is not None:
        low_first = first_open
        if wm_ms is not None:
            low_first = min(
                low_first, watermark_floor(wm_ms, length_ms, slide_ms)
            )
        return low_first * slide_ms - 1
    min_future_start = ((hint_ts + 1 - length_ms) // slide_ms + 1) * slide_ms
    return min_future_start - 1


class _WindowTier:
    """Cold tier of one device-ring window operator: spills the OLDEST
    contiguous prefix of open-but-not-closable window slots (the
    window-frame spilling of the PAPERS.md spilling design — watermark-
    deferred frames whose rows have stopped arriving) out of the device
    ring into the LSM, then advances ``first_open`` past them so the
    ring stops reserving capacity for the skew span.  A spilled window

    - emits straight from its stored component planes when the
      watermark closes it (same finalize as the ring path);
    - reloads into the ring — lowering ``first_open`` back, exactly the
      per-partition rebase machinery — when a late-ish batch lands rows
      in it (touch), so drop semantics match the all-resident run;
    - rides checkpoints as an epoch-referenced block like every tier.

    Memory wins two ways: a spilled prefix stops ``_ensure_capacity``
    growing W for event-time skew, and when the resident span shrinks
    far enough the ring rebuilds at a smaller W (true allocation
    shrink)."""

    __slots__ = (
        "op", "node_id", "ctrl", "any_spilled", "spilled_bytes",
        "_blocks", "_next",
    )

    def __init__(self, op: "StreamingWindowExec", node_id: str, ctrl) -> None:
        self.op = op
        self.node_id = node_id
        self.ctrl = ctrl
        self.any_spilled = False
        self.spilled_bytes = 0
        self._blocks: dict[int, dict] = {}  # window index -> meta
        self._next = 0
        ctrl.register(node_id, op, self.resident_bytes)

    def resident_bytes(self) -> int:
        from denormalized_tpu.obs import statewatch as swm

        op = self.op
        spec = op._spec
        try:
            itemsize = int(np.dtype(spec.accum_dtype).itemsize)
        except TypeError:
            itemsize = 4
        keys = len(op._interner) if op._interner is not None else 1
        return (
            len(spec.components)
            * spec.window_slots
            * spec.group_capacity
            * itemsize
            + keys * swm.KEY_EST_BYTES
        )

    # -- touch / reload ---------------------------------------------------
    def touch_and_reload(self, lo_win: int, hi_win: int) -> None:
        """Reload every spilled window the incoming batch's rows can
        land in (windows [lo_win, hi_win]) BEFORE the operator computes
        win_rel — otherwise those rows would read as late and drop."""
        if not self.any_spilled:
            return
        due = sorted(j for j in self._blocks if lo_win <= j <= hi_win)
        if not due:
            return
        # INVARIANT: every spilled window stays strictly below
        # first_open.  Reloading lowers first_open to the lowest touched
        # window, so every spilled window ABOVE it must come back too —
        # left spilled, the ring's emission loop would reach its reset
        # slot and emit nothing where the all-resident run emits a window
        lo = due[0]
        due = sorted(j for j in self._blocks if j >= lo)
        self._reload(due)
        self._write_manifest()

    def _reload(self, js: list[int]) -> None:
        from denormalized_tpu.state.serialization import unpack_snapshot

        op = self.op
        op._flush()
        new_first = min(js)
        # ring capacity must cover [new_first, max_win_seen] BEFORE the
        # base lowers (the _grow-before-rebase aliasing rule the
        # per-partition watermark path documents)
        op._ensure_capacity(op._max_win_seen - new_first)
        op._first_open = new_first
        # export may hand back read-only device views — copy to mutate
        host = {
            label: np.array(buf) for label, buf in op._backend.export().items()
        }
        W = op._spec.window_slots
        for j in js:
            meta = self._blocks.pop(j)
            raw = self.ctrl.get_block(self.node_id, meta["id"])
            _bmeta, arrays = unpack_snapshot(raw)
            slot = j % W
            for label, arr in arrays.items():
                g = arr.shape[0]
                host[label][slot, :g] = arr
            self.spilled_bytes -= meta["bytes"]
            self.ctrl.note_reload(self.node_id, 1, len(raw))
            self.ctrl.delete_block(self.node_id, meta["id"])
        op._backend.import_(host)
        self.any_spilled = bool(self._blocks)
        op._state_info_cache = None

    # -- eviction ---------------------------------------------------------
    def maybe_spill(self, hot_lo_win: int) -> None:
        """Spill the prefix [first_open, min(hot_lo_win, …)) when over
        budget — the windows old enough that the current batch no longer
        feeds them.  Runs AFTER the trigger, so closable windows have
        already emitted and the prefix is genuinely deferred-open."""
        from denormalized_tpu.state.serialization import pack_snapshot

        need = self.ctrl.over_budget()
        if need <= 0:
            self.ctrl.relax(self.node_id)
            return
        op = self.op
        spec = op._spec
        spilled_any = False
        if op._first_open is not None:
            try:
                itemsize = int(np.dtype(spec.accum_dtype).itemsize)
            except TypeError:
                itemsize = 4
            per_window = max(
                len(spec.components) * spec.group_capacity * itemsize, 1
            )
            hi = min(int(hot_lo_win), op._max_win_seen + 1)
            want = -(-need // per_window)
            cut = min(op._first_open + want, hi)
            if cut > op._first_open:
                op._flush()
                W = spec.window_slots
                from denormalized_tpu.common.errors import StateError

                for j in range(op._first_open, cut):
                    rows = op._backend.read_slot(j % W)
                    arrays = {
                        label: np.asarray(arr)
                        for label, arr in rows.items()
                    }
                    block_id = f"w{self._next}"
                    blob = pack_snapshot({"window": int(j)}, arrays)
                    try:
                        # durable FIRST, reset after — a failed put must
                        # leave the slot's data in the ring
                        nbytes = self.ctrl.put_block(
                            self.node_id, block_id, blob
                        )
                    except StateError as e:
                        logger.warning(
                            "spill: window eviction put failed (%s) — "
                            "window %d stays resident this pass", e, j,
                        )
                        break
                    self._next += 1
                    op._backend.reset_slot(j % W)
                    self._blocks[j] = {"id": block_id, "bytes": nbytes}
                    self.spilled_bytes += nbytes
                    self.ctrl.note_spill(self.node_id, 1, nbytes)
                    op._first_open = j + 1
                    self.any_spilled = True
                    spilled_any = True
                if spilled_any:
                    self._write_manifest()
                    self._maybe_shrink()
                    op._state_info_cache = None
        self.ctrl.check_pressure(self.node_id)

    def _maybe_shrink(self) -> None:
        """Rebuild the ring at a smaller W once the resident span allows
        it — the actual allocation shrink (spilling alone only frees the
        slots logically)."""
        op = self.op
        span = max(op._max_win_seen - op._first_open + 2, 1)
        new_w = max(_next_pow2(span), 16)
        if new_w < op._spec.window_slots:
            op._grow(window_slots=new_w)

    # -- emission ---------------------------------------------------------
    def due_windows(self, wm_floor: int) -> list[int]:
        """Spilled windows the watermark has closed, ascending — they
        emit from their stored planes before any ring emission of the
        same trigger (preserving ascending-window output order)."""
        if not self.any_spilled:
            return []
        return sorted(j for j in self._blocks if j < wm_floor)

    def emit_rows(self, j: int) -> dict:
        """Load + drop one due window's component planes."""
        from denormalized_tpu.state.serialization import unpack_snapshot

        meta = self._blocks.pop(j)
        raw = self.ctrl.get_block(self.node_id, meta["id"])
        _bmeta, arrays = unpack_snapshot(raw)
        self.spilled_bytes -= meta["bytes"]
        self.any_spilled = bool(self._blocks)
        self.ctrl.note_reload(self.node_id, 1, len(raw))
        self.ctrl.delete_block(self.node_id, meta["id"])
        self._write_manifest()
        return arrays

    def _write_manifest(self) -> None:
        self.ctrl.write_manifest(
            self.node_id, [m["id"] for m in self._blocks.values()]
        )

    def info(self) -> dict:
        return {
            "spilled_bytes": self.spilled_bytes,
            "spilled_keys": 0,
            "spilled_blocks": len(self._blocks),
            "spilled_windows": sorted(self._blocks),
            "spill": self.ctrl.spill_stats(self.node_id),
        }

    # -- checkpoint integration -------------------------------------------
    def snapshot_refs(self, coord, key: str, epoch: int) -> dict:
        refs = {}
        for j in sorted(self._blocks):
            meta = self._blocks[j]
            self.ctrl.copy_block_to_epoch(
                coord, key, epoch, self.node_id, meta["id"]
            )
            refs[str(j)] = meta["id"]
        return refs

    def restore_refs(self, coord, key: str, refs: dict) -> None:
        for j_str, block_id in refs.items():
            raw = self.ctrl.restore_block_from_epoch(
                coord, key, self.node_id, block_id
            )
            self._blocks[int(j_str)] = {
                "id": block_id, "bytes": len(raw),
            }
            self.spilled_bytes += len(raw)
            seq = int(block_id[1:])
            self._next = max(self._next, seq + 1)
        self.any_spilled = bool(self._blocks)
        self._write_manifest()


class StreamingWindowExec(ExecOperator):
    def __init__(
        self,
        input_op: ExecOperator,
        group_exprs: list[Expr],
        aggr_exprs: list[AggregateExpr],
        window_type: WindowType,
        length_ms: int,
        slide_ms: int | None,
        *,
        accum_dtype=jnp.float32,
        compensated_sums: bool = False,
        device_finalize: bool = True,
        min_group_capacity: int = 128,
        min_window_slots: int = 16,
        min_batch_bucket: int = 256,
        emit_on_close: bool = True,
        mesh=None,
        shard_strategy: str = "auto",
        device_strategy: str = "scatter",
        partial_merge_rows: int = 4_000_000,
        emit_lag_ms: int | None = None,
        host_pipeline: bool = False,
        name: str = "window",
    ) -> None:
        if window_type is WindowType.SESSION:
            raise PlanError(
                "session windows are handled by SessionWindowExec"
            )
        self.input_op = input_op
        self.group_exprs = list(group_exprs)
        self.aggr_exprs = list(aggr_exprs)
        self.window_type = window_type
        self.length_ms = int(length_ms)
        self.slide_ms = int(slide_ms) if slide_ms else self.length_ms
        self.emit_on_close = emit_on_close
        self.name = name
        self._min_batch_bucket = min_batch_bucket

        in_schema = input_op.schema
        # deduped value columns: one device column per distinct agg argument
        self._value_exprs: list[Expr] = []
        keys = {}

        def value_idx(e: Expr) -> int:
            k = repr(e)
            if k not in keys:
                keys[k] = len(self._value_exprs)
                self._value_exprs.append(e)
                self._value_transforms.append(None)
            return keys[k]

        # variance columns are SHIFTED on host by a pivot K picked from the
        # first data (see segment_agg.variance_result): transforms[j] is
        # None | "shift" | "shift_sq", and _var_shift maps the source
        # expression's repr to its pivot (checkpointed with the operator)
        self._value_transforms: list[str | None] = []
        self._var_shift: dict[str, float] = {}

        def shifted_idx(e: Expr, transform: str) -> int:
            k = (transform, repr(e))
            if k not in keys:
                keys[k] = len(self._value_exprs)
                self._value_exprs.append(e)
                self._value_transforms.append(transform)
            return keys[k]

        self._agg_specs: list[tuple] = []
        for a in self.aggr_exprs:
            if a.kind == "udaf":
                raise PlanError("UDAF aggregates run in UdafWindowExec")
            if a.arg is None:
                self._agg_specs.append((a.kind, None))
                continue
            if a.kind in sa.VAR_KINDS:
                self._agg_specs.append(
                    (
                        a.kind,
                        shifted_idx(a.arg, "shift"),
                        shifted_idx(a.arg, "shift_sq"),
                    )
                )
            else:
                self._agg_specs.append((a.kind, value_idx(a.arg)))
        if accum_dtype == jnp.float64 and not jax.config.jax_enable_x64:
            raise PlanError(
                "accum_dtype=float64 requires jax.config.update("
                "'jax_enable_x64', True) — without it JAX silently "
                "accumulates in float32; either enable x64 or use "
                "compensated_sums=True for near-f64 sums in f32 storage"
            )
        comps = sa.components_for(self._agg_specs)
        if compensated_sums:
            comps = sa.with_compensation(comps)
        components = tuple(comps)
        self._compensated = compensated_sums

        self._grouped = len(self.group_exprs) > 0
        self._interner = GroupInterner(len(self.group_exprs)) if self._grouped else None
        self._mesh = mesh
        self._shard_strategy = shard_strategy
        self._device_strategy = device_strategy
        n_dev = 1 if mesh is None else mesh.devices.size
        self._spec = sa.WindowKernelSpec(
            components=components,
            num_value_cols=len(self._value_exprs),
            window_slots=min_window_slots,
            group_capacity=_round_capacity(
                min_group_capacity if self._grouped else 128, n_dev
            ),
            length_ms=self.length_ms,
            slide_ms=self.slide_ms,
            accum_dtype=accum_dtype,
            compensated=compensated_sums,
        )
        # bound like the registry instruments below: the shared falsy null
        # under metrics_enabled=False
        self._phases = phase_clock("window", WINDOW_PHASES + (D2H_FETCH,))
        self._backend = self._new_backend()
        # on-device finalization: emission ships final output planes + an
        # active bitmask instead of raw component planes (see
        # segment_agg._finals_and_reset).  Only when every aggregate is
        # finalizable and the backend layout supports it (it returns None
        # from read_reset_block_finals_start otherwise).
        self._finals_specs = (
            tuple(self._agg_specs)
            if device_finalize
            and sa.finals_possible(tuple(self._agg_specs))
            else None
        )
        self._prepare_emission()

        # schema: group cols + agg cols + window bounds (+ canonical ts)
        fields = [g.out_field(in_schema) for g in self.group_exprs]
        fields += [a.out_field(in_schema) for a in self.aggr_exprs]
        fields += [
            Field(WINDOW_START_COLUMN, DataType.TIMESTAMP_MS, nullable=False),
            Field(WINDOW_END_COLUMN, DataType.TIMESTAMP_MS, nullable=False),
            Field(CANONICAL_TIMESTAMP_COLUMN, DataType.TIMESTAMP_MS, nullable=False),
        ]
        self.schema = Schema(fields)
        self._emit_cols = _EmissionColumns(
            [f.dtype.to_numpy() if f.dtype.is_numeric else np.dtype(object)
             for f in fields]
        )
        # the filter directly above, where the planner handed it down
        # (set_emission_predicate)
        self._emit_pred: _EmissionPredicate | None = None

        # streaming state
        self._ckpt: tuple | None = None
        # cold tier (state/tiering.py): set by enable_spill
        self._tier: _WindowTier | None = None
        self._first_open: int | None = None  # lowest non-emitted slide index
        self._max_win_seen: int = -1
        self._watermark_ms: int | None = None
        # True once a kind="partition" hint arrived: the source computes
        # per-partition watermarks, so raw batch min-ts must NOT advance
        # the operator watermark (it races ahead on replay skew)
        self._src_watermarks = False
        # monotone: True once any value column carried a null.  While
        # False, emission gathers skip per-column count planes (they equal
        # the row-count plane) — see _gather_and_reset(lean=True)
        self._any_nulls_seen = False
        # host pipelining for accumulating backends: backend.accumulate
        # (the native C++ stripe reduction — it releases the GIL) runs on
        # a single worker thread so batch N's reduction overlaps batch
        # N+1's eval/intern on the main thread.  The single worker keeps
        # stripe mutation serialized; _join_acc() fences before any other
        # backend access (flush/emission/export/growth)
        self._host_pipeline = host_pipeline
        # the batch's time arithmetic; its outputs go into buffers it keeps
        # only where a batch is folded before the next is projected (see
        # WindowProjector): not under host_pipeline, whose worker may still
        # read batch k while k+1 is projected, and not for a row-shipping
        # backend, whose device program reads its inputs asynchronously —
        # those get fresh arrays a batch
        self._proj = WindowProjector(
            self.slide_ms, self._spec.length_units,
            reuse_buffers=self._backend.accumulates_host and not host_pipeline,
        )
        self._acc_exec = None
        self._acc_future = None
        self._acc_error: BaseException | None = None
        # partial_merge flush/emission pacing: emission is deferred up to
        # emit_lag_s after a window becomes closable so replay-speed runs
        # batch several windows per device round-trip; paced (real-time)
        # feeds always exceed the lag and emit promptly.  Backend-default
        # (None): 0 on CPU — merges are memcpy-cheap, and the deferral
        # only re-checks on rowful batches, so it would hold a paused
        # live stream's final windows until the next batch arrives; 200ms
        # on every accelerator backend (TPU, GPU, ...), to amortize the
        # merge round-trip — a value chosen on an earlier installation,
        # not measured on this one (ROADMAP S2).
        if emit_lag_ms is None:
            emit_lag_ms = 0 if jax.default_backend() == "cpu" else 200
        self._emit_lag_s = emit_lag_ms / 1000.0
        self._merge_rows = partial_merge_rows
        self._stripe_wall: float | None = None
        # dispatched-but-untaken emission blocks, ascending:
        # (j0, n, handle, is_finals, fetch) — ``fetch`` is the future of the
        # ``-d2h`` worker bringing the block to the host, or None where the
        # trigger that dispatched the block drains it itself
        self._pending_emit: list[tuple] = []
        self._emit_exec = None
        # async checkpoint in flight: (epoch, meta, backend, handle), plus
        # the barrier marker held until the snapshot is durable
        self._pending_snapshot: tuple | None = None
        self._held_marker = None
        self._metrics = {
            "rows_in": 0,
            "batches_in": 0,
            "late_rows": 0,
            "windows_emitted": 0,
            # rows the closed windows held (before the emission predicate
            # or any downstream operator), and those of them the emission
            # predicate kept out: delivered = emit_rows - emit_rows_filtered
            "emit_rows": 0,
            "emit_rows_filtered": 0,
            "device_steps": 0,
            "partial_merges": 0,
            "grow_events": 0,
            # deferred emission blocks the pull thread took when their fetch
            # had landed, and those something made it wait for
            # (_drain_pending)
            "emit_blocks_overlapped": 0,
            "emit_blocks_waited": 0,
            # whole duration of the spans around hints, markers and
            # end-of-stream: the hint path's counterpart of dnz_op_batch_ms
            "hint_path_ms": 0.0,
        }
        # registry instruments (obs subsystem), pre-bound so the per-
        # batch path is attribute adds only
        from denormalized_tpu import obs
        from denormalized_tpu.obs import statewatch

        self.bind_obs("window")
        # state observatory sketches, fed dense gids per batch
        self._sw = statewatch.make_watch("window")
        self._obs_late = obs.counter("dnz_late_rows_total", op="window")
        self._obs_windows = obs.counter(
            "dnz_windows_emitted_total", op="window"
        )
        self._obs_emit_lag = obs.histogram(
            "dnz_emit_event_lag_ms", op="window"
        )
        self._obs_wm_lag = obs.gauge("dnz_watermark_lag_ms", op="window")
        self._obs_wm_lag_hist = obs.histogram(
            "dnz_watermark_lag_hist_ms", op="window"
        )

    # ------------------------------------------------------------------
    @property
    def children(self):
        return [self.input_op]

    def metrics(self):
        m = dict(self._metrics)
        if self._backend.accumulates_host:
            # reconcile from the backend counter: flushes can also happen
            # inside accumulate() (stripe-span overflow), which per-call
            # deltas in _flush would miss
            m["partial_merges"] = self._backend.merges
            m["device_steps"] = self._backend.merges
        m["bytes_h2d"] = self._backend.bytes_h2d
        m["bytes_d2h"] = self._backend.bytes_d2h
        # what the stripe's flushes cost: cells with rows, cells sent
        # (padding included), host bytes scanned and rewritten, bytes of
        # the packed matrices, the ring rows and entries their merges fold,
        # why each flush happened (all 0 for a row-shipping backend), and
        # the cells with rows by the key block (device) they fell in
        m.update(self._backend.stripe_counters())
        ms = self._phases.ms
        for key in WINDOW_PHASES:
            m[f"phase_ms_{key}"] = ms.get(key, 0.0)
        # the whole of ``window.flush``: its self time (transfer and
        # dispatch) and its child phase
        m["phase_ms_flush"] = (
            m["phase_ms_flush_send"] + m["phase_ms_flush_pack"]
        )
        # what the emission blocks' bytes cost on the ``-d2h`` worker, beside
        # the pull thread's phases (phase_ms_d2h_wait is the part of it the
        # pull thread had to stand still for)
        m["d2h_fetch_ms"] = ms.get(D2H_FETCH, 0.0)
        # what ``window.statewatch`` ran: batches sketched, and how many of
        # them the native pass folded (all, where the library loaded)
        m["sketch_update_batches"] = self._sw.update_batches
        m["sketch_native_batches"] = self._sw.sketch_native_batches
        # batches whose timestamps ``window.project``'s native pass took
        # (= batches_in where the library loaded and the timestamps are a
        # contiguous int64 array)
        m["project_native_batches"] = self._proj.native_batches
        # what the intern phase's native table did: intern_rows,
        # intern_extra_probes, intern_overflow_rows (0 when ungrouped)
        m.update(
            self._interner.stats() if self._interner is not None
            else dict.fromkeys(INTERN_STATS, 0)
        )
        # what 'auto' actually chose (a report must RECORD the resolved
        # strategy, not just the request) — each backend labels itself —
        # and over how many devices the ring is laid out
        m["strategy_resolved"] = self._backend.strategy_name
        m["mesh_devices"] = (
            1 if self._mesh is None else int(self._mesh.devices.size)
        )
        return m

    def _label(self):
        w = f"{self.window_type.value} {self.length_ms}ms"
        if self.slide_ms != self.length_ms:
            w += f"/{self.slide_ms}ms"
        flt = (
            f", emit_filter={self._emit_pred.predicate!r}"
            if self._emit_pred is not None else ""
        )
        return (
            f"StreamingWindowExec({w}, groups=[{', '.join(g.name for g in self.group_exprs)}], "
            f"aggs=[{', '.join(a.name for a in self.aggr_exprs)}]{flt})"
        )

    def set_emission_predicate(self, predicate: Expr) -> None:
        """Apply ``predicate`` — a row-wise expression over aggregate
        outputs and window bounds, no group key (the planner checks) —
        where windows are emitted, on every emission path: the rows that
        fail are counted (``emit_rows_filtered``) and never built."""
        self._emit_pred = _EmissionPredicate(
            predicate, self.schema, len(self.group_exprs)
        )

    # -- cold tier (state/tiering.py) -----------------------------------
    def enable_spill(self, node_id: str, controller) -> None:
        self._tier = _WindowTier(self, node_id, controller)

    # -- state observatory (obs/statewatch.py) --------------------------
    def state_info(self) -> dict:
        from denormalized_tpu.obs import statewatch as swm

        spec = self._spec
        try:
            itemsize = int(np.dtype(spec.accum_dtype).itemsize)
        except TypeError:
            itemsize = 4
        # the device ring is a DENSE allocation: its footprint IS the
        # component-plane volume, independent of occupancy
        device_bytes = (
            len(spec.components)
            * spec.window_slots
            * spec.group_capacity
            * itemsize
        )
        live_keys = (
            len(self._interner) if self._interner is not None
            else (1 if self._first_open is not None else 0)
        )
        open_windows = (
            max(0, self._max_win_seen - self._first_open + 1)
            if self._first_open is not None
            else 0
        )
        oldest = (
            self._first_open * self.slide_ms
            if self._first_open is not None and open_windows
            else None
        )
        wm = self._watermark_ms
        info = {
            "op": "window",
            "state_bytes": device_bytes + live_keys * swm.KEY_EST_BYTES,
            "device_state_bytes": device_bytes,
            "live_keys": live_keys,
            "slot_capacity": int(spec.group_capacity),
            "slot_live": live_keys,
            "open_windows": open_windows,
            "window_slots": int(spec.window_slots),
            "retention_unit_ms": self.length_ms,
            "oldest_event_ms": oldest,
            "watermark_ms": wm,
        }
        if wm is not None and oldest is not None:
            info["oldest_event_lag_ms"] = max(0, int(wm) - int(oldest))
        if self._tier is not None:
            info.update(self._tier.info())
        return info

    def _state_watch_views(self):
        if not self._sw:
            return []
        if self._interner is None:
            return [(None, self._sw, None)]
        from denormalized_tpu.ops.interner import display_keys

        return [
            (None, self._sw, lambda g: display_keys(self._interner, g))
        ]

    # -- capacity management --------------------------------------------
    def _new_backend(self):
        from denormalized_tpu.parallel.sharded_state import make_sharded_state

        backend = make_sharded_state(
            self._spec, self._mesh, self._shard_strategy, self._device_strategy
        )
        backend.phases = self._phases  # window.flush is opened in there
        return backend

    def _prepare_emission(self) -> None:
        """Pre-compile the emission programs this plan can reach: the
        finals ladder or the component gather's, never both (the trigger
        takes the gather only where no finals were prepared)."""
        if self._finals_specs is not None:
            self._backend.prepare_finals(self._finals_specs)
        else:
            self._backend.prepare_gather()

    def _grow(self, *, window_slots: int | None = None, group_capacity: int | None = None):
        # host-accumulated partials are bound to the old G/W layout —
        # merge them into device state before exporting it
        self._join_acc()
        self._backend.flush_pending()
        host = self._backend.export()
        old = self._spec
        self._spec = sa.WindowKernelSpec(
            components=old.components,
            num_value_cols=old.num_value_cols,
            window_slots=window_slots or old.window_slots,
            group_capacity=group_capacity or old.group_capacity,
            length_ms=old.length_ms,
            slide_ms=old.slide_ms,
            accum_dtype=old.accum_dtype,
            compensated=old.compensated,
        )
        if window_slots and self._first_open is not None:
            # ring phase changes with W: re-lay out slots by absolute window
            # index.  Only windows the old ring could actually hold are live.
            hi = min(self._max_win_seen, self._first_open + old.window_slots - 1)
            init_scalars = {
                c.label: np.asarray(self._spec.init_value(c))
                for c in self._spec.components
            }
            remapped = {}
            for label, buf in host.items():
                nbuf = np.full(
                    (self._spec.window_slots, self._spec.group_capacity),
                    init_scalars[label],
                    dtype=buf.dtype,
                )
                for j in range(self._first_open, hi + 1):
                    nbuf[j % self._spec.window_slots, : buf.shape[1]] = buf[
                        j % old.window_slots
                    ]
                remapped[label] = nbuf
            host = remapped
        old_backend = self._backend
        self._backend = self._new_backend()
        self._carry_counters(old_backend)
        self._prepare_emission()
        self._backend.import_(host)
        self._metrics["grow_events"] += 1

    def _carry_counters(self, old_backend) -> None:
        """Link-traffic and merge counters live on the backend instance;
        a grow/restore replacement must carry them or ``metrics()``'s
        bytes_h2d/bytes_d2h reflect only the post-last-growth tail —
        exactly wrong for high-cardinality runs that grow repeatedly."""
        self._backend.bytes_h2d += old_backend.bytes_h2d
        self._backend.bytes_d2h += old_backend.bytes_d2h
        self._backend.carry_stripe_counters(old_backend)

    def _ensure_capacity(self, max_win_rel: int):
        # gids are interner-dense and this runs after interning, before the
        # batch is folded: the ring is too small only once an id does not
        # fit (growing at nine tenths doubled a ring sized for its keys)
        cap = self._backend.group_capacity
        if self._grouped and len(self._interner) > cap:
            n_dev = 1 if self._mesh is None else self._mesh.devices.size
            self._grow(
                group_capacity=_round_capacity(
                    _next_pow2(int(len(self._interner) * 2)), n_dev
                )
            )
        if max_win_rel >= self._spec.window_slots:
            self._grow(window_slots=_next_pow2(max_win_rel + 2))

    # -- per-batch processing -------------------------------------------
    def _process_batch(self, batch: RecordBatch) -> Iterator[RecordBatch]:
        n = batch.num_rows
        if n == 0:
            return
        if "first_batch_at" not in self._metrics:
            # perf_counter at the first accepted batch: everything before
            # it (plan build, prewarm ladders, restore) is set-up
            self._metrics["first_batch_at"] = time.perf_counter()
        self._metrics["rows_in"] += n
        # ordinal of this batch: the identifier its spans share
        bno = self._metrics["batches_in"]
        self._metrics["batches_in"] += 1
        self._obs_rows_in.add(n)
        ph = self._phases
        with ph.phase("project", batch=bno, rows=n):
            # the batch's time arithmetic (ops/window_project.py): ONE pass
            # over the timestamps gives every row's slide unit and remainder
            # and the batch's extremes, so everything below that used to
            # scan an array for its min or max reads a scalar
            proj = self._proj
            host = self._backend.accumulates_host
            units, rem, u_min, u_max, ts_min = proj.units(
                batch.column(CANONICAL_TIMESTAMP_COLUMN)
            )

            anchor = u_min - self._spec.length_units + 1
            if self._first_open is None:
                # windows overlapping the first data: back to units.min() - k + 1
                self._first_open = anchor
            elif self._src_watermarks and anchor < self._first_open:
                # per-partition watermarks: the first batch anchored first_open
                # to ITS partition's windows, but a slower partition's earlier
                # windows are still legitimate until the (min-driven) watermark
                # closes them.  Rebase down to the watermark floor — the ring
                # addresses slots by absolute window index, so this only
                # widens the logical span (capacity grows below).  Triggers
                # advance first_open exactly to the wm floor, so anything
                # below it was genuinely closed and stays late.
                wm_floor = (
                    watermark_floor(
                        self._watermark_ms, self.length_ms, self.slide_ms
                    )
                    if self._watermark_ms is not None
                    else anchor
                )
                new_first = max(anchor, int(wm_floor))
                if new_first < self._first_open:
                    if self._backend.accumulates_host:
                        # the pending stripe's units are relative to the OLD
                        # first_open (via its captured base_mod) — fold it
                        # into the device ring before the base moves
                        self._flush()
                    # the widened span (new_first.._max_win_seen) needs ring
                    # capacity, and the grow must run BEFORE the base moves:
                    # _grow attributes old ring slots to windows
                    # first_open..first_open+old_W-1, so lowering first would
                    # alias a re-admitted low window with a live high one and
                    # the remap would credit the high window's accumulators
                    # to the low one (found by hypothesis: L=1000/S=100,
                    # span 17 over a 16-slot ring lost window 7's content).
                    # No sentinel guard: reaching this branch means a batch
                    # was seen, and _max_win_seen's -1 floor (negative
                    # event-time streams pin it there) only OVERestimates
                    # the span — a larger-than-needed grow is safe, a
                    # skipped one aliases slots.
                    self._ensure_capacity(self._max_win_seen - new_first)
                    self._first_open = new_first
            if self._tier is not None:
                # reload-on-touch BEFORE win_rel is computed: a spilled
                # window this batch's rows can land in comes back into the
                # ring (first_open lowers with it), so nothing reads as late
                # that the all-resident run would have accepted
                self._tier.touch_and_reload(anchor, u_max)
            first = self._first_open
            # a host-reducing backend drops against the WATERMARK (windows
            # already closable), not first_open — see below; neither moves
            # before the drop is decided, so both counts, and the mask where
            # a row is dropped, come out of the one pass that rebases the
            # units (a row-shipping backend drops by win_rel: no mask)
            closable_pre = self._closable() if host else 0
            win_rel64, late, n_behind, straddle, keep = proj.rebase(
                units, u_min, first, closable_pre, mask=host
            )
            self._max_win_seen = max(self._max_win_seen, u_max)
            if late:
                self._metrics["late_rows"] += late
                self._obs_late.add(late)

            # group ids — intern BEFORE the capacity check so G always covers
            # every id this batch scatters
            with ph.phase("intern", batch=bno):
                if self._grouped:
                    key_cols = [g.eval(batch) for g in self.group_exprs]
                    gid = self._interner.intern(key_cols)
                else:
                    gid = np.zeros(n, dtype=np.int32)
            with ph.phase("statewatch", batch=bno):
                self._sw.update(gid)
            self._ensure_capacity(u_max - first)

            # value matrix + per-column validity: f64 only when the backend
            # accumulates on host (partial_merge keeps f64 precision); the
            # row-shipping paths fill f32 directly — no second full-matrix copy
            V = self._spec.num_value_cols
            from denormalized_tpu.logical.expr import column_validity

            host_dtype = np.float64 if host else np.float32
            single_untransformed = (
                V == 1 and self._value_transforms[0] is None
            )
            # per-column validity, built only once a column has a mask
            # (None = every value valid)
            colvalid = None
            any_invalid = False
            if single_untransformed:
                # single untransformed value column (the common case): the
                # evaluated column IS the value matrix — skip the zeros
                # allocation and the per-column copy.  The host reducer and
                # the device paths only read it, so aliasing the batch
                # column (host path, already f64) is safe.
                e = self._value_exprs[0]
                values64 = np.asarray(e.eval(batch), dtype=host_dtype).reshape(
                    n, 1
                )
                m = column_validity(e, batch)
                if m is not None:
                    colvalid = np.asarray(m, dtype=bool).reshape(n, 1)
                    any_invalid = not colvalid.all()
            else:
                values64 = np.zeros((n, max(V, 1)), dtype=host_dtype)
                for j, e in enumerate(self._value_exprs):
                    raw = np.asarray(e.eval(batch), dtype=np.float64)
                    m = column_validity(e, batch)
                    if m is not None:
                        if colvalid is None:
                            colvalid = np.ones((n, max(V, 1)), dtype=bool)
                        colvalid[:, j] = m
                        any_invalid = any_invalid or not colvalid[:, j].all()
                    tr = self._value_transforms[j]
                    if tr is not None:
                        # variance moment columns: shift by a pivot K taken
                        # from the first valid value ever seen for this
                        # expression, so the s2 − s²/c finalize never
                        # catastrophically cancels (exact for any constant K)
                        key = repr(e)
                        K = self._var_shift.get(key)
                        if K is None:
                            valid_vals = (
                                raw[colvalid[:, j]] if m is not None else raw
                            )
                            finite = valid_vals[np.isfinite(valid_vals)]
                            if len(finite):
                                K = float(finite[0])
                                self._var_shift[key] = K
                            else:
                                # no finite value yet (all-null warm-up
                                # batch): use 0 transiently but do NOT cache
                                # it — a later batch with real data must
                                # still set a magnitude-matched pivot, or
                                # the cancellation guard is lost
                                K = 0.0
                        raw = raw - K
                        if tr == "shift_sq":
                            raw = raw * raw
                    values64[:, j] = raw

            if any_invalid:
                self._any_nulls_seen = True

            if host:
                # partial_merge: reduce the batch on host; the device sees a
                # merged stripe later (flush on trigger/growth/snapshot).
                # Late-drop against the WATERMARK (windows already closable),
                # not first_open: emission deferral must not make drop
                # semantics wall-clock-dependent — this is exactly where the
                # scatter path's first_open would sit, since it emits every
                # closable window immediately.
                if straddle:
                    # A kept row's unit partial feeds EVERY window
                    # containing that unit — including closable windows
                    # whose emission is merely deferred.  The stripe is
                    # per-unit, so that stale contribution cannot be
                    # subtracted per-window later; the only sound order is
                    # freeze-then-accumulate: emit every closable window
                    # now, then rebase against the advanced first_open.
                    # Only rows strictly BEHIND the watermark can straddle
                    # (a row at ts ≥ wm has no closable window), so a
                    # sorted feed never takes this path.
                    yield from self._trigger(force=True)
                    first = self._first_open
                    win_rel64, _, n_behind, _, keep = proj.rebase(
                        units, u_min, first, self._closable()  # 0 post-emission
                    )
                if n_behind:
                    # rows behind the watermark's closable count that were
                    # not already counted against first_open
                    self._metrics["late_rows"] += n_behind - late
                    self._obs_late.add(n_behind - late)
        if host:
            if (
                self._acc_future is None or self._acc_future.done()
            ) and self._backend.pending_rows == 0:
                self._stripe_wall = time.perf_counter()
            acc_args = (
                win_rel64,
                rem,
                gid,
                values64,
                colvalid if any_invalid else None,
                keep,
                first % self._spec.window_slots,
                # the batch's extremes, known since the timestamp pass
                u_min - first,
                u_max - first,
            )
            if self._host_pipeline:
                self._submit_acc(bno, acc_args)
            else:
                with ph.phase("reduce", batch=bno):
                    self._backend.accumulate(*acc_args)
        else:
            values = values64  # already f32 (see allocation above)
            if colvalid is None:
                colvalid = np.ones(values.shape, dtype=bool)
            win_rel = np.clip(
                win_rel64, -1, self._spec.window_slots
            ).astype(np.int32)
            # pad to bucket (divisible by the mesh so row-sharding splits
            # evenly)
            Bp = max(self._min_batch_bucket, _next_pow2(n))
            n_dev = 1 if self._mesh is None else self._mesh.devices.size
            Bp = -(-Bp // n_dev) * n_dev
            row_valid = np.zeros(Bp, dtype=bool)
            row_valid[:n] = True

            def pad(a, fill=0):
                if a.shape[0] == Bp:
                    return a
                out = np.full((Bp,) + a.shape[1:], fill, dtype=a.dtype)
                out[:n] = a
                return out

            with ph.phase("update", batch=bno):
                self._backend.update(
                    pad(values),
                    pad(colvalid),
                    pad(win_rel, fill=-1),
                    pad(rem),
                    pad(gid),
                    row_valid,
                    first % self._spec.window_slots,
                )
            self._metrics["device_steps"] += 1

        # watermark: monotonic max of batch min-ts (reference semantics) —
        # unless the source supplies per-partition watermarks, which
        # arrive as kind="partition" hints right after their batch
        if not self._src_watermarks:
            if self._watermark_ms is None or ts_min > self._watermark_ms:
                self._watermark_ms = ts_min
        yield from self._trigger()
        if self._tier is not None:
            # after the trigger: closable windows have emitted, so the
            # [first_open, this batch's lowest window) prefix is the
            # watermark-deferred cold span
            self._tier.maybe_spill(anchor)

    # -- host pipeline fence --------------------------------------------
    def _join_acc(self) -> None:
        """Wait for any in-flight host accumulation.  Every backend access
        other than pacing reads (pending_rows) must fence through here —
        the stripe and the device merge stream are only consistent between
        worker tasks."""
        f, self._acc_future = self._acc_future, None
        err = None
        if f is not None:
            try:
                with self._phases.phase("acc_wait"):
                    f.result()  # re-raises a worker failure on this thread
            finally:
                # read the flag only AFTER the wait: an EARLIER task
                # (future superseded by a later submission) may set it
                # while we block on the latest one.  Clearing it here also
                # prevents f's own failure from being raised a second time
                # by a later, unrelated fence.
                err, self._acc_error = self._acc_error, None
        else:
            err, self._acc_error = self._acc_error, None
        if err is not None:
            # a superseded task failed even though the latest one
            # succeeded; the stream must not keep running on a
            # half-updated stripe
            raise err

    def _one_worker(self, role: str):
        """A pool of one thread, ``<operator>-<role>``: its tasks run in
        the order they were handed over."""
        from concurrent.futures import ThreadPoolExecutor

        return ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"{self.name}-{role}"
        )

    def _submit_acc(self, bno: int, args: tuple) -> None:
        if self._acc_error is not None:
            err, self._acc_error = self._acc_error, None
            raise err
        if self._acc_exec is None:
            self._acc_exec = self._one_worker("acc")

        backend = self._backend
        ph = self._phases

        def run():
            try:
                with ph.phase("reduce", batch=bno):
                    backend.accumulate(*args)
            except BaseException as e:  # surfaced via _join_acc/_submit_acc
                self._acc_error = e
                raise

        self._acc_future = self._acc_exec.submit(run)

    def _output_low_watermark(self, hint_ts: int) -> int:
        return window_output_low_watermark(
            self._first_open, self.slide_ms, self.length_ms, hint_ts,
            wm_ms=self._watermark_ms if self._src_watermarks else None,
        )

    # -- emission --------------------------------------------------------
    def _closable(self) -> int:
        if self._watermark_ms is None or self._first_open is None:
            return 0
        wm_win = watermark_floor(
            self._watermark_ms, self.length_ms, self.slide_ms
        )
        return max(0, int(wm_win) - self._first_open)

    def _drain_pending(self, wait: bool = True) -> Iterator[RecordBatch]:
        """Take dispatched emission blocks, oldest first, and emit their
        windows.  A deferred block is brought to the host by the ``-d2h``
        worker beside ingest (``_fetch_block``); ``wait=False`` — the
        trigger after every batch — takes the blocks that have landed and
        leaves the rest in flight, so the pull thread stands still for a
        block only where something needs it out first: the next close, a
        marker, an idle hint, the end of the stream, the cold tier's due
        windows.  A block without a worker (dispatched and drained in one
        trigger) is fetched here.  A worker's failure is raised here, on
        the pull thread, when its block is taken."""
        ph = self._phases
        pending = self._pending_emit
        while pending:
            j0, n, handle, is_finals, fetch = pending[0]
            landed = fetch is not None and fetch.done()
            if fetch is not None and not (wait or landed):
                return
            del pending[0]
            if landed:
                self._metrics["emit_blocks_overlapped"] += 1
                block = fetch.result()
            else:
                # the only place the pull thread waits for the device
                with ph.phase("d2h_wait", window=j0, n=n):
                    if fetch is None:
                        block = self._backend.read_reset_block_finish(handle)
                    else:
                        self._metrics["emit_blocks_waited"] += 1
                        block = fetch.result()
            with ph.phase("finalize", window=j0, n=n):
                out = list(self._finalize_block(j0, n, block, is_finals))
            # booked on this thread (read_slot adds to the same counter),
            # right after the block's windows were (windows_emitted), not a
            # finalize before them: whoever reads both reads them in step
            self._backend.count_block_d2h(block)
            yield from out

    def _fetch_block(self, j0: int, n: int, handle):
        """Hand a dispatched block to the one ``-d2h`` worker, which waits
        for its copy and assembles it on the host — ``jax.device_get`` waits
        inside the runtime, without the interpreter lock — and touches
        nothing but the handle.  Returns the future ``_drain_pending``
        takes the block from."""
        if self._emit_exec is None:
            self._emit_exec = self._one_worker("d2h")
        finish = self._backend.read_reset_block_finish
        ph = self._phases

        def fetch():
            with ph.phase(D2H_FETCH, window=j0, n=n):
                return finish(handle)

        return self._emit_exec.submit(fetch)

    def _finalize_block(
        self, j0: int, n: int, block: dict, is_finals: bool
    ) -> Iterator[RecordBatch]:
        """Emission batches of one materialized block of ``n`` windows."""
        ngroups = len(self._interner) if self._grouped else 1
        if is_finals:
            # finals block: one plane per output aggregate + packed
            # active bitmask; no host-side finalize needed
            bits = sa.unpack_active(
                block[sa.ACTIVE_BITS], self._backend.key_blocks
            )
            planes = [
                block[f"__final_{k}__"] for k in range(len(self.aggr_exprs))
            ]
            for i in range(n):
                b = self._emit_window_batch(
                    j0 + i,
                    bits[i, :ngroups],
                    lambda lo, hi, local, i=i: [
                        p[i, lo:hi][local] for p in planes
                    ],
                )
                if b is not None:
                    yield b
            return
        # lean gathers omit per-column count planes (null-free stream:
        # they equal the row-count plane) — alias them back
        for c in self._spec.components:
            if c.kind == "count" and c.label not in block:
                block[c.label] = block[sa.ROW_COUNT.label]
        for i in range(n):
            b = self._finalize_rows(
                j0 + i, {label: arr[i] for label, arr in block.items()}
            )
            if b is not None:
                yield b

    def _emit_window_batch(
        self, j: int, active: np.ndarray, finals_of
    ) -> RecordBatch | None:
        """Window ``j``'s emission batch — the rows of the positions
        ``active`` marks, ascending — or None when it marks none.  Position
        ``p`` is group ``p``; ``finals_of(lo, hi, local)`` gives the output
        columns of the positions ``lo + local`` (``local`` an index array,
        or ``slice(None)`` for all of ``lo:hi``); the batch is filled
        ``EMIT_CHUNK_GROUPS`` positions at a time.  ONE batch a window: a
        consumer may take a window's first row for the whole window
        delivered (the benchmark's harness does).

        Under an emission predicate a chunk's positions are narrowed to the
        rows that pass before anything of a row is built: the predicate is
        evaluated over the chunk's final values at EVERY position — views
        of the finals planes, contiguous passes — and the result masked by
        ``active``, which costs less than gathering the active positions'
        finals first and compressing them after.  A window whose every row
        fails gives no batch and is a window emitted all the same."""
        m = int(np.count_nonzero(active))
        if m == 0:
            return None
        self._metrics["emit_rows"] += m
        cols = self._emit_cols.take(m)
        n_keys = len(self.group_exprs)
        pred = self._emit_pred
        start = j * self.slide_ms
        bounds = (start, start + self.length_ms, start)
        off = 0
        for lo in range(0, len(active), EMIT_CHUNK_GROUPS):
            hi = min(lo + EMIT_CHUNK_GROUPS, len(active))
            mask = active[lo:hi]
            if pred is not None:
                whole = finals_of(lo, hi, slice(None))
                mask = mask & pred.keep(hi - lo, whole, bounds)
            local = np.flatnonzero(mask)
            if len(local) == 0:
                continue
            finals = (
                finals_of(lo, hi, local) if pred is None
                else [a[local] for a in whole]
            )
            end = off + len(local)
            if self._grouped:
                keys = self._interner.keys_of((local + lo).astype(np.int32))
                for c, kv in zip(cols, keys):
                    c[off:end] = kv
            for c, arr in zip(cols[n_keys:], finals):
                c[off:end] = arr
            off = end
        self._window_emitted(j)
        if off < m:
            self._metrics["emit_rows_filtered"] += m - off
            if off == 0:
                return None
            cols = [c[:off] for c in cols]
        # window bounds + canonical timestamp
        for c, v in zip(cols[-3:], bounds):
            c[:] = v
        return RecordBatch(self.schema, cols)

    def _trigger(self, force: bool = False) -> Iterator[RecordBatch]:
        """Emit every window whose end ≤ watermark (trigger_windows,
        grouped_window_agg_stream.rs:220-253).

        With a host-accumulating backend, emission is deferred up to
        ``_emit_lag_s`` after the first window becomes closable: a
        replay-speed feed then closes several windows per device
        round-trip (merge + block gather amortized), while a real-time
        feed — whose stripe is necessarily older than the lag when its
        window closes — emits immediately.  ``force`` bypasses the
        deferral: ingest uses it to freeze closable windows before a
        batch whose rows would otherwise leak late units into them.

        Opens by taking the emission blocks whose fetch has landed — in
        order, without waiting for the rest (``_drain_pending``)."""
        yield from self._drain_pending(wait=False)
        if (
            self._tier is not None
            and self._tier.any_spilled
            and self._watermark_ms is not None
            and self._first_open is not None
        ):
            # spilled windows the watermark closed emit straight from
            # their stored planes — they are all below first_open, so
            # ascending-window output order is preserved
            wmf = int(
                watermark_floor(
                    self._watermark_ms, self.length_ms, self.slide_ms
                )
            )
            due = self._tier.due_windows(wmf)
            if due:
                # blocks in flight hold older windows still
                yield from self._drain_pending()
            for j in due:
                b = self._finalize_rows(j, self._tier.emit_rows(j))
                if b is not None:
                    yield b
        if self._obs_wm_lag and self._watermark_ms is not None:
            # watermark lag (wall − watermark): how far event time trails
            # real time at this trigger.  Gauge = latest, histogram =
            # distribution (its max is the run's peak lag).
            lag = time.time() * 1000.0 - self._watermark_ms
            self._obs_wm_lag.set(lag)
            self._obs_wm_lag_hist.observe(lag)
        n_close = self._closable()
        if n_close == 0:
            if (
                self._backend.accumulates_host
                and self._backend.pending_rows >= self._merge_rows
            ):
                self._flush("rows")
            return
        # why the close's flush happens, for the backend's count
        reason = "forced" if force else "close"
        if self._backend.accumulates_host and not force:
            if self._backend.pending_rows >= self._merge_rows:
                reason = "rows"
            elif self._emit_lag_s > 0:
                age = time.perf_counter() - (self._stripe_wall or 0.0)
                if age >= self._emit_lag_s:
                    reason = "lag"
                elif self._stripe_fits_more():
                    return
        # the trigger acts.  Only now does it open its span: it runs after
        # every batch and every hint and mostly returns above, and the
        # deferral test is not worth two clock reads each time (it stays
        # in the self time of the outer span)
        with self._phases.phase(
            "trigger", batch=self._metrics["batches_in"], n=n_close
        ):
            yield from self._close_windows(n_close, reason)

    def _close_windows(
        self, n_close: int, reason: str = "close"
    ) -> Iterator[RecordBatch]:
        """Flush the stripe (``reason``: what set the close off), then
        gather (and, where nothing is deferred, emit) the ``n_close``
        windows the watermark has closed."""
        if self._backend.accumulates_host:
            self._flush(reason)
        # every older block leaves first: at most one close's blocks are in
        # flight (the device holds no more of them than one close makes),
        # and windows leave in ascending order
        yield from self._drain_pending()
        # row-shipping backends emit in the same trigger; so does a zero
        # emit lag (the CPU default): its streams may pause, and a block
        # left in flight would hold a paused stream's output until the next
        # batch arrives.  Everywhere else the block's bytes cross beside
        # ingest and the trigger after the batch they land in takes them
        deferred = self._backend.accumulates_host and self._emit_lag_s > 0
        while n_close > 0:
            # pow2 block sizes bound the compiled gather variants
            n = 1 << min(3, (n_close).bit_length() - 1)
            n = min(n, self._spec.window_slots)
            live = len(self._interner) if self._grouped else 1
            j0 = self._first_open
            with self._phases.phase("gather", window=j0, n=n):
                handle = None
                if self._finals_specs is not None:
                    handle = self._backend.read_reset_block_finals_start(
                        j0 % self._spec.window_slots, n, live_groups=live,
                    )
                is_finals = handle is not None
                if not is_finals:
                    handle = self._backend.read_reset_block_start(
                        j0 % self._spec.window_slots, n,
                        live_groups=live,
                        # only when the lean layout actually differs — else
                        # the lean=True program would be a duplicate
                        # compilation of the full one
                        lean=(
                            not self._any_nulls_seen
                            and sa.lean_possible(self._spec)
                        ),
                    )
            self._pending_emit.append((
                j0, n, handle, is_finals,
                self._fetch_block(j0, n, handle) if deferred else None,
            ))
            self._first_open += n
            n_close -= n
        if not deferred:
            yield from self._drain_pending()

    def _stripe_fits_more(self) -> bool:
        """Can the stripe still absorb the next slide unit without
        overflowing its span? (else defer no further — flush and emit)"""
        span_now = self._max_win_seen - self._first_open + 1
        return span_now + 1 < HostPartialStripe.U_MAX

    def _flush(self, reason: str = "forced") -> None:
        # counters reconcile from backend.merges in metrics()
        self._join_acc()
        self._backend.flush_pending(reason)

    def _emit_window(self, j: int) -> RecordBatch | None:
        """Read, reset and finalize ring slot ``j`` alone — the end-of-stream
        flush of the windows the watermark never closed."""
        slot = j % self._spec.window_slots
        with self._phases.phase("finalize", window=j, n=1):
            with self._phases.phase("d2h_wait", window=j, n=1), span(
                "window.emit", op=self.name, window=j * self.slide_ms
            ):
                rows = self._backend.read_slot(slot)
                self._backend.reset_slot(slot)
            return self._finalize_rows(j, rows)

    def _finalize_rows(self, j: int, rows: dict) -> RecordBatch | None:
        """Finalize one window's component planes into an emission batch
        — shared by the ring's block and slot paths and the cold tier's
        emit-from-store path (identical output either way)."""
        ngroups = len(self._interner) if self._grouped else 1
        return self._emit_window_batch(
            j, rows[sa.ROW_COUNT.label][:ngroups] > 0, self._finals_of(rows)
        )

    def _finals_of(self, rows: dict):
        """Host finalization of a chunk of one window's component planes,
        as ``_emit_window_batch`` asks for it."""
        return lambda lo, hi, local: sa.finalize(
            self._agg_specs,
            {label: arr[lo:hi] for label, arr in rows.items()},
            local,
        )

    def _window_emitted(self, j: int) -> None:
        """Bookkeeping of window ``j``'s emission, once however many
        batches carry its rows — the one place every emission path funnels
        through."""
        self._metrics["windows_emitted"] += 1
        self._obs_windows.add(1)
        if self._obs_emit_lag:
            # end-to-end event-time emission latency
            self._obs_emit_lag.observe(
                time.time() * 1000.0 - (j * self.slide_ms + self.length_ms)
            )
        if self._dr_lineage is not None:
            # sampled record lineage: close every chain whose tagged row
            # fell inside this window
            self._dr_lineage.emitted(
                self._dr_node_id,
                j * self.slide_ms,
                j * self.slide_ms + self.length_ms,
            )

    # -- checkpointing ----------------------------------------------------
    # Snapshot = device state buffers + interner + watermark scalars, the
    # analog of CheckpointedGroupedWindowAggStream
    # (grouped_window_agg_stream.rs:84-102,355-418) — but taken from an
    # ALIGNED in-band marker, and without the reference's drain-then-reseed
    # trick (:379-394): export_state reads buffers without mutating them.
    def enable_checkpointing(self, node_id: str, coord, orch) -> None:
        self._ckpt = (coord, f"window_{node_id}")
        self._restore()

    def _snapshot(self, epoch: int) -> None:
        """Dispatch an epoch snapshot WITHOUT blocking on the device→host
        transfer: flush host partials, clone the ring on device, start its
        async host copy, and capture the host-side meta NOW (it mutates
        with the very next batch).  ``_release_snapshot`` materializes and
        persists it — and only then releases the held barrier marker, so
        the commit protocol (snapshot durable before the marker reaches
        the root) is preserved while the transfer overlaps downstream
        work and the next source read."""
        # device state must include everything the stripe holds — the
        # snapshot is the recovery point
        self._flush()
        meta = {
            "epoch": epoch,
            "first_open": self._first_open,
            "max_win_seen": self._max_win_seen,
            "watermark_ms": self._watermark_ms,
            "window_slots": self._spec.window_slots,
            "group_capacity": self._backend.group_capacity,
            "interner": self._interner.snapshot() if self._grouped else None,
            # variance pivots: shifted sums are only comparable under the
            # same K, so K must survive restart with the state it shifted
            "var_shift": dict(self._var_shift),
            "any_nulls_seen": self._any_nulls_seen,
        }
        if self._tier is not None and self._tier.any_spilled:
            coord, key = self._ckpt
            # spilled window planes commit under this SAME epoch; the
            # ring export below holds only the resident windows
            meta["spill_windows"] = self._tier.snapshot_refs(
                coord, key, epoch
            )
        self._pending_snapshot = (
            epoch, meta, self._backend, self._backend.export_start()
        )

    def _release_snapshot(self) -> Iterator:
        """Persist a pending snapshot and release its held marker.  MUST
        run before any output derived from post-marker input leaves this
        operator — a downstream operator that saw post-marker emissions
        before the marker would snapshot state AHEAD of ours, and a
        restore would double-apply those windows."""
        if self._pending_snapshot is not None:
            from denormalized_tpu.state.serialization import pack_snapshot

            epoch, meta, backend, handle = self._pending_snapshot
            self._pending_snapshot = None
            coord, key = self._ckpt
            coord.put_snapshot(
                key, epoch, pack_snapshot(meta, backend.export_finish(handle))
            )
        if self._held_marker is not None:
            marker, self._held_marker = self._held_marker, None
            yield marker

    def _restore(self) -> None:
        from denormalized_tpu.state.serialization import unpack_snapshot

        coord, key = self._ckpt
        blob = coord.get_snapshot(key)
        if blob is None:
            return
        meta, arrays = unpack_snapshot(blob)
        n_dev = 1 if self._mesh is None else self._mesh.devices.size
        old = self._spec
        self._spec = sa.WindowKernelSpec(
            components=old.components,
            num_value_cols=old.num_value_cols,
            window_slots=int(meta["window_slots"]),
            group_capacity=_round_capacity(int(meta["group_capacity"]), n_dev),
            length_ms=old.length_ms,
            slide_ms=old.slide_ms,
            accum_dtype=old.accum_dtype,
            compensated=old.compensated,
        )
        old_backend = self._backend
        self._backend = self._new_backend()
        self._carry_counters(old_backend)
        self._prepare_emission()
        self._backend.import_(arrays)
        self._first_open = meta["first_open"]
        self._max_win_seen = meta["max_win_seen"]
        self._watermark_ms = meta["watermark_ms"]
        # restored state may hold counts < row counts (nulls before the
        # kill); unless the snapshot says otherwise, stay on full gathers
        self._any_nulls_seen = bool(meta.get("any_nulls_seen", True))
        self._var_shift = dict(meta.get("var_shift") or {})
        if self._grouped and meta["interner"] is not None:
            self._interner = GroupInterner.restore(meta["interner"])
        refs = meta.get("spill_windows")
        if refs:
            coord, key = self._ckpt
            if self._tier is not None:
                self._tier.restore_refs(coord, key, refs)
            else:
                self._restore_spilled_resident(coord, key, refs)

    def _restore_spilled_resident(self, coord, key: str, refs: dict) -> None:
        """Budget removed since the checkpoint: spilled window planes
        merge back into the ring (first_open lowers to cover them)."""
        from denormalized_tpu.common.errors import StateError
        from denormalized_tpu.state.serialization import unpack_snapshot

        js = sorted(int(k) for k in refs)
        new_first = min(js + ([self._first_open] if self._first_open is not None else []))
        self._ensure_capacity(self._max_win_seen - new_first)
        self._first_open = new_first
        host = {
            label: np.array(buf)
            for label, buf in self._backend.export().items()
        }
        W = self._spec.window_slots
        for j in js:
            raw = coord.get_snapshot(f"{key}:spill:{refs[str(j)]}")
            if raw is None:
                raise StateError(
                    f"checkpoint references spilled window {j} but the "
                    "epoch holds no such snapshot"
                )
            _bmeta, arrays = unpack_snapshot(raw)
            for label, arr in arrays.items():
                host[label][j % W, : arr.shape[0]] = arr
        self._backend.import_(host)

    # -- stream loop -----------------------------------------------------
    def run(self) -> Iterator[StreamItem]:
        try:
            yield from self._run_inner()
        finally:
            try:
                self._shutdown_acc()
            finally:
                # the -d2h worker: a stream that ends takes its blocks
                # first, so a fetch is still running only in one that is
                # abandoned — its block is dropped with it
                ex, self._emit_exec = self._emit_exec, None
                if ex is not None:
                    ex.shutdown(wait=True, cancel_futures=True)

    def _shutdown_acc(self) -> None:
        """Stop the host-pipeline worker (if any).  Joins the in-flight
        task so a failure in the stream's final batches still surfaces,
        and releases the thread — one leaked worker per finished stream
        otherwise."""
        ex, self._acc_exec = self._acc_exec, None
        if ex is not None:
            try:
                self._join_acc()
            finally:
                ex.shutdown(wait=True)

    def _run_inner(self) -> Iterator[StreamItem]:
        ph = self._phases
        for item in self._doctor_input():
            if isinstance(item, RecordBatch):
                # materialize any in-flight snapshot and release its
                # marker BEFORE producing output from post-marker input
                # (alignment invariant, see _release_snapshot)
                yield from self._release_snapshot()
                # emissions are materialized INSIDE the timing bracket so
                # the span and the batch-time histogram measure this
                # operator's own work, not time spent suspended while
                # downstream consumed the yielded windows
                t0 = time.perf_counter()
                with ph.phase(
                    "process_batch", "other", op=self.name,
                    rows=item.num_rows, batch=self._metrics["batches_in"],
                ):
                    out = list(self._process_batch(item))
                self._note_batch(t0, item.num_rows)
                yield from out
                continue
            if isinstance(item, WatermarkHint):
                if item.kind == "partition":
                    # authoritative per-partition watermark: from now on
                    # batch min-ts must not advance the watermark
                    self._src_watermarks = True
                    if item.is_announcement:
                        yield item  # pure mode announcement
                        continue
                name, handler = "hint", self._on_hint
            elif isinstance(item, Marker):
                name, handler = "marker", self._on_marker
            elif isinstance(item, EndOfStream):
                name, handler = "eos", self._on_eos
            else:
                continue
            # same bracket contract as the batch path, for the items
            # dnz_op_batch_ms does not cover: every trigger, flush, gather
            # and emission a hint sets off is timed here
            t0 = time.perf_counter()
            with ph.phase(name, "other", batch=self._metrics["batches_in"]):
                out = list(handler(item))
            self._metrics["hint_path_ms"] += (time.perf_counter() - t0) * 1e3
            yield from out
            if name == "eos":
                return

    def _on_hint(self, item: WatermarkHint) -> Iterator[StreamItem]:
        if item.kind == "partition":
            # barrier alignment: a held marker must reach downstream
            # before any trigger output this hint produces (same
            # invariant as the batch path)
            yield from self._release_snapshot()
            if self._watermark_ms is None or item.ts_ms > self._watermark_ms:
                self._watermark_ms = item.ts_ms
                # normal trigger: these hints arrive continuously (one per
                # advancing batch), so the emit-lag deferral keeps working
                # — no force, no drain
                yield from self._trigger()
            yield WatermarkHint(
                min(item.ts_ms, self._output_low_watermark(item.ts_ms)),
                kind="partition",
            )
            return
        # idle source: advance event time and close what's ready, then
        # forward the hint for downstream stateful operators — CLAMPED
        # below this operator's lowest possible future emission timestamp
        # (emissions are stamped with the window START, so an unclamped
        # forward would make a downstream operator drop our later closed
        # windows as late)
        yield from self._release_snapshot()
        if self._watermark_ms is None or item.ts_ms > self._watermark_ms:
            self._watermark_ms = item.ts_ms
            # force: the emit-lag deferral assumes another batch (or hint)
            # will follow, but an idle period delivers exactly ONE hint — a
            # deferred emission would never run and the final windows would
            # sit closed-but-unemitted, defeating the feature.  Likewise
            # wait for the emission blocks NOW: a block this trigger
            # dispatched is normally taken by a later trigger, once it has
            # landed, and there is no later item.
            yield from self._trigger(force=True)
            yield from self._drain_pending()
        yield WatermarkHint(
            min(item.ts_ms, self._output_low_watermark(item.ts_ms))
        )

    def _on_marker(self, item: Marker) -> Iterator[StreamItem]:
        yield from self._drain_pending()
        yield from self._release_snapshot()  # an earlier epoch
        if self._ckpt is not None:
            self._snapshot(item.epoch)
            self._held_marker = item
        else:
            yield item

    def _on_eos(self, item: EndOfStream) -> Iterator[StreamItem]:
        # pending blocks are watermark-CLOSED windows: they emit even when
        # the unclosed-window flush is disabled
        yield from self._drain_pending()
        yield from self._release_snapshot()
        if self.emit_on_close and self._first_open is not None:
            self._flush()
            if self._tier is not None and self._tier.any_spilled:
                # spilled windows all sit below first_open: flushing them
                # first keeps ascending order
                for j in self._tier.due_windows(self._max_win_seen + 1):
                    b = self._finalize_rows(j, self._tier.emit_rows(j))
                    if b is not None:
                        yield b
            for j in range(self._first_open, self._max_win_seen + 1):
                b = self._emit_window(j)
                if b is not None:
                    yield b
            self._first_open = self._max_win_seen + 1
        else:
            # no final flush ran — still fence the worker so an async
            # accumulate failure cannot be swallowed
            self._join_acc()
        yield EOS
