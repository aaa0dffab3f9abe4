"""Sharded window-state backends — TPU-native scale-out of the hot path.

The reference scales grouped window aggregation with a hash
``RepartitionExec`` exchange feeding per-partition streams, and merges
ungrouped aggregates through a Partial→Final operator pair
(SURVEY.md §2.4, coalesce_before_streaming_window_aggregate.rs:63-70,
planner/streaming_window.rs:133-153).  On a TPU mesh both strategies become
sharding layouts of the SAME device kernel (`update_state_impl`), wrapped in
``shard_map`` so XLA owns the collectives:

- :class:`KeyShardedWindowState` — the hash-partition analog.  Accumulator
  buffers are sharded over the group axis (each device owns a contiguous
  block of group ids); the batch is replicated and every device applies only
  its own block via masking.  Update needs NO collective (the "exchange"
  rides the input broadcast over ICI); emission gathers one window row
  (G-sized) per device.  Right choice for high-cardinality state that must
  not be duplicated per device.

- :class:`PartialFinalWindowState` — the Partial→Final analog.  Rows are
  sharded across devices (data parallel); every device keeps a full local
  copy of the (small) state and emission merges with ``psum`` / ``pmin`` /
  ``pmax`` at watermark triggers only.  Right choice for low-cardinality
  aggregation at extreme ingest rates: input transfer is 1/n per device and
  the merge collective runs once per window, not per batch.

- :class:`SingleDeviceWindowState` — the degenerate 1-device backend, and
  :class:`PartialMergeWindowState`, the same ring fed with host-reduced
  partials instead of rows (``device_strategy='auto'`` on every TPU and
  CPU backend).

- :class:`KeyShardedPartialMergeWindowState` — ``partial_merge`` over the
  key-sharded ring, ``auto``'s pick on a 1-D mesh: the host splits each
  packed stripe unit by key block (binary search over its ascending cell
  ids), a device receives and folds its own block with the program a single
  device runs, and emission runs the single device's programs on every
  block.  Every program that touches the ring is a ``shard_map`` over
  ``P(None, "keys")``: nothing is replicated, gathered or left to GSPMD.

All present the same interface to the window operator, which stays
oblivious to the layout.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe
from denormalized_tpu.parallel.mesh import KEY_AXIS, SLICE_AXIS, shard_map
from denormalized_tpu.runtime.tracing import NULL_CLOCK


def _prewarm() -> bool:
    """Whether the backends compile their program ladders up front: on a
    TPU, where an unseen shape compiling mid-stream stalls the stream for
    seconds.  Elsewhere compiles are cheap and the ladders are skipped."""
    return jax.default_backend() == "tpu"


class WindowStateBackend:
    """Interface the window operator drives."""

    spec: sa.WindowKernelSpec  # device-local spec
    # True when the backend reduces rows on host and ships partial
    # aggregates (the ``partial_merge`` strategy): the operator then calls
    # ``accumulate``/``flush_pending`` instead of per-batch ``update``
    accumulates_host: bool = False
    # link-traffic accounting (numpy-payload bytes handed to/from the
    # device): these feed the benchmark's bytes-per-event metrics and
    # chip_smoke.py's per-leg lines
    bytes_h2d: int = 0
    bytes_d2h: int = 0
    # the driving operator's phase clock (runtime/tracing.py): the stripe
    # flush opens ``window.flush`` on it wherever the flush is set off
    # (trigger, growth, snapshot, or span overflow inside ``accumulate``)
    phases = NULL_CLOCK
    # contiguous blocks of group ids the ring is split into, one a device:
    # above 1 on the key-sharded layouts
    key_blocks: int = 1
    # why a stripe flush happened (``flush_pending``'s ``reason``), one
    # counter a reason, summing to the flushes that found rows (``merges``):
    # ``span`` a unit outside the stripe's span met inside ``accumulate``,
    # ``rows`` the operator's ``partial_merge_rows`` or the stripe's row cap,
    # ``close`` a window close nothing deferred, ``lag`` a close taken when
    # the deferral clock ran out, ``forced`` everything else (a hint, a
    # marker, the end of the stream, growth, a lowered ring base, the cold
    # tier)
    FLUSH_REASONS = ("span", "rows", "close", "lag", "forced")

    def stripe_counters(self) -> dict:
        """What the host stripe's flushes cost, as ``metrics()`` names it:
        ``stripe_cells_active``, ``stripe_cells_shipped``,
        ``stripe_bytes_touched``, ``stripe_bytes_packed``; what the merges
        fold on the device, ``merge_window_folds`` and
        ``merge_fold_entries``; why each flush happened,
        ``flush_reason_<reason>`` — all 0 for a row-shipping backend — and
        ``merge_cells_by_shard``, the active cells again by the key block
        (device) they fell in: a list of ``key_blocks`` sums that add up to
        ``stripe_cells_active``, and the same numbers one by one as
        ``merge_cells_shard_<i>``."""
        stripe = getattr(self, "_stripe", None)
        out = {
            f"stripe_{name}": getattr(stripe, name, 0)
            for name in HostPartialStripe.COUNTERS
        }
        for name in HostPartialStripe.MERGE_COUNTERS:
            out[f"merge_{name}"] = getattr(stripe, name, 0)
        reasons = getattr(self, "flush_reasons", {})
        for reason in self.FLUSH_REASONS:
            out[f"flush_reason_{reason}"] = reasons.get(reason, 0)
        by_shard = (
            [0] * self.key_blocks if stripe is None
            else stripe.cells_by_block.tolist()
        )
        out["merge_cells_by_shard"] = by_shard
        for i, cells in enumerate(by_shard):
            out[f"merge_cells_shard_{i}"] = cells
        return out

    def _count_rows_h2d(self, *arrays) -> None:
        """Row shipping: the batch's arrays cross from the host once — on
        a mesh to one device, from which the update program fans them out
        over the interconnect (``bytes_h2d`` counts what the host sends)."""
        self.bytes_h2d += sum(
            int(np.asarray(a).nbytes) for a in arrays if a is not None
        )

    def carry_stripe_counters(self, old: "WindowStateBackend") -> None:
        """Take over the stripe counts and the flush counts of the backend
        this one replaces."""
        if self.accumulates_host and old.accumulates_host:
            self._stripe.carry_counters(old._stripe)
            for reason, n in old.flush_reasons.items():
                self.flush_reasons[reason] += n

    @property
    def strategy_name(self) -> str:
        """What actually executes — a constant next to each backend, so a
        rename or new subclass cannot silently mislabel ``metrics()``'s
        ``strategy_resolved`` field."""
        return type(self).__name__

    @property
    def group_capacity(self) -> int:
        """Total group-id capacity visible to the host interner."""
        raise NotImplementedError

    def update(self, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        raise NotImplementedError

    def flush_pending(self, reason: str = "forced") -> None:
        """Merge any host-accumulated partials into device state.  No-op
        for row-shipping backends.  MUST be called before emission,
        export, or capacity growth on host-accumulating backends.
        ``reason``: one of ``FLUSH_REASONS``, counted where the flush found
        rows to merge."""

    def read_reset_block(self, first_slot: int, n: int) -> dict[str, "np.ndarray"]:
        """Read and reset n consecutive ring slots; default = per-slot
        loop (sharded layouts)."""
        rows = []
        for i in range(n):
            slot = (first_slot + i) % self.spec.window_slots
            rows.append(self.read_slot(slot))
            self.reset_slot(slot)
        return {
            label: np.stack([r[label] for r in rows])
            for label in rows[0]
        }

    # -- async emission pipeline: start dispatches the device work and
    # returns a handle; finish materializes it on host.  The default is
    # synchronous (start does the work); device backends override start to
    # return in-flight device arrays so the transfer overlaps ingest.
    # ``finish`` touches the handle alone — the operator runs it on a worker
    # thread beside ingest — and ``count_block_d2h`` books the block it
    # returned, on the thread that drives the backend.
    def read_reset_block_start(
        self, first_slot: int, n: int, live_groups=None, lean=False
    ):
        return self.read_reset_block(first_slot, n)

    def read_reset_block_finish(self, handle) -> dict[str, "np.ndarray"]:
        return handle

    def count_block_d2h(self, block: dict) -> None:
        """Add a block ``read_reset_block_finish`` returned to
        ``bytes_d2h``.  Nothing here: ``read_slot`` counted it."""

    # -- emission prewarm: the operator calls the one its plan can reach --
    def prepare_gather(self) -> None:
        """Pre-compile the component-gather emission programs.  No-op for
        backends that read slots one by one."""

    # -- on-device finalization (optional) -----------------------------
    def prepare_finals(self, agg_specs: tuple) -> None:
        """Announce the output aggregate specs so the backend can
        pre-compile finals-emission programs.  No-op for backends that
        don't finalize on device."""

    def read_reset_block_finals_start(
        self, first_slot: int, n: int, live_groups=None
    ):
        """Dispatch a finals emission (final output planes + active
        bitmask, see segment_agg._finals_and_reset) for n ring slots —
        or return None when this layout doesn't support it (caller falls
        back to the component-plane path)."""
        return None

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        raise NotImplementedError

    def reset_slot(self, slot: int) -> None:
        raise NotImplementedError

    def export(self) -> dict[str, np.ndarray]:
        """(W, G_total) host snapshot for checkpoint/growth."""
        raise NotImplementedError

    # -- async export (checkpointing): start dispatches an on-device clone
    # plus its host copy and returns a handle; finish materializes it.
    # Default is synchronous.
    def export_start(self):
        return self.export()

    def export_finish(self, handle) -> dict[str, "np.ndarray"]:
        return handle

    def import_(self, host_state: dict[str, np.ndarray]) -> None:
        raise NotImplementedError


class SingleDeviceWindowState(WindowStateBackend):
    strategy_name = "row_shipping:scatter"

    def __init__(self, spec: sa.WindowKernelSpec):
        self.spec = spec
        self._state = sa.init_state(spec)

    def prepare_gather(self) -> None:
        if _prewarm():
            # pre-compile emission gather programs for the block sizes and
            # group buckets the trigger will actually request: an unseen
            # (n, g_bucket, lean) tuple compiling mid-stream stalls the
            # stream for seconds at a wide ring.  Running them on the
            # freshly-initialized state is a no-op (slots are already at
            # init values).  The runtime bucket is pow2(live groups),
            # floor 1024, cap G — warm the two endpoints; a pow2 crossing
            # in between pays a one-off compile (and hits the persistent
            # XLA cache on any later run).  Both layout variants are
            # warmed when they differ: a stream flips lean→full on its
            # first null, and a restored stream starts full.
            spec = self.spec
            variants = {False, sa.lean_possible(spec)}
            for n in (1, 2, 4, 8):
                if n <= spec.window_slots:
                    for g_bucket in {min(1024, spec.group_capacity),
                                     spec.group_capacity}:
                        for lean in variants:
                            self._gather(
                                n, g_bucket, np.int32(0), lean
                            )

    @property
    def group_capacity(self) -> int:
        return self.spec.group_capacity

    def update(self, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        self._count_rows_h2d(values, colvalid, win_rel, rem, gid, row_valid)
        self._state = sa.update_state(
            self.spec,
            self._state,
            jnp.asarray(values),
            jnp.asarray(colvalid),
            jnp.asarray(win_rel),
            jnp.asarray(rem),
            jnp.asarray(gid),
            jnp.asarray(row_valid),
            jnp.asarray(base_mod, dtype=jnp.int32),
        )

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        out = sa.read_slot(self.spec, self._state, slot)
        self.bytes_d2h += sum(int(a.nbytes) for a in out.values())
        return out

    def reset_slot(self, slot: int) -> None:
        self._state = sa.reset_slot(
            self.spec, self._state, jnp.asarray(slot, dtype=jnp.int32)
        )

    def read_reset_block_start(
        self, first_slot: int, n: int, live_groups=None, lean=False
    ):
        """Dispatch the fused gather+reset and return the in-flight device
        arrays WITHOUT blocking — the device→host transfer overlaps
        whatever the host does next (typically accumulating the next
        stripe).

        ``live_groups`` (the interner's current size) bounds the
        transferred group width: gids are interner-dense, so every cell
        at index ≥ live_groups is still at its init value and need not
        cross the link.  The width is bucketed to a pow2 (floor 1024) so
        the (n, bucket) program ladder stays ≤ log2(G/1024) entries per
        block size — the bucket only grows when the interner crosses a
        pow2 boundary, a one-off compile, while the transfer shrinks by
        the full capacity/cardinality ratio (e.g. 2.6× at 100K keys in a
        262K-capacity ring, and ~all of it when capacity is
        over-provisioned)."""
        assert n <= self.spec.window_slots  # slots must be distinct
        out = self._gather(
            n, self._live_bucket(live_groups), np.int32(first_slot), lean,
        )
        for arr in out.values():
            arr.copy_to_host_async()
        return out

    # -- the two emission programs, run on the ring this backend holds: a
    # key-sharded mesh overrides them with the same bodies under shard_map.
    # ``g_bucket`` is a prefix of ``self.spec.group_capacity`` groups: of
    # the ring here, of every device's own key block there.  ``first_slot``
    # comes as a host scalar, which goes to every device of a mesh with the
    # call (one put on a device would be resharded to the others first)
    def _gather(self, n: int, g_bucket: int, first_slot, lean: bool) -> dict:
        self._state, out = sa._gather_and_reset(
            self.spec, n, g_bucket, self._state, first_slot, lean
        )
        return out

    def _finals(self, n: int, g_bucket: int, first_slot) -> dict:
        self._state, out = sa._finals_and_reset(
            self.spec, self._finals_specs, n, g_bucket, self._state,
            first_slot,
        )
        return out

    def read_reset_block_finish(self, handle) -> dict[str, np.ndarray]:
        return jax.device_get(handle)

    def count_block_d2h(self, block: dict) -> None:
        self.bytes_d2h += sum(int(a.nbytes) for a in block.values())

    def prepare_finals(self, agg_specs: tuple) -> None:
        self._finals_specs = tuple(agg_specs)
        if _prewarm():
            # pre-compile the finals ladder like the component-gather one
            # in prepare_gather: an unseen (n, bucket) pair compiling
            # mid-stream stalls the stream for seconds at a wide ring.
            # Every n the operator's block sizes reach (_close_windows:
            # powers of two up to 8) is reachable on any plan: one step of
            # the watermark can pass several window ends (a feed gap, an
            # idle partition timing out, a stripe of several windows at
            # replay speed).  Each program runs once here, so the device's
            # peak memory includes one n = 8 block the traffic may never ask
            # for (1.6 GB at 10M groups, freed at once).
            for n in (1, 2, 4, 8):
                if n <= self.spec.window_slots:
                    for g_bucket in {min(1024, self.spec.group_capacity),
                                     self.spec.group_capacity}:
                        self._finals(n, g_bucket, np.int32(0))

    def _live_bucket(self, live_groups) -> int:
        """Transferred group width: pow2 of the interner's live size
        (floor 1024), capped at the capacity of the ring a device holds —
        the single bucketing policy for every emission ladder (component
        gather AND finals), so both prewarm sets stay aligned with runtime
        requests.  On a key-sharded mesh every device hands back this
        prefix of its own key block: ids are dealt in order, so either the
        live ids all lie in block 0's prefix, or the prefix is the whole
        block — position ``p`` of the assembled row is group ``p`` for
        every live ``p`` either way."""
        g_bucket = self.spec.group_capacity
        if live_groups is not None:
            g_bucket = min(
                g_bucket,
                max(1024, 1 << max(0, int(live_groups) - 1).bit_length()),
            )
        return g_bucket

    def read_reset_block_finals_start(
        self, first_slot: int, n: int, live_groups=None
    ):
        specs = getattr(self, "_finals_specs", None)
        if specs is None:
            return None
        assert n <= self.spec.window_slots
        out = self._finals(
            n, self._live_bucket(live_groups), np.int32(first_slot),
        )
        for arr in out.values():
            arr.copy_to_host_async()
        return out

    def export(self) -> dict[str, np.ndarray]:
        return sa.export_state(self._state)

    def export_start(self):
        snap = sa.clone_state(self._state)
        for arr in snap.values():
            arr.copy_to_host_async()
        return snap

    def export_finish(self, handle) -> dict[str, np.ndarray]:
        out = jax.device_get(handle)
        self.bytes_d2h += sum(int(a.nbytes) for a in out.values())
        return out

    def import_(self, host_state: dict[str, np.ndarray]) -> None:
        self._state = sa.import_state(self.spec, host_state)


class _HostPartialMixin:
    """Shared host-stripe machinery for partial_merge backends: batch
    chunk-folding, flush orchestration, and merge-program prewarming.
    Concrete classes provide ``_merge(packed, a_pad)``."""

    accumulates_host = True

    def _init_host_partial(self, stripe_group_capacity: int) -> None:
        self._stripe = HostPartialStripe(
            self.spec, stripe_group_capacity, self.key_blocks
        )
        self._pending_base_mod = 0
        self.flush_reasons = dict.fromkeys(self.FLUSH_REASONS, 0)
        if _prewarm():
            # pre-compile every merge program with a no-op stripe: which
            # bucket a flush lands in depends on runtime pacing, and an
            # unseen size mid-stream is a multi-second compile.  Both
            # packed layouts are warmed when the spec has per-column
            # counts: lean (the null-free steady state) and full (the
            # moment a null shows up).
            variants = [False]
            if sa.lean_possible(self.spec):
                variants.append(True)
            stripe = self._stripe
            for lean in variants:
                for a_pad in stripe.transfer_buckets():
                    self._merge(stripe.compact_noop(a_pad, lean), a_pad, lean)
                # dense no-op: fold-neutral planes (a zeroed min plane
                # would clobber state with 0.0 — dense has no validity
                # mask); layout owned by the stripe.  A flush's dense
                # units go stacked, U to a call
                self._merge(
                    np.stack([stripe.dense_noop(lean)] * stripe.U, axis=-3),
                    stripe.block_cells, lean, dense=True,
                )

    @property
    def pending_rows(self) -> int:
        return self._stripe.rows

    @property
    def merges(self) -> int:
        """Stripe flushes that found rows and merged them: the sum of the
        counts by reason."""
        return sum(self.flush_reasons.values())

    def update(self, *a, **k):
        raise RuntimeError(
            "partial_merge backend consumes host partials via accumulate(); "
            "the operator must not ship rows to it"
        )

    def accumulate(
        self, units_rel, rem, gid, values64, colvalid, keep, base_mod,
        u_min=None, u_max=None,
    ) -> None:
        """Fold one batch into the host stripe, flushing/chunking so no
        row is ever dropped: a batch spanning more slide units than a
        stripe can hold (catch-up reads, giant arrival batches) is folded
        in unit-range chunks with a merge between them — the partial-path
        equivalent of the scatter path's W growth.  ``u_min`` / ``u_max``:
        the extremes of ``units_rel``, where the caller knows them (the
        operator does, from its pass over the timestamps); the steady path
        then scans no array."""
        units_rel = np.asarray(units_rel, np.int64)
        stripe = self._stripe
        span_u = stripe.U  # units a stripe holds
        if keep is None and len(units_rel):
            # fast path for the steady state: no late/keep mask and the
            # whole batch fits the CURRENT stripe as-is — fold it in one
            # call with no boolean scans or masked copies.  Anything that
            # would need a flush (span overflow, row cap, units behind
            # u_base) falls through to the chunk loop below, which keeps
            # the one and only copy of the flush/admission logic.
            if u_min is None:
                u_min, u_max = int(units_rel.min()), int(units_rel.max())
            base = stripe.u_base if not stripe.is_empty() else u_min
            if (
                u_min >= base
                and u_max <= base + span_u - 1
                and (
                    stripe.is_empty()
                    or stripe.rows + len(units_rel)
                    <= stripe.MAX_STRIPE_ROWS
                )
            ):
                if stripe.is_empty():
                    self._pending_base_mod = int(base_mod)
                stripe.add_batch(
                    units_rel, rem, gid, values64, colvalid, None,
                    u_min, u_max,
                )
                return
        remaining = (
            np.ones(len(units_rel), bool) if keep is None else keep.copy()
        )
        while remaining.any():
            u0 = int(units_rel[remaining].min())
            if not stripe.is_empty() and (
                u0 < stripe.u_base
                or stripe.rows >= stripe.MAX_STRIPE_ROWS
            ):
                self.flush_pending(
                    "span" if u0 < stripe.u_base else "rows"
                )
            base = stripe.u_base if not stripe.is_empty() else u0
            chunk = (
                remaining
                & (units_rel >= base)
                & (units_rel <= base + span_u - 1)
            )
            n_chunk = int(chunk.sum())
            if n_chunk == 0 or (
                not stripe.is_empty()
                and stripe.rows + n_chunk > stripe.MAX_STRIPE_ROWS
            ):
                # no row of the batch lies in the stripe's span, or the
                # rows that do would pass the row cap
                self.flush_pending("span" if n_chunk == 0 else "rows")
                continue
            if stripe.is_empty():
                self._pending_base_mod = int(base_mod)
            stripe.add_batch(
                units_rel, rem, gid, values64, colvalid, chunk
            )
            remaining &= ~chunk

    def flush_pending(self, reason: str = "forced") -> None:
        if self._stripe.is_empty():
            return
        with self.phases.phase(
            "flush", key="flush_send", rows=self._stripe.rows
        ):
            # one transfer and one merge program per compact unit, and one
            # for all the dense units together (stacked, the stack padded
            # with no-op units to the stripe's span): a stripe of many small
            # units costs one dispatch, not one each.  ``bytes_h2d`` counts
            # every byte the host sends, once: on a mesh a packed matrix is
            # a block a device and each block goes to its own device only
            stripe = self._stripe
            dense_units = []
            for packed, a_pad, lean, dense in stripe.take_packed(
                self._pending_base_mod, self.phases
            ):
                if dense:
                    dense_units.append(packed)
                    continue
                self.bytes_h2d += int(packed.nbytes)
                self._merge(packed, a_pad, lean, dense)
            if dense_units:
                pad = stripe.U - len(dense_units)
                noop = stripe.dense_noop(lean)
                dense_units += [noop] * pad
                stripe.cells_shipped += pad * stripe.unit_cells
                stripe.bytes_packed += pad * noop.nbytes
                # units stacked in front of the (planes, cells) axes,
                # behind the key-block axis where there is one
                stacked = (
                    np.expand_dims(dense_units[0], -3) if stripe.U == 1
                    else np.stack(dense_units, axis=-3)
                )
                self.bytes_h2d += int(stacked.nbytes)
                self._merge(stacked, stripe.block_cells, lean, True)
            self.flush_reasons[reason] += 1


class PartialMergeWindowState(_HostPartialMixin, SingleDeviceWindowState):
    strategy_name = "partial_merge"

    """Host edge-reduction + device merge (the ``partial_merge`` strategy).

    Rows are reduced on the host into per-(slide-unit, sub, group) partials
    (native C++ single-pass, ops/host_partial.py) and the device folds each
    stripe into the HBM window ring with ONE transfer + ONE program — the
    reference's Partial/Final operator split (planner/streaming_window.rs
    :133-153) applied across the host↔accelerator boundary.  This is the
    right layout whenever the host→device link is narrow relative to the
    ingest rate: traffic scales with group cardinality × window span, not
    row count.  Device state, emission, growth, and checkpointing are
    identical to the scatter path."""

    def __init__(self, spec: sa.WindowKernelSpec):
        super().__init__(spec)
        self._init_host_partial(spec.group_capacity)

    def _merge(
        self, packed: np.ndarray, a_pad: int, lean: bool = False,
        dense: bool = False,
    ) -> None:
        self._state = sa.merge_partials(
            self.spec, self._stripe.SUB, a_pad, lean, dense, self._state,
            jnp.asarray(packed),
        )


# ---------------------------------------------------------------------------


def _mask_to_key_shard(spec: sa.WindowKernelSpec, gid, row_valid):
    """Inside a shard_map body: rebase global group ids onto THIS key
    shard's block and mask out everyone else's rows — the one place the
    key-sharded 'exchange rides the broadcast' trick is implemented (both
    the 1-D and 2-D layouts use it)."""
    G_local = spec.group_capacity
    shard = jax.lax.axis_index(KEY_AXIS)
    local_gid = gid - shard * G_local
    mine = row_valid & (local_gid >= 0) & (local_gid < G_local)
    return jnp.clip(local_gid, 0, G_local - 1), mine


def _ring_specs(spec: sa.WindowKernelSpec) -> dict:
    """The ring's partitioning on a 1-D mesh, a spec a component: window
    slots whole, groups split into key blocks.  Every program below that
    touches the ring takes and returns it under these specs inside
    ``shard_map``, so no compiler decision can gather or replicate it."""
    return {c.label: P(None, KEY_AXIS) for c in spec.components}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _key_sharded_init(spec: sa.WindowKernelSpec, mesh: Mesh):
    """A fresh ring, each device's block made on that device (``spec`` is
    the device-local one)."""
    return shard_map(
        lambda: sa.init_state(spec), mesh=mesh, in_specs=(),
        out_specs=_ring_specs(spec),
    )()


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _key_sharded_update(
    spec: sa.WindowKernelSpec,
    mesh: Mesh,
    state,
    values,
    colvalid,
    win_rel,
    rem,
    gid,
    row_valid,
    base_mod,
):
    def body(state_l, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        local_gid, mine = _mask_to_key_shard(spec, gid, row_valid)
        return sa.update_state_impl(
            spec, state_l, values, colvalid, win_rel, rem, local_gid, mine, base_mod
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(_ring_specs(spec), P(), P(), P(), P(), P(), P(), P()),
        out_specs=_ring_specs(spec),
    )(state, values, colvalid, win_rel, rem, gid, row_valid, base_mod)


class KeyShardedWindowState(WindowStateBackend):
    """Group axis sharded over the mesh; batch replicated; no per-batch
    collectives."""

    strategy_name = "key_sharded"

    def __init__(self, spec: sa.WindowKernelSpec, mesh: Mesh):
        # spec is the GLOBAL spec; each device holds G_total/n
        n = mesh.devices.size
        if spec.group_capacity % n:
            raise ValueError(
                f"group capacity {spec.group_capacity} is not divisible by "
                f"the mesh size {n}"
            )
        self.mesh = mesh
        self.n = n
        self.spec = sa.WindowKernelSpec(
            components=spec.components,
            num_value_cols=spec.num_value_cols,
            window_slots=spec.window_slots,
            group_capacity=spec.group_capacity // n,
            length_ms=spec.length_ms,
            slide_ms=spec.slide_ms,
            accum_dtype=spec.accum_dtype,
            compensated=spec.compensated,
        )
        self.key_blocks = n
        self._sharding = NamedSharding(mesh, P(None, KEY_AXIS))
        # born sharded: every device fills its own block (a full ring made
        # on one device first would be the whole ring's bytes there)
        self._state = _key_sharded_init(self.spec, mesh)

    @property
    def group_capacity(self) -> int:
        return self.spec.group_capacity * self.n

    def update(self, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        self._count_rows_h2d(values, colvalid, win_rel, rem, gid, row_valid)
        self._state = _key_sharded_update(
            self.spec,
            self.mesh,
            self._state,
            jnp.asarray(values),
            jnp.asarray(colvalid),
            jnp.asarray(win_rel),
            jnp.asarray(rem),
            jnp.asarray(gid),
            jnp.asarray(row_valid),
            jnp.asarray(base_mod, dtype=jnp.int32),
        )

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        # jitted traced-slot gather; slicing a G-sharded array gathers one
        # (G_total,) row per component
        out = sa.read_slot(self.spec, self._state, slot)
        self.bytes_d2h += sum(int(a.nbytes) for a in out.values())
        return out

    def reset_slot(self, slot: int) -> None:
        self._state = _key_sharded_reset_slot(
            self.spec, self.mesh, self._state, np.int32(slot)
        )

    def export(self) -> dict[str, np.ndarray]:
        return jax.device_get(self._state)

    def import_(self, host_state: dict[str, np.ndarray]) -> None:
        W = self.spec.window_slots
        G_total = self.group_capacity
        for c in self.spec.components:
            buf = np.full(
                (W, G_total), np.asarray(self.spec.init_value(c)),
                dtype=np.asarray(self.spec.init_value(c)).dtype,
            )
            src = host_state.get(c.label)
            if src is not None:
                w = min(src.shape[0], W)
                g = min(src.shape[1], G_total)
                buf[:w, :g] = src[:w, :g]
            # from the host straight to each device's own block
            self._state[c.label] = jax.device_put(buf, self._sharding)


@functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3, 4, 5), donate_argnums=6
)
def _key_sharded_merge_partials(
    spec: sa.WindowKernelSpec,  # LOCAL spec (G_local per device)
    mesh: Mesh,
    SUB: int,
    a_pad: int,
    lean: bool,
    dense: bool,
    state,
    packed,
):
    """Sharded fold of one host-partial stripe: ``packed`` holds one matrix
    a key block (``HostPartialStripe(..., key_blocks=n)`` split the unit on
    the host, ids local to the block) and device ``b`` receives and folds
    block ``b`` alone — the program a single device runs, over its own
    groups.  The hash exchange of the reference, done by binary search
    before the transfer: no collective, nothing replicated."""

    def body(state_l, packed_l):
        return sa.merge_partials_body(
            spec, SUB, a_pad, state_l, packed_l[0], lean, dense
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(_ring_specs(spec), P(KEY_AXIS)),
        out_specs=_ring_specs(spec),
    )(state, packed)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), donate_argnums=5)
def _key_sharded_gather_and_reset(
    spec: sa.WindowKernelSpec, mesh: Mesh, n: int, g_bucket: int, lean: bool,
    state, first_slot,
):
    """:func:`segment_agg.gather_and_reset_body` on every device's own key
    block: the ``g_bucket`` prefix of each block, side by side."""
    return shard_map(
        lambda state_l, slot: sa.gather_and_reset_body(
            spec, n, g_bucket, state_l, slot, lean
        ),
        mesh=mesh,
        in_specs=(_ring_specs(spec), P()),
        out_specs=(_ring_specs(spec), P(None, KEY_AXIS)),
    )(state, first_slot)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), donate_argnums=5)
def _key_sharded_finals_and_reset(
    spec: sa.WindowKernelSpec, mesh: Mesh, agg_specs: tuple, n: int,
    g_bucket: int, state, first_slot,
):
    """:func:`segment_agg.finals_and_reset_body` on every device's own key
    block: finals and active bits of the ``g_bucket`` prefix of each block,
    side by side (``segment_agg.unpack_active(bits, blocks=n_devices)``)."""
    return shard_map(
        lambda state_l, slot: sa.finals_and_reset_body(
            spec, agg_specs, n, g_bucket, state_l, slot
        ),
        mesh=mesh,
        in_specs=(_ring_specs(spec), P()),
        out_specs=(_ring_specs(spec), P(None, KEY_AXIS)),
    )(state, first_slot)


class KeyShardedPartialMergeWindowState(_HostPartialMixin, KeyShardedWindowState):
    """partial_merge over a device mesh: the host stripe covers the GLOBAL
    group space and packs each unit split by key block, a device merging
    its own block's share.  Emission runs the single device's gather and
    finals programs on every device's block under ``shard_map``: the ring,
    the finals and the active bits stay split over the key axis from the
    first program to the copy back to the host."""

    strategy_name = "partial_merge/key_sharded"

    def __init__(self, spec: sa.WindowKernelSpec, mesh: Mesh):
        super().__init__(spec, mesh)
        self._packed_sharding = NamedSharding(mesh, P(KEY_AXIS))
        # stripe spans the GLOBAL group space
        self._init_host_partial(self.group_capacity)

    def _merge(
        self, packed: np.ndarray, a_pad: int, lean: bool = False,
        dense: bool = False,
    ) -> None:
        self._state = _key_sharded_merge_partials(
            self.spec, self.mesh, self._stripe.SUB, a_pad, lean, dense,
            self._state, jax.device_put(packed, self._packed_sharding),
        )

    def _gather(self, n: int, g_bucket: int, first_slot, lean: bool) -> dict:
        self._state, out = _key_sharded_gather_and_reset(
            self.spec, self.mesh, n, g_bucket, lean, self._state, first_slot
        )
        return out

    def _finals(self, n: int, g_bucket: int, first_slot) -> dict:
        self._state, out = _key_sharded_finals_and_reset(
            self.spec, self.mesh, self._finals_specs, n, g_bucket,
            self._state, first_slot,
        )
        return out

    # the async block emission and its prewarm are the single device's,
    # over the two programs above
    read_reset_block_start = SingleDeviceWindowState.read_reset_block_start
    read_reset_block_finish = SingleDeviceWindowState.read_reset_block_finish
    count_block_d2h = SingleDeviceWindowState.count_block_d2h
    _live_bucket = SingleDeviceWindowState._live_bucket
    prepare_gather = SingleDeviceWindowState.prepare_gather
    prepare_finals = SingleDeviceWindowState.prepare_finals
    read_reset_block_finals_start = (
        SingleDeviceWindowState.read_reset_block_finals_start
    )
    export_start = SingleDeviceWindowState.export_start
    export_finish = SingleDeviceWindowState.export_finish


# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _partial_update(
    spec: sa.WindowKernelSpec,
    mesh: Mesh,
    state,
    values,
    colvalid,
    win_rel,
    rem,
    gid,
    row_valid,
    base_mod,
):
    def body(state_l, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        st = {k: v[0] for k, v in state_l.items()}
        st = sa.update_state_impl(
            spec, st, values, colvalid, win_rel, rem, gid, row_valid, base_mod
        )
        return {k: v[None] for k, v in st.items()}

    n = mesh.devices.size
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            {c.label: P(KEY_AXIS) for c in spec.components},
            P(KEY_AXIS),
            P(KEY_AXIS),
            P(KEY_AXIS),
            P(KEY_AXIS),
            P(KEY_AXIS),
            P(KEY_AXIS),
            P(),
        ),
        out_specs={c.label: P(KEY_AXIS) for c in spec.components},
    )(state, values, colvalid, win_rel, rem, gid, row_valid, base_mod)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _merge_slot_over(
    spec: sa.WindowKernelSpec, mesh: Mesh, reduce_axis: str, state, slot
):
    """Final merge of one window row across device partials: psum for
    count/sum, pmin/pmax for extrema — the reference's Final stage
    (streaming_window.rs:484-489) as a single collective over
    ``reduce_axis``.  Serves both partial layouts (the per-kind fold must
    exist ONCE): partial_final reduces over the 1-D key axis; two_level
    reduces over the slice axis of the 2-D mesh, and its key axis
    assembles via the out-spec with no collective.  ``slot`` is traced
    (dynamic slice), so one compilation serves every ring slot."""
    two_d = reduce_axis == SLICE_AXIS
    state_spec = P(SLICE_AXIS, None, KEY_AXIS) if two_d else P(KEY_AXIS)
    out_spec = P(KEY_AXIS) if two_d else P()

    def body(state_l, slot):
        out = {}
        for c in spec.components:
            row = jax.lax.dynamic_index_in_dim(
                state_l[c.label][0], slot, axis=0, keepdims=False
            )
            if c.kind in ("count", "sum", "sumc"):
                out[c.label] = jax.lax.psum(row, reduce_axis)
            elif c.kind == "min":
                out[c.label] = jax.lax.pmin(row, reduce_axis)
            else:
                out[c.label] = jax.lax.pmax(row, reduce_axis)
        return out

    return shard_map(
        body,
        mesh=mesh,
        in_specs=({c.label: state_spec for c in spec.components}, P()),
        out_specs={c.label: out_spec for c in spec.components},
    )(state, slot)


def _fold_partials_host(
    spec: sa.WindowKernelSpec, host: dict, axis: int = 0
) -> dict:
    """Host-side fold of per-device partial planes along ``axis`` (the
    export path's counterpart of _merge_slot_over)."""
    out = {}
    for c in spec.components:
        b = host[c.label]
        if c.kind in ("count", "sum", "sumc"):
            out[c.label] = b.sum(axis=axis)
        elif c.kind == "min":
            out[c.label] = b.min(axis=axis)
        else:
            out[c.label] = b.max(axis=axis)
    return out


def _import_merged_into_lead(
    spec: sa.WindowKernelSpec,
    host_state: dict,
    n_lead: int,
    W: int,
    G_total: int,
    sharding,
) -> dict:
    """Load a merged (W, G) snapshot into partial 0 of an (n, W, G)
    layout, init elsewhere — restore-time equivalence: the per-kind merge
    reproduces the snapshot exactly."""
    out = {}
    for c in spec.components:
        init = np.asarray(jax.device_get(spec.init_value(c)))
        buf = np.full((n_lead, W, G_total), init, dtype=init.dtype)
        src = host_state.get(c.label)
        if src is not None:
            w = min(src.shape[0], W)
            g = min(src.shape[1], G_total)
            buf[0, :w, :g] = src[:w, :g]
        out[c.label] = jax.device_put(jnp.asarray(buf), sharding)
    return out


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _partial_reset_slot(spec: sa.WindowKernelSpec, state, slot):
    for c in spec.components:
        buf = state[c.label]
        row = jnp.full((buf.shape[0], 1, buf.shape[2]), spec.init_value(c))
        state[c.label] = jax.lax.dynamic_update_slice(
            buf, row.astype(buf.dtype), (0, slot, 0)
        )
    return state


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _key_sharded_reset_slot(
    spec: sa.WindowKernelSpec, mesh: Mesh, state, slot
):
    """Every device re-initializes the slot in its own block (``spec`` is
    the device-local one)."""
    def body(state_l, slot):
        for c in spec.components:
            buf = state_l[c.label]
            row = jnp.full((buf.shape[1],), spec.init_value(c))
            state_l[c.label] = buf.at[slot].set(row.astype(buf.dtype))
        return state_l

    return shard_map(
        body, mesh=mesh, in_specs=(_ring_specs(spec), P()),
        out_specs=_ring_specs(spec),
    )(state, slot)


class PartialFinalWindowState(WindowStateBackend):
    """Rows data-parallel across devices; full state replica per device;
    collective merge only at emission."""

    strategy_name = "partial_final"

    def __init__(self, spec: sa.WindowKernelSpec, mesh: Mesh):
        self.mesh = mesh
        self.n = mesh.devices.size
        self.spec = spec
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        self._state = {
            c.label: jax.device_put(
                jnp.full(
                    (self.n, spec.window_slots, spec.group_capacity),
                    spec.init_value(c),
                ),
                self._sharding,
            )
            for c in spec.components
        }

    @property
    def group_capacity(self) -> int:
        return self.spec.group_capacity

    def update(self, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        # rows must split evenly over the mesh: bucketed batches are powers
        # of two >= mesh size, so this holds by construction
        self._count_rows_h2d(values, colvalid, win_rel, rem, gid, row_valid)
        self._state = _partial_update(
            self.spec,
            self.mesh,
            self._state,
            jnp.asarray(values),
            jnp.asarray(colvalid),
            jnp.asarray(win_rel),
            jnp.asarray(rem),
            jnp.asarray(gid),
            jnp.asarray(row_valid),
            jnp.asarray(base_mod, dtype=jnp.int32),
        )

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        out = jax.device_get(
            _merge_slot_over(
                self.spec, self.mesh, KEY_AXIS, self._state,
                jnp.asarray(slot, jnp.int32),
            )
        )
        self.bytes_d2h += sum(int(a.nbytes) for a in out.values())
        return out

    def reset_slot(self, slot: int) -> None:
        self._state = _partial_reset_slot(
            self.spec, self._state, jnp.asarray(slot, dtype=jnp.int32)
        )

    def export(self) -> dict[str, np.ndarray]:
        """Merged (W, G) snapshot."""
        return _fold_partials_host(self.spec, jax.device_get(self._state))

    def import_(self, host_state: dict[str, np.ndarray]) -> None:
        # load merged snapshot into device 0's partial, init elsewhere
        self._state = _import_merged_into_lead(
            self.spec, host_state, self.n, self.spec.window_slots,
            self.spec.group_capacity, self._sharding,
        )


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _two_level_update(
    spec: sa.WindowKernelSpec,  # LOCAL spec (G_local per key shard)
    mesh: Mesh,
    state,
    values,
    colvalid,
    win_rel,
    rem,
    gid,
    row_valid,
    base_mod,
):
    """2-D update: rows split across the slice axis (each slice applies
    only its shard of the batch — in a multi-host job each host feeds its
    own slice), group blocks split across the key axis (each device masks
    to its gid block, exactly like the 1-D key-sharded layout).  NO
    collective: the key exchange rides the within-slice input broadcast
    and slices don't talk until emission."""

    def body(state_l, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        local_gid, mine = _mask_to_key_shard(spec, gid, row_valid)
        st = {k: v[0] for k, v in state_l.items()}
        st = sa.update_state_impl(
            spec, st, values, colvalid, win_rel, rem, local_gid, mine, base_mod
        )
        return {k: v[None] for k, v in st.items()}

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            {c.label: P(SLICE_AXIS, None, KEY_AXIS) for c in spec.components},
            P(SLICE_AXIS),
            P(SLICE_AXIS),
            P(SLICE_AXIS),
            P(SLICE_AXIS),
            P(SLICE_AXIS),
            P(SLICE_AXIS),
            P(),
        ),
        out_specs={
            c.label: P(SLICE_AXIS, None, KEY_AXIS) for c in spec.components
        },
    )(state, values, colvalid, win_rel, rem, gid, row_valid, base_mod)


class TwoLevelWindowState(WindowStateBackend):
    """2-D ``(slices, keys)`` layout composing the two 1-D strategies:
    rows data-parallel across slices (the Partial/Final axis — cross-
    slice collectives fire only at emission, so this axis tolerates DCN
    in a multi-slice job), state key-sharded within each slice (the
    hash-partition axis — per-batch traffic stays on ICI).  The dp x tp
    analog for streaming window state."""

    strategy_name = "two_level"

    def __init__(self, spec: sa.WindowKernelSpec, mesh: Mesh):
        if SLICE_AXIS not in mesh.axis_names or KEY_AXIS not in mesh.axis_names:
            raise ValueError(
                f"two_level needs a ({SLICE_AXIS}, {KEY_AXIS}) mesh; got "
                f"{mesh.axis_names}"
            )
        self.mesh = mesh
        self.n_slices = mesh.shape[SLICE_AXIS]
        self.n_keys = mesh.shape[KEY_AXIS]
        if spec.group_capacity % self.n_keys:
            raise ValueError(
                f"group capacity {spec.group_capacity} not divisible by "
                f"{self.n_keys} key shards"
            )
        self.spec = sa.WindowKernelSpec(
            components=spec.components,
            num_value_cols=spec.num_value_cols,
            window_slots=spec.window_slots,
            group_capacity=spec.group_capacity // self.n_keys,
            length_ms=spec.length_ms,
            slide_ms=spec.slide_ms,
            accum_dtype=spec.accum_dtype,
            compensated=spec.compensated,
        )
        self._sharding = NamedSharding(mesh, P(SLICE_AXIS, None, KEY_AXIS))
        self._state = {
            c.label: jax.device_put(
                jnp.full(
                    (self.n_slices, spec.window_slots, spec.group_capacity),
                    self.spec.init_value(c),
                ),
                self._sharding,
            )
            for c in spec.components
        }

    @property
    def group_capacity(self) -> int:
        return self.spec.group_capacity * self.n_keys

    def update(self, values, colvalid, win_rel, rem, gid, row_valid, base_mod):
        # rows split S ways (bucketed pow2 batches >= mesh rows by
        # construction, same invariant as PartialFinalWindowState)
        self._count_rows_h2d(values, colvalid, win_rel, rem, gid, row_valid)
        self._state = _two_level_update(
            self.spec,
            self.mesh,
            self._state,
            jnp.asarray(values),
            jnp.asarray(colvalid),
            jnp.asarray(win_rel),
            jnp.asarray(rem),
            jnp.asarray(gid),
            jnp.asarray(row_valid),
            jnp.asarray(base_mod, dtype=jnp.int32),
        )

    def read_slot(self, slot: int) -> dict[str, np.ndarray]:
        # cross-slice merge (the layout's only collective) + key-axis
        # assembly via the out-spec — see _merge_slot_over
        out = jax.device_get(
            _merge_slot_over(
                self.spec, self.mesh, SLICE_AXIS, self._state,
                jnp.asarray(slot, jnp.int32),
            )
        )
        self.bytes_d2h += sum(int(a.nbytes) for a in out.values())
        return out

    def reset_slot(self, slot: int) -> None:
        # global-shape program; GSPMD partitions it over self._sharding
        self._state = _partial_reset_slot(
            self.spec, self._state, jnp.asarray(slot, dtype=jnp.int32)
        )

    def export(self) -> dict[str, np.ndarray]:
        """Merged (W, G_total) snapshot (cross-slice fold on host)."""
        return _fold_partials_host(self.spec, jax.device_get(self._state))

    def import_(self, host_state: dict[str, np.ndarray]) -> None:
        # merged snapshot into slice 0, init elsewhere (restore-time
        # equivalence: sums re-merge identically across slices)
        self._state = _import_merged_into_lead(
            self.spec, host_state, self.n_slices, self.spec.window_slots,
            self.group_capacity, self._sharding,
        )


def make_sharded_state(
    spec: sa.WindowKernelSpec,
    mesh: Mesh | None,
    strategy: str = "auto",
    device_strategy: str = "scatter",
) -> WindowStateBackend:
    """Pick a backend from the mesh, the shard ``strategy`` and the
    ``device_strategy``.

    ``device_strategy='auto'`` is one rule on one device and on a 1-D mesh:
    host edge-reduction (``partial_merge``) on every TPU and CPU backend,
    f64 accumulators on the CPU excepted — partials are orders of magnitude
    smaller than rows, so the host↔device traffic follows cardinality and
    not the row rate.  On one device that is
    :class:`PartialMergeWindowState`; on a 1-D mesh, with the shard
    strategy left at ``auto`` too, :class:`KeyShardedPartialMergeWindowState`
    (every device folds its own key block's share of the stripe).  Measured
    on four v5e chips at 40M groups against the row-shipping ``key_sharded``
    layout that ``auto`` used to pick there (PERF.md section 6, PR 31).  A
    mesh that spans processes keeps row shipping: a process's stripe cannot
    reach another process's devices.

    Row shipping stays available by name: ``device_strategy='scatter'``, or
    a shard strategy named on a mesh — ``key_sharded`` (shard the state,
    broadcast the rows; ``auto``'s pick above 4096 groups where the rule
    above does not apply), ``partial_final`` (duplicate the small state,
    shard the rows), ``two_level`` on a 2-D mesh."""
    if device_strategy not in ("scatter", "auto", "partial_merge"):
        raise ValueError(
            f"unknown device strategy {device_strategy!r} (expected "
            "'scatter', 'partial_merge', or 'auto')"
        )
    # first point that touches the device, and the prewarm ladders below
    # are the bulk of what a stream compiles
    from denormalized_tpu.api.context import enable_compilation_cache

    enable_compilation_cache()
    # 'auto' chooses host edge-reduction on EVERY TPU and CPU backend.  For
    # one TPU the rule was chosen on an earlier installation (ROADMAP S2);
    # on four it was measured (PR 31); for CPU JAX it rests on host runs in
    # which the native single-pass reducer (native/partial_agg.cpp) beat
    # shipping rows through XLA's scatter adds.  Row shipping stays the
    # 'auto' pick on backends neither covers (e.g. a GPU).
    # ... except f64 accumulators on CPU: the partial_merge stripe
    # transports f64 as an f32 hi/lo split and refuses finite sums beyond
    # f32 range (ops/host_partial.py), while CPU XLA scatter keeps f64
    # end-to-end — don't let 'auto' turn a working f64 workload into a
    # runtime OverflowError.
    backend = jax.default_backend()
    f64_on_cpu = spec.accum_dtype == jnp.float64 and backend == "cpu"
    auto_partial = (
        device_strategy == "auto"
        and backend in ("tpu", "cpu")
        and not f64_on_cpu
    )
    if mesh is None or mesh.devices.size == 1:
        if device_strategy == "partial_merge" or auto_partial:
            return PartialMergeWindowState(spec)
        return SingleDeviceWindowState(spec)
    if SLICE_AXIS in mesh.axis_names:
        # 2-D (slices, keys) mesh: the two_level layout is the only one
        # shaped for it
        if strategy not in ("auto", "two_level"):
            raise ValueError(
                f"strategy {strategy!r} does not fit a 2-D "
                f"({SLICE_AXIS}, {KEY_AXIS}) mesh — use 'two_level'/'auto'"
            )
        if device_strategy == "partial_merge":
            raise ValueError(
                "partial_merge composes with the 1-D key-sharded mesh "
                "(host partials already ARE the slice axis); use "
                "mesh_devices without mesh_slices"
            )
        return TwoLevelWindowState(spec, mesh)
    if strategy == "two_level":
        raise ValueError(
            "two_level needs a 2-D mesh — set EngineConfig.mesh_slices"
        )
    if device_strategy == "partial_merge" or (
        auto_partial and strategy == "auto" and not mesh.is_multi_process
    ):
        # host partials imply the Partial/Final split already happened on
        # the host, so the mesh's job is holding the (large) group space:
        # the key-sharded layout is the only one that makes sense here
        return KeyShardedPartialMergeWindowState(spec, mesh)
    if strategy == "auto":
        strategy = (
            "partial_final" if spec.group_capacity <= 4096 else "key_sharded"
        )
    if strategy == "partial_final":
        return PartialFinalWindowState(spec, mesh)
    if strategy == "key_sharded":
        return KeyShardedWindowState(spec, mesh)
    raise ValueError(f"unknown shard strategy {strategy!r}")
