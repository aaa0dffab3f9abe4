"""Device-resident windowed segment aggregation — THE hot path.

TPU re-design of the reference's ``GroupedWindowAggStream`` /
``GroupedAggWindowFrame`` (grouped_window_agg_stream.rs:501-605): where the
reference keeps one ``GroupValues`` table + boxed ``GroupsAccumulator`` per
open window frame and pushes 32-row batches through them on CPU, we keep ONE
set of ``(num_window_slots, group_capacity)`` accumulator buffers resident in
TPU HBM for *all* open windows and update them with a single ``jax.jit``
step per (large) batch:

- window slots form a ring over the window index (slide index), so sliding
  windows fan out on-device without duplicating row data (the reference
  re-filters the batch once per overlapping frame, streaming_window.rs
  :1063-1075 + :548-605 — O(frames x batch) CPU work);
- group keys arrive as dense int32 ids from the host interner
  (:mod:`denormalized_tpu.ops.interner`);
- nulls are neutralized on-device per aggregate kind (0 for sum, ±inf for
  min/max) so XLA fuses mask+scatter into one pass over the batch;
- all state buffers are donated, so the update is allocation-free at
  steady state;
- late rows (window < first_open) and padding rows are dropped by scatter
  ``mode='drop'`` — the device-side mirror of the reference's late-data drop
  (streaming_window.rs:982-991).

Shapes are static: batches are bucketed to powers of two and state is grown
by re-compilation when group cardinality or window skew exceeds capacity.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class AggComponent:
    """One primitive accumulator buffer.  Composite aggregates decompose:
    avg = sum + count (exactly as DataFusion's AvgGroupsAccumulator does).
    Kind 'sumc' is the compensation (low-order) buffer paired with a 'sum'
    of the same column when the spec runs compensated summation."""

    kind: str  # 'count' | 'sum' | 'min' | 'max' | 'sumc'
    col: int | None  # value-column index; None = row count (count(*))

    @property
    def label(self) -> str:
        return f"{self.kind}_{'star' if self.col is None else self.col}"


# presence counter: always first so emission knows which groups are active
ROW_COUNT = AggComponent("count", None)


from denormalized_tpu.logical.expr import VAR_KINDS  # noqa: E402


def variance_result(
    kind: str, c: np.ndarray, s: np.ndarray, s2: np.ndarray
) -> np.ndarray:
    """Shared variance finalize: ``s``/``s2`` are Σ(x−K) and Σ(x−K)² for any
    constant shift K (callers pick K near the data's magnitude so the
    ``s2 − s²/c`` subtraction doesn't catastrophically cancel — with K=0 and
    epoch-scale values the two terms agree to ~24 digits and f32/f64 both
    return garbage).  The shift cancels exactly in the algebra."""
    c = np.asarray(c, np.float64)
    s = np.asarray(s, np.float64)
    s2 = np.asarray(s2, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        m2 = np.maximum(s2 - s * s / np.maximum(c, 1), 0.0)
    return variance_from_m2(kind, c, m2)


def variance_from_m2(kind: str, c, m2):
    """Variance finalize from Welford/Chan moments (count, M2) — the host
    accumulators' representation."""
    c = np.asarray(c, np.float64)
    m2 = np.asarray(m2, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        if kind.endswith("_pop"):
            v = np.where(c > 0, m2 / np.maximum(c, 1), np.nan)
        else:
            v = np.where(c > 1, m2 / np.maximum(c - 1, 1), np.nan)
    return np.sqrt(v) if kind.startswith("stddev") else v


def chan_merge(n1, mean1, m21, n2, mean2, m22):
    """Chan et al. parallel combine of (count, mean, M2) moment pairs —
    numerically stable for any magnitude, exact merge algebra."""
    n = n1 + n2
    if n == 0:
        return 0.0, 0.0, 0.0
    delta = mean2 - mean1
    mean = mean1 + delta * n2 / n
    m2 = m21 + m22 + delta * delta * n1 * n2 / n
    return n, mean, m2


def components_for(aggs: list[tuple]) -> list[AggComponent]:
    """Decompose aggregate specs into deduped primitive components.

    Spec entries are ``(kind, value_col)`` — or, for the variance family,
    ``(kind, shifted_col, shifted_sq_col)``: the caller registers two
    DEDICATED value columns holding (x−K) and (x−K)² for a pivot K it picks
    from the first data it sees (see ``variance_result``).  ``avg`` → sum +
    count; variance → sum + count + sum of squares over the shifted
    columns (the running-moments decomposition DataFusion's
    VarianceGroupsAccumulator keeps, made cancellation-safe)."""
    comps: list[AggComponent] = [ROW_COUNT]
    for spec in aggs:
        kind, col = spec[0], spec[1]
        if kind == "count":
            wanted = [AggComponent("count", col)]
        elif kind == "avg":
            wanted = [AggComponent("sum", col), AggComponent("count", col)]
        elif kind in VAR_KINDS:
            sq = spec[2]
            wanted = [
                AggComponent("sum", col),
                AggComponent("count", col),
                AggComponent("sum", sq),
            ]
        elif kind in ("sum", "min", "max"):
            wanted = [AggComponent(kind, col)]
        elif kind == "sketch":
            # sketch aggregates carry their own slice-store planes
            # (ops/sketches.py SketchSpec) — no scalar components
            wanted = []
        else:
            raise ValueError(f"unknown aggregate kind {kind!r}")
        for c in wanted:
            if c not in comps:
                comps.append(c)
    return comps


def with_compensation(comps: list[AggComponent]) -> list[AggComponent]:
    """Add a low-order ('sumc') companion for every 'sum' component —
    storage for Kahan-style compensated accumulation (see
    ``update_state_impl``)."""
    out = list(comps)
    for c in comps:
        if c.kind == "sum":
            out.append(AggComponent("sumc", c.col))
    return out


def read_sum(rows: dict[str, np.ndarray], col: int) -> np.ndarray:
    """A column's total from an emitted row set: hi + lo when compensated
    (lo absent → plain)."""
    hi = rows[AggComponent("sum", col).label].astype(np.float64)
    lo = rows.get(AggComponent("sumc", col).label)
    return hi if lo is None else hi + lo.astype(np.float64)


@dataclass(frozen=True)
class WindowKernelSpec:
    """Static configuration of one compiled window-aggregation kernel.

    Window indexing: windows are identified by their *slide index* ``j``,
    covering ``[j*slide_ms, j*slide_ms + length_ms)`` in epoch milliseconds
    (tumbling ⇒ slide == length, epoch-aligned snapping like the reference's
    ``snap_to_window_start``, streaming_window.rs:1088).  The host rebases
    indices to ``win_rel = j - first_open`` so the device works in small
    int32s; ring slots use the *absolute* index mod W via ``base_mod``."""

    components: tuple[AggComponent, ...]
    num_value_cols: int
    window_slots: int  # W — ring size over open window indices
    group_capacity: int  # G — padded group-id capacity (multiple of 128)
    length_ms: int
    slide_ms: int
    accum_dtype: Any = jnp.float32
    # compensated (Kahan-style) summation: each batch's contribution is
    # scattered into a fresh per-batch partial, then folded into the
    # running (hi, lo) pair with an exact TwoSum — cross-batch rounding
    # vanishes, leaving only intra-batch scatter rounding.  Error bound for
    # a group receiving n values per batch over B batches (f32):
    # |err|/|sum| ≲ sqrt(n)·2^-24 per batch partial, combining across
    # batches as a random walk of batch-sized contributions — ~1e-6
    # relative at 1M values/group vs ~1e-4 for plain f32 accumulation.
    compensated: bool = False

    @property
    def length_units(self) -> int:
        """k = number of windows each row fans out to."""
        return -(-self.length_ms // self.slide_ms)

    def init_value(self, comp: AggComponent):
        if comp.kind == "count":
            return jnp.zeros((), jnp.int32)
        if comp.kind in ("sum", "sumc"):
            return jnp.zeros((), self.accum_dtype)
        if comp.kind == "min":
            return jnp.array(jnp.inf, self.accum_dtype)
        if comp.kind == "max":
            return jnp.array(-jnp.inf, self.accum_dtype)
        raise ValueError(comp.kind)


def init_state(spec: WindowKernelSpec) -> dict[str, jax.Array]:
    """Allocate the HBM-resident accumulator buffers: one (W, G) array per
    primitive component."""
    shape = (spec.window_slots, spec.group_capacity)
    return {
        c.label: jnp.full(shape, spec.init_value(c))
        for c in spec.components
    }


def _apply_component(
    spec: WindowKernelSpec,
    comp: AggComponent,
    buf: jax.Array,
    slot: jax.Array,  # (B,) int32, out-of-range => dropped
    gid: jax.Array,  # (B,) int32
    values: jax.Array,  # (B, V) accum_dtype
    colvalid: jax.Array,  # (B, V) bool
) -> jax.Array:
    at = buf.at[slot, gid]
    if comp.kind == "count":
        if comp.col is None:
            inc = jnp.ones(slot.shape, jnp.int32)
        else:
            inc = colvalid[:, comp.col].astype(jnp.int32)
        return at.add(inc, mode="drop")
    v = values[:, comp.col]
    ok = colvalid[:, comp.col]
    if comp.kind == "sum":
        return at.add(jnp.where(ok, v, 0), mode="drop")
    if comp.kind == "min":
        return at.min(jnp.where(ok, v, jnp.inf), mode="drop")
    if comp.kind == "max":
        return at.max(jnp.where(ok, v, -jnp.inf), mode="drop")
    raise ValueError(comp.kind)


# the ``dnz.*`` scopes below name the four device programs of the window
# operator in the profiler's device trace (op_name metadata only: results,
# shapes and the compiled code do not depend on them).  Each sits on the
# body every layout shares, so the sharded variants carry the same name.
@jax.named_scope("dnz.update_state")
def update_state_impl(
    spec: WindowKernelSpec,
    state: dict[str, jax.Array],
    values: jax.Array,  # (B, V)
    colvalid: jax.Array,  # (B, V) bool
    win_rel: jax.Array,  # (B,) int32: slide-index of row minus first_open
    rem_ms: jax.Array,  # (B,) int32: ts - slide_index*slide (in [0, S))
    gid: jax.Array,  # (B,) int32 dense group ids from the host interner
    row_valid: jax.Array,  # (B,) bool (padding rows false)
    base_mod: jax.Array,  # () int32: first_open % W (ring phase)
) -> dict[str, jax.Array]:
    """One device step: scatter the batch into every window frame it belongs
    to.  A row with slide-index ``t`` belongs to windows ``t-k+1 .. t``
    (k = length_units); the fan-out is a static unrolled loop of k scatters —
    XLA fuses the mask/neutralize work, and row data crosses host→HBM once
    regardless of k (tumbling: k=1).  The reference instead re-filters the
    batch once per overlapping frame on CPU (streaming_window.rs:1063-1075)."""
    W = spec.window_slots
    values = values.astype(spec.accum_dtype)
    # compensated mode: scatter 'sum' components into fresh per-batch
    # partials, folded into (hi, lo) once at the end via exact TwoSum
    partials = {}
    if spec.compensated:
        for comp in spec.components:
            if comp.kind == "sum":
                partials[comp.label] = jnp.zeros_like(state[comp.label])
    for i in range(spec.length_units):
        wr = win_rel - i  # rebased index of the i-th window this row feeds
        # membership: window covers the row iff i*S + rem < L (exactly k
        # windows when L % S == 0); late rows (wr < 0 — window already
        # emitted; the reference logs-and-drops at streaming_window.rs:982)
        # and skew overflow (wr >= W, guarded host-side) are masked out.
        ok = row_valid & (wr >= 0) & (wr < W)
        if spec.length_ms - i * spec.slide_ms < spec.slide_ms:
            ok = ok & (rem_ms < spec.length_ms - i * spec.slide_ms)
        # ring slot of the *absolute* window index; invalid rows pushed out of
        # range so mode='drop' skips them
        slot = jnp.where(ok, (wr + base_mod) % W, W).astype(jnp.int32)
        for comp in spec.components:
            if comp.kind == "sumc":
                continue  # written only by the TwoSum fold below
            if comp.kind == "sum" and spec.compensated:
                partials[comp.label] = _apply_component(
                    spec, comp, partials[comp.label], slot, gid, values,
                    colvalid,
                )
                continue
            state[comp.label] = _apply_component(
                spec, comp, state[comp.label], slot, gid, values, colvalid
            )
    if spec.compensated:
        for comp in spec.components:
            if comp.kind != "sum":
                continue
            hi = state[comp.label]
            lo = state[AggComponent("sumc", comp.col).label]
            p = partials[comp.label]
            # Knuth TwoSum: s + e == hi + p exactly
            s = hi + p
            t = s - hi
            e = (hi - (s - t)) + (p - t)
            state[comp.label] = s
            state[AggComponent("sumc", comp.col).label] = lo + e
    return state


# jitted single-device entry; the sharded variants wrap update_state_impl in
# shard_map (see denormalized_tpu.parallel.sharded_state)
update_state = functools.partial(jax.jit, static_argnums=0, donate_argnums=1)(
    update_state_impl
)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), donate_argnums=5)
def merge_partials(
    spec: WindowKernelSpec,
    SUB: int,
    a_pad: int,
    lean: bool,
    dense: bool,
    state: dict[str, jax.Array],
    packed: jax.Array,  # int32, (P+1, a_pad+2) compact / (P, a_pad+2) dense
) -> dict[str, jax.Array]:
    """Fold one slide unit's host-side partial aggregates (or, with a
    leading axis, a stack of units of one layout) into the window ring — the device half of the ``partial_merge`` strategy (host
    edge-reduction + accelerator merge; see ops/host_partial.py).

    ``packed`` is an **int32 carrier** (immune to x64-off canonicalization):
    row 0 holds the unit's active cell indices ``s*G + g``, ascending (−1 =
    padding), plus ``u_rel`` (the unit relative to first_open) and
    ``base_mod`` (first_open % W) in its tail slots; value planes are f32
    bitcasts — sums arrive as (hi, lo) so the host's f64 accumulation
    survives transit.  The k-way sliding fan-out happens HERE: the unit's
    partial feeds windows u-k+1..u, with sub-bucket 1 (rows past the
    L-(k-1)S edge) excluded from the oldest window.  Compensated mode
    routes lo into the 'sumc' buffer — one rounding per merge per cell
    instead of one per row.

    ``dense`` selects the index-free layout (host_partial.take_packed):
    ``a_pad == SUB*G``, cell i IS index i of the unit, the index plane is
    omitted (plane p sits at row p, header ints still in row 0's tail
    slots) and cells without rows carry fold-neutral values."""
    return merge_partials_body(spec, SUB, a_pad, state, packed, lean, dense)


def lean_skippable(c: AggComponent) -> bool:
    """Whether ``c``'s plane is omitted from the LEAN packed/gather layouts
    and aliased to plane 1 (row count).  Single source of truth: the host
    packing (host_partial.take_packed), the device merge unpack, the
    emission gather, and the prewarm plane count must all agree on this
    predicate or plane indices silently shift."""
    return c.kind == "count" and c.col is not None


def lean_possible(spec: WindowKernelSpec) -> bool:
    """Whether the lean layout differs from the full one for this spec."""
    return any(lean_skippable(c) for c in spec.components)


@jax.named_scope("dnz.merge_partials")
def merge_partials_body(
    spec: WindowKernelSpec,
    SUB: int,
    a_pad: int,
    state: dict[str, jax.Array],
    packed: jax.Array,
    lean: bool = False,
    dense: bool = False,
) -> dict[str, jax.Array]:
    """Shared fold of one slide unit into the ``spec.group_capacity``
    groups ``state`` holds: the whole group space on a single device, one
    key block on a key-sharded mesh — there ``spec`` is the device-local
    one and ``packed`` the block's own share of the unit, its cell ids
    local to the block (host_partial.take_packed splits a unit by key
    block), so a device of a mesh runs the program a single device runs.

    Each window the unit feeds is ONE ring row: the row is sliced out,
    folded and written back, so a merge reads and writes ``k`` rows of
    each component and nothing else of the ring.  (Scattering into the
    two-dimensional buffer made XLA:TPU re-lay the whole ``(W, G)`` plane
    out as one dimension and back around every scatter: 10M groups put
    12.8 GB of copies behind a merge of a thousand cells.)

    ``lean`` selects the null-free packed layout: per-column count planes
    are omitted from ``packed`` and aliased to the row-count plane — a
    null-free stripe's per-column counts equal its row counts
    cell-for-cell (host_partial.take_packed).

    Compact: the cells' (s, g) come from the index row; with ``SUB == 1``
    they are the row's scatter indices as they stand — ascending and
    distinct, which the scatter is told.  Dense: plane ``p`` is ``SUB``
    rows of ``G`` cells and folds elementwise, no scatter."""
    if packed.ndim == 3:
        # a stripe's dense units in one call (the backend stacks them, padded
        # with no-op units to the stripe's span: one transfer and one
        # dispatch for them all).  A loop, not an unrolling: one body
        # whatever the span
        def one(j, st):
            unit = jax.lax.dynamic_index_in_dim(packed, j, 0, keepdims=False)
            return merge_partials_body(
                spec, SUB, a_pad, st, unit, lean, dense
            )

        if packed.shape[0] == 1:
            return one(0, state)
        return jax.lax.fori_loop(0, packed.shape[0], one, state)
    W = spec.window_slots
    k = spec.length_units
    u_rel = packed[0, a_pad]
    base_mod = packed[0, a_pad + 1]
    G = spec.group_capacity
    plane0 = 0 if dense else 1

    def f32_plane(pi):
        return jax.lax.bitcast_convert_type(
            packed[plane0 + pi, :a_pad], jnp.float32
        )

    if not dense:
        idx = packed[0, :a_pad]
        s = idx // G
        g = idx % G
        valid = idx >= 0
        # what the host guarantees
        flags = dict(indices_are_sorted=SUB == 1, unique_indices=SUB == 1)
        # dropped entries scatter out of range, each to a place of its own
        out_of_range = G + jnp.arange(a_pad, dtype=jnp.int32)

    def fold(kind, row, pv, ok_sub):
        """``row`` (G,) with one plane of the unit folded in; ``ok_sub``
        says whether sub-bucket 1 belongs to this window."""
        pv = pv.astype(row.dtype)
        if dense:
            rows = pv.reshape(SUB, G)
            for sub in range(SUB if ok_sub else 1):
                r = rows[sub]
                row = (
                    row + r if kind in ("count", "sum")
                    else jnp.minimum(row, r) if kind == "min"
                    else jnp.maximum(row, r)
                )
            return row
        ok = valid if ok_sub or SUB == 1 else valid & (s == 0)
        at = row.at[jnp.where(ok, g, out_of_range)]
        if kind in ("count", "sum"):
            return at.add(pv, mode="drop", **flags)
        if kind == "min":
            return at.min(pv, mode="drop", **flags)
        return at.max(pv, mode="drop", **flags)

    for i in range(k):
        w_rel = u_rel - i
        # a window outside the ring takes nothing (skew overflow is guarded
        # host-side; late units were dropped there)
        in_ring = (w_rel >= 0) & (w_rel < W)
        slot = (base_mod + w_rel) % W
        ok_sub = not (SUB == 2 and i == k - 1)

        def apply(label, kind, planes):
            buf = state[label]
            row = jax.lax.dynamic_slice(buf, (slot, 0), (1, G)).reshape(G)
            new = row
            for pv in planes:
                new = fold(kind, new, pv, ok_sub)
            state[label] = jax.lax.dynamic_update_slice(
                buf, jnp.where(in_ring, new, row).reshape(1, G), (slot, 0)
            )

        pi = 0
        for comp in spec.components:
            if comp.kind == "sumc":
                continue
            if comp.kind == "sum":
                hi, lo = f32_plane(pi), f32_plane(pi + 1)
                pi += 2
                if spec.compensated:
                    apply(comp.label, "sum", [hi])
                    apply(AggComponent("sumc", comp.col).label, "sum", [lo])
                else:
                    # two adds keep most of the host f64 precision even in
                    # a plain f32 buffer
                    apply(comp.label, "sum", [hi, lo])
                continue
            if lean and lean_skippable(comp):
                pv = f32_plane(0)  # alias the row-count plane
            else:
                pv = f32_plane(pi)
                pi += 1
            apply(comp.label, comp.kind, [pv])
    return state


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 5), donate_argnums=3)
def _gather_and_reset(
    spec: WindowKernelSpec,
    n: int,
    g_bucket: int,
    state: dict[str, jax.Array],
    first_slot,
    lean: bool = False,
):
    """Single-device entry of :func:`gather_and_reset_body`."""
    return gather_and_reset_body(spec, n, g_bucket, state, first_slot, lean)


@jax.named_scope("dnz.gather_and_reset")
def gather_and_reset_body(
    spec: WindowKernelSpec,
    n: int,
    g_bucket: int,
    state: dict[str, jax.Array],
    first_slot,
    lean: bool = False,
):
    """Read ``n`` consecutive ring slots AND reset them in one program —
    one device round-trip per emission cycle instead of two per window.

    ``g_bucket`` is the transferred group width, a prefix of the groups
    ``state`` holds: of the whole group space on a single device, of the
    device's own key block under ``shard_map`` on a key-sharded mesh
    (``spec`` is the device-local one there).  ``lean`` omits per-column
    count planes from the transfer (they equal the row-count plane when
    the stream has never carried a null; the host aliases them back)."""
    state, comp = _read_and_reset_slots(spec, n, g_bucket, state, first_slot)
    out = {
        c.label: comp[c.label]
        for c in spec.components
        if not (lean and lean_skippable(c))
    }
    return state, out


def _read_and_reset_slots(
    spec: WindowKernelSpec, n: int, g_bucket: int, state, first_slot
):
    """Traced slice of ``n`` consecutive ring slots (``:g_bucket`` group
    prefix) of EVERY component, and re-initialization of those slots in
    the (donated) state — the shared read+reset core of both emission
    paths (_gather_and_reset and _finals_and_reset), so the ':g_bucket
    prefix only' reset invariant cannot diverge between them.

    One dynamic slice and one dynamic update per slot and component, not
    a gather and a scatter over ``n`` computed row indices: the ring
    wraps, so the rows are not one slice, but each is — and XLA:TPU
    compiles the slices in well under a second at any width, where the
    gather form took time in proportion to ``n * g_bucket`` (half a
    minute at 200K groups, minutes at 10M)."""
    W = spec.window_slots
    rows = {c.label: [] for c in spec.components}
    for i in range(n):
        slot = (first_slot + i) % W
        for c in spec.components:
            buf = state[c.label]
            rows[c.label].append(
                jax.lax.dynamic_slice(buf, (slot, 0), (1, g_bucket))
            )
            # only the transferred prefix needs resetting: cells beyond
            # the live-group prefix were never written
            init = jnp.full((1, g_bucket), spec.init_value(c)).astype(buf.dtype)
            state[c.label] = jax.lax.dynamic_update_slice(buf, init, (slot, 0))
    comp = {
        label: r[0] if n == 1 else jnp.concatenate(r, axis=0)
        for label, r in rows.items()
    }
    return state, comp


# aggregate kinds whose final value is cheap elementwise math over the
# component planes — eligible for on-device finalization at emission
BASIC_FINAL_KINDS = ("count", "sum", "min", "max", "avg")

# key of the packed active-group bitmask in a finals emission block
ACTIVE_BITS = "__active_bits__"


def pack_active(active: jax.Array) -> jax.Array:
    """(n, g) bool → (n, g // 8) uint8 with bit ``j`` of byte ``k`` =
    ``active[:, j * (g // 8) + k]``: the eight bits of a byte are taken a
    stride of ``g // 8`` apart, so the long axis stays on the lanes
    (``jnp.packbits`` packs eight NEIGHBOURS, a minor dimension of 8, and
    its compile time grew with ``n * g``: two minutes at 10M groups).
    ``g`` is a multiple of 8 (group widths are multiples of 128).
    :func:`unpack_active` is the host's inverse."""
    n, g = active.shape
    a = active.reshape(n, 8, g // 8).astype(jnp.int32)
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    return jnp.sum(a << shifts, axis=1, dtype=jnp.int32).astype(jnp.uint8)


def unpack_active(packed: np.ndarray, blocks: int = 1) -> np.ndarray:
    """Host inverse of :func:`pack_active`: (n, g // 8) uint8 → (n, g)
    bool.  ``blocks``: how many equal runs of the group axis were packed
    each on its own and laid side by side (one a device of a key-sharded
    mesh, whose devices each pack their own key block)."""
    n, w = packed.shape
    bits = np.unpackbits(
        packed.reshape(n * blocks, w // blocks)[:, :, None],
        axis=2, bitorder="little",
    )
    return bits.transpose(0, 2, 1).reshape(n, 8 * w).astype(bool)


def finals_possible(agg_specs: tuple) -> bool:
    """True when every output aggregate can be finalized on device (the
    variance family needs the host's pivot-shifted f64 algebra)."""
    return all(s[0] in BASIC_FINAL_KINDS for s in agg_specs)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=4)
def _finals_and_reset(
    spec: WindowKernelSpec,
    agg_specs: tuple,
    n: int,
    g_bucket: int,
    state: dict[str, jax.Array],
    first_slot,
):
    """Single-device entry of :func:`finals_and_reset_body`."""
    return finals_and_reset_body(
        spec, agg_specs, n, g_bucket, state, first_slot
    )


@jax.named_scope("dnz.finals_and_reset")
def finals_and_reset_body(
    spec: WindowKernelSpec,
    agg_specs: tuple,
    n: int,
    g_bucket: int,
    state: dict[str, jax.Array],
    first_slot,
):
    """Emission with on-device finalization: read ``n`` ring slots, compute
    the FINAL output columns (count/sum/min/max/avg) and an active-group
    bitmask on device, reset the slots, and return only the finals.

    Versus the component gather this ships one ``accum_dtype`` plane per
    OUTPUT aggregate plus ``g_bucket/8`` mask bytes — instead of one plane
    per primitive component (row count, per-column counts, Kahan hi+lo sum
    pairs).  On a narrow host↔device link emission traffic drops by the
    component/output ratio (e.g. 12→8.5 bytes per group for sum+avg,
    12→4.5 for a single avg).  Precision: a compensated sum is emitted as
    fl(hi+lo) — the correctly-rounded ``accum_dtype`` value of the
    maintained sum (≤1 ulp), vs the host's f64 hi+lo add; checkpoints and
    state export still carry full components, so this rounding affects
    emitted values only.  Mirrors ``GroupsAccumulator::evaluate``
    (grouped_window_agg_stream.rs:609-629) run device-side."""
    state, comp = _read_and_reset_slots(spec, n, g_bucket, state, first_slot)
    rc = comp[ROW_COUNT.label]
    out = {ACTIVE_BITS: pack_active(rc > 0)}

    def cnt_of(col):
        lbl = AggComponent("count", col).label
        return comp[lbl] if lbl in comp else rc

    def sum_of(col):
        hi = comp[AggComponent("sum", col).label]
        lo = comp.get(AggComponent("sumc", col).label)
        return hi if lo is None else hi + lo

    nan = jnp.asarray(jnp.nan, spec.accum_dtype)
    for i, s in enumerate(agg_specs):
        kind, col = s[0], s[1]
        if kind == "count":
            f = cnt_of(col)
        elif kind == "sum":
            f = sum_of(col)
        elif kind == "avg":
            c = cnt_of(col)
            f = jnp.where(c > 0, sum_of(col) / jnp.maximum(c, 1), nan)
        elif kind == "min":
            v = comp[AggComponent("min", col).label]
            f = jnp.where(jnp.isposinf(v), nan, v)
        elif kind == "max":
            v = comp[AggComponent("max", col).label]
            f = jnp.where(jnp.isneginf(v), nan, v)
        else:  # pragma: no cover — guarded by finals_possible
            raise ValueError(kind)
        out[f"__final_{i}__"] = f
    return state, out


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def reset_slot(
    spec: WindowKernelSpec, state: dict[str, jax.Array], slot: jax.Array
) -> dict[str, jax.Array]:
    """Re-initialize one ring slot after its window was emitted, freeing it
    for reuse (the reference instead drops the whole frame from its BTreeMap,
    streaming_window.rs:703-730; our buffers are preallocated)."""
    for comp in spec.components:
        buf = state[comp.label]
        state[comp.label] = buf.at[slot].set(
            jnp.full((spec.group_capacity,), spec.init_value(comp))
        )
    return state


@functools.partial(jax.jit, static_argnums=0)
def _gather_slot(spec: WindowKernelSpec, state, slot):
    # slot is TRACED: one compiled program serves every ring slot.  Indexing
    # with a Python int instead would compile a fresh gather per distinct
    # slot (up to W compiles instead of one).
    return {
        c.label: jax.lax.dynamic_index_in_dim(
            state[c.label], slot, axis=0, keepdims=False
        )
        for c in spec.components
    }


def read_slot(
    spec: WindowKernelSpec, state: dict[str, jax.Array], slot: int
) -> dict[str, np.ndarray]:
    """Fetch one window's accumulator rows to host (device→host crossing of
    G-sized vectors only — results, never raw rows)."""
    return jax.device_get(
        _gather_slot(spec, state, jnp.asarray(slot, jnp.int32))
    )


def export_state(state: dict[str, jax.Array]) -> dict[str, np.ndarray]:
    """Full device→host snapshot (checkpointing / capacity growth)."""
    return jax.device_get(state)


@jax.jit
def clone_state(state: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """On-device copy of the window ring — an immutable snapshot source
    that later (donated) update programs cannot touch, so its
    device→host transfer can run asynchronously under ingest (the
    drain-free analog of the reference's state()-then-reseed trick,
    grouped_window_agg_stream.rs:379-394)."""
    return {k: jnp.copy(v) for k, v in state.items()}


def import_state(
    spec: WindowKernelSpec, host_state: dict[str, np.ndarray]
) -> dict[str, jax.Array]:
    """Rebuild device state from a host snapshot, padding up to the spec's
    (possibly larger) capacity — used on restore and on G/W growth."""
    state = init_state(spec)
    out = {}
    for comp in spec.components:
        # np.array copies: device_get may hand back read-only views
        buf = np.array(jax.device_get(state[comp.label]))
        src = host_state.get(comp.label)
        if src is not None:
            w = min(src.shape[0], buf.shape[0])
            g = min(src.shape[1], buf.shape[1])
            buf[:w, :g] = src[:w, :g]
        out[comp.label] = jnp.asarray(buf)
    return out


def finalize(
    agg_specs: list[tuple],
    rows: dict[str, np.ndarray],
    active: np.ndarray,
) -> list[np.ndarray]:
    """Host-side final evaluation of one emitted window from its primitive
    component rows (the mirror of ``Accumulator::evaluate`` /
    ``GroupsAccumulator::evaluate`` at grouped_window_agg_stream.rs:609-629).

    ``active`` is the boolean mask of live group slots in this window."""
    outs: list[np.ndarray] = []
    for spec in agg_specs:
        kind, col = spec[0], spec[1]
        if kind in VAR_KINDS:
            sq = spec[2]
            outs.append(
                variance_result(
                    kind,
                    rows[AggComponent("count", col).label][active],
                    read_sum(rows, col)[active],
                    read_sum(rows, sq)[active],
                )
            )
            continue
        if kind == "count":
            label = AggComponent("count", col).label
            outs.append(rows[label][active].astype(np.int64))
        elif kind == "sum":
            outs.append(read_sum(rows, col)[active])
        elif kind == "avg":
            s = read_sum(rows, col)[active]
            c = rows[AggComponent("count", col).label][active].astype(np.float64)
            with np.errstate(invalid="ignore", divide="ignore"):
                outs.append(np.where(c > 0, s / np.maximum(c, 1), np.nan))
        elif kind == "min":
            v = rows[AggComponent("min", col).label][active].astype(np.float64)
            outs.append(np.where(np.isposinf(v), np.nan, v))
        elif kind == "max":
            v = rows[AggComponent("max", col).label][active].astype(np.float64)
            outs.append(np.where(np.isneginf(v), np.nan, v))
        else:
            raise ValueError(kind)
    return outs
