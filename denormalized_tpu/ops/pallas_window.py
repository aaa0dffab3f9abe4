"""Pallas TPU kernel for the windowed-aggregation hot op (dense small-G
path).

The default device step (`segment_agg.update_state`) scatters rows into
``(W, G)`` HBM buffers — general, but scatter on TPU serializes through
sort-based lowering.  For LOW-cardinality aggregation (the emit_measurements
shape: ≤ ~2k groups), this kernel reformulates the scatter as dense
MXU/VPU work per TILE-row tile (TILE=256):

- count/sum become one-hot matmuls on the MXU
  (``one_hot(gid).T @ masked_values``);
- min/max become masked broadcast-reductions on the VPU;
- the few window slots a batch touches (``k_active``, static) are handled by
  masking rows per relative slot, so the kernel accumulates a
  ``(k_active, G)`` VMEM scratch and the caller adds/merges it into the HBM
  ring at ``[base : base+k_active]`` — one dynamic-slice update instead of a
  row scatter.

Selected via ``EngineConfig(device_strategy="pallas_dense")``; falls back to
the scatter path when G or the batch's window span exceeds the dense limits.
Runs under ``interpret=True`` on CPU so tests validate bit-parity with the
scatter path without TPU hardware.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from denormalized_tpu.ops import segment_agg as sa

# dense-path limits: specs beyond these, or batches spanning more ring slots
# than K_ACTIVE, fall back to the scatter path
MAX_DENSE_GROUPS = 2048
MAX_DENSE_VALUE_COLS = 4
K_ACTIVE = 8
TILE = 256
# widest group tile one kernel instance holds.  The (TILE, G) one-hot and
# its per-slot masked temporaries live in Mosaic's scoped VMEM (16 MiB on
# v5e); at 1024 groups they overflow it for tumbling windows, so the group
# axis is a grid axis and each instance sees at most this many lanes.
GROUP_TILE = 512


def group_tile(G: int) -> int:
    """Lanes per kernel instance: G is a multiple of 128, so this is 128,
    256 or 512 and always divides G."""
    return math.gcd(G, GROUP_TILE)


def _kernel(
    values_ref,  # (TILE, V) f32
    colvalid_ref,  # (TILE, V) f32 (1.0 valid)
    in_slot_ref,  # (TILE, K) f32 — 1.0 where the row feeds slot j
    gid_ref,  # (TILE, 1) int32
    cnt_ref,  # (K, V*GT) f32 out — valid-entry count per (slot, col, group)
    sum_ref,  # (K, V*GT) f32 out
    min_ref,  # (K, V*GT) f32 out
    max_ref,  # (K, V*GT) f32 out
    rowcnt_ref,  # (K, GT) f32 out — rows per (slot, group), for count(*)
    *,
    GT: int,
    V: int,
):
    # grid = (group tiles, row tiles): the row axis is innermost, so each
    # group tile's output block stays resident while every row tile folds
    # into it, and is initialized on that tile's first row step
    g0 = pl.program_id(0) * GT
    step = pl.program_id(1)
    values = values_ref[:]
    colvalid = colvalid_ref[:]
    in_slots = in_slot_ref[:]
    gid = gid_ref[:]

    # one-hot over this tile's groups, (TILE, GT)
    groups = g0 + jax.lax.broadcasted_iota(jnp.int32, (TILE, GT), 1)
    onehot = (gid == groups).astype(jnp.float32)

    @pl.when(step == 0)
    def _init():
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        sum_ref[:] = jnp.zeros_like(sum_ref)
        min_ref[:] = jnp.full_like(min_ref, jnp.inf)
        max_ref[:] = jnp.full_like(max_ref, -jnp.inf)
        rowcnt_ref[:] = jnp.zeros_like(rowcnt_ref)

    for j in range(K_ACTIVE):
        oh = onehot * in_slots[:, j : j + 1]  # rows of this slot only
        rowcnt_ref[j, :] += jnp.sum(oh, axis=0)
        for v in range(V):
            col = values[:, v : v + 1]  # (TILE, 1)
            ok = colvalid[:, v : v + 1]
            sel = (oh * ok) > 0
            lanes = slice(v * GT, (v + 1) * GT)
            # count/sum via where-selection: masked-out lanes may hold NaN
            # (values behind an invalid mask are unspecified), and 0*NaN
            # would poison a multiplicative mask
            cnt_ref[j, lanes] += jnp.sum(oh * ok, axis=0)
            sum_ref[j, lanes] += jnp.sum(jnp.where(sel, col, 0.0), axis=0)
            # min/max via masked broadcast reduce on the VPU
            min_ref[j, lanes] = jnp.minimum(
                min_ref[j, lanes],
                jnp.min(jnp.where(sel, col, jnp.inf), axis=0),
            )
            max_ref[j, lanes] = jnp.maximum(
                max_ref[j, lanes],
                jnp.max(jnp.where(sel, col, -jnp.inf), axis=0),
            )


@functools.partial(
    jax.jit, static_argnames=("G", "V", "KREL", "interpret")
)
def _dense_partials(
    values, colvalid, rel, gid, *, G: int, V: int, KREL: int, interpret: bool
):
    """→ (rowcnt (K,G), cnt (K,V,G), sum (K,V,G), min (K,V,G), max (K,V,G))

    ``rel`` is (B, KREL): each row's target slots (rebased), -1 = dropped.
    One column per window the row fans out to (sliding: KREL =
    length_units); the columns are distinct windows, so folding them into
    a 0/1 (B, K) slot-membership matrix here keeps the kernel itself
    independent of KREL."""
    B = values.shape[0]
    assert B % TILE == 0
    GT = group_tile(G)
    n_gt = G // GT
    slots = jnp.arange(K_ACTIVE, dtype=jnp.int32)
    in_slot = jnp.any(
        rel.reshape(-1, KREL, 1) == slots, axis=1
    ).astype(jnp.float32)  # (B, K)

    def rows(g, i):
        return (i, 0)

    def tile(g, i):
        return (0, g)

    outs = pl.pallas_call(
        functools.partial(_kernel, GT=GT, V=V),
        grid=(n_gt, B // TILE),
        in_specs=[
            pl.BlockSpec((TILE, V), rows),
            pl.BlockSpec((TILE, V), rows),
            pl.BlockSpec((TILE, K_ACTIVE), rows),
            pl.BlockSpec((TILE, 1), rows),
        ],
        out_specs=[pl.BlockSpec((K_ACTIVE, V * GT), tile)] * 4
        + [pl.BlockSpec((K_ACTIVE, GT), tile)],
        out_shape=[jax.ShapeDtypeStruct((K_ACTIVE, V * G), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((K_ACTIVE, G), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="dnz_dense_partials",
    )(
        values.astype(jnp.float32),
        colvalid.astype(jnp.float32),
        in_slot,
        gid.reshape(-1, 1),
    )
    cnt, ssum, smin, smax, rowcnt = outs

    def planes(a):
        # kernel columns are (group tile, value col, lane) — regroup to
        # (K, V, G)
        return (
            a.reshape(K_ACTIVE, n_gt, V, GT)
            .transpose(0, 2, 1, 3)
            .reshape(K_ACTIVE, V, G)
        )

    return rowcnt, planes(cnt), planes(ssum), planes(smin), planes(smax)


def dense_supported(spec: sa.WindowKernelSpec) -> bool:
    """The envelope the kernel is compiled over for v5e by
    tests/test_tpu_aot_compile.py — keep the two in step."""
    return (
        spec.group_capacity <= MAX_DENSE_GROUPS
        and spec.group_capacity % 128 == 0
        and spec.num_value_cols <= MAX_DENSE_VALUE_COLS
        # sliding fan-out rides the (B, k) rel matrix in ONE launch; the
        # batch's slot span must still fit the K_ACTIVE scratch rows (the
        # caller additionally checks the actual span per batch)
        and spec.length_units <= K_ACTIVE
        # the kernel accumulates in f32; honor an explicit f64 request by
        # staying on the scatter path
        and spec.accum_dtype == jnp.float32
        # compensated (hi, lo) sums need the scatter path's TwoSum fold
        and not spec.compensated
    )


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _merge_partials(spec, state, partials, base_mod):
    """Fold the (K, ...) dense partials into the HBM ring with ONE
    dynamic-window update per component (no row scatter)."""
    rowcnt, cnt, ssum, smin, smax = partials
    W = spec.window_slots
    G = spec.group_capacity
    # ring rows base_mod..base_mod+K (mod W): do it as a K-row scatter-free
    # update using modular row indices via take/set on a small index vector
    rows = (base_mod + jnp.arange(K_ACTIVE, dtype=jnp.int32)) % W
    for comp in spec.components:
        buf = state[comp.label]
        if comp.kind == "count":
            upd = (
                rowcnt if comp.col is None else cnt[:, comp.col, :]
            ).astype(buf.dtype)
            state[comp.label] = buf.at[rows].add(upd)
        elif comp.kind == "sum":
            state[comp.label] = buf.at[rows].add(
                ssum[:, comp.col, :].astype(buf.dtype)
            )
        elif comp.kind == "min":
            state[comp.label] = buf.at[rows].min(
                smin[:, comp.col, :].astype(buf.dtype)
            )
        else:
            state[comp.label] = buf.at[rows].max(
                smax[:, comp.col, :].astype(buf.dtype)
            )
    return state


def dense_update(
    spec: sa.WindowKernelSpec,
    state,
    values,
    colvalid,
    win_rel,
    rem,
    gid,
    row_valid,
    base_mod,
    *,
    min_win_rel: int,
    interpret: bool = False,
):
    """Dense-path equivalent of ``update_state``: compute per-slot partials
    with the pallas kernel, then fold them into the ring.

    ``min_win_rel`` is the smallest window index (relative to first_open) any
    row of this batch touches; the kernel works in ``rel - min_win_rel``
    space so K_ACTIVE covers the batch's span.  Caller guarantees the span
    fits (else it uses the scatter path).  The k-way sliding fan-out is one
    (B, k) rel matrix → ONE kernel launch regardless of k."""
    k = spec.length_units
    rel_cols = []
    for i in range(k):
        wr = win_rel - i
        ok = row_valid & (wr >= 0) & (wr < spec.window_slots)
        if spec.length_ms - i * spec.slide_ms < spec.slide_ms:
            ok = ok & (rem < spec.length_ms - i * spec.slide_ms)
        rel_cols.append(jnp.where(ok, wr - min_win_rel, -1).astype(jnp.int32))
    rel = jnp.stack(rel_cols, axis=1)  # (B, k)
    partials = _dense_partials(
        values,
        colvalid,
        rel,
        gid,
        G=spec.group_capacity,
        V=max(spec.num_value_cols, 1),
        KREL=k,
        interpret=interpret,
    )
    base = (base_mod + jnp.asarray(min_win_rel, jnp.int32)) % spec.window_slots
    return _merge_partials(spec, state, partials, base)
