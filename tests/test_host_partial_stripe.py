"""The host stripe of ``partial_merge`` on its own: pack → device merge →
ring equals a numpy f64 fold; a taken stripe is a fresh one; every padded
size it can ship was announced for prewarm; and what a sparse stripe costs
does not depend on the group capacity it was allocated for."""

import jax.numpy as jnp
import numpy as np
import pytest

from denormalized_tpu.ops import host_partial
from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe

AGGS = [("count", 0), ("sum", 0), ("min", 0), ("max", 0), ("avg", 0)]
W = 16


def _spec(G, length_ms=1000, slide_ms=1000):
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for(AGGS)), num_value_cols=1,
        window_slots=W, group_capacity=G, length_ms=length_ms,
        slide_ms=slide_ms,
    )


@pytest.fixture(params=["native", "numpy"])
def reducer(request, monkeypatch):
    if request.param == "numpy":
        monkeypatch.setattr(host_partial, "_LIB", None)
        monkeypatch.setattr(host_partial, "_LIB_TRIED", True)
    elif host_partial._native() is None:
        pytest.skip("no native partial_agg here")
    return request.param


# name -> (G, length, slide, rows a batch, batches, live keys, units, nulls)
CASES = {
    "sparse": (4096, 1000, 1000, 40, 2, 4096, 1, False),
    "dense": (256, 1000, 1000, 3000, 2, 200, 1, False),
    "two_units": (256, 1000, 1000, 1500, 3, 256, 2, False),
    "sparse_two_units_nulls": (2048, 1000, 1000, 60, 3, 2048, 2, True),
    "dense_nulls": (128, 1000, 1000, 2000, 2, 100, 1, True),
    "sub2_sliding": (256, 1000, 400, 800, 3, 150, 2, False),
    "sub2_sliding_nulls": (1024, 1000, 400, 90, 3, 1024, 3, True),
    "capacity_far_above_keys": (1 << 18, 10000, 10000, 500, 4, 20000, 1, False),
}


def _rows(case, seed):
    G, length, slide, n, batches, keys, units, nulls = CASES[case]
    rng = np.random.default_rng([seed, len(case)])
    u_first = 5
    out = []
    for _ in range(batches):
        unit = u_first + rng.integers(0, units, n)
        rem = rng.integers(0, slide, n).astype(np.int32)
        gid = rng.integers(0, keys, n).astype(np.int32)
        x = rng.uniform(10.0, 99.0, n)
        valid = rng.random(n) > 0.2 if nulls else np.ones(n, bool)
        out.append((unit.astype(np.int64), rem, gid, x, valid))
    return out


def _expected(spec, SUB, base_mod, batches):
    """numpy f64 fold of the rows into the ring, window by window."""
    G, k = spec.group_capacity, spec.length_units
    edge = spec.length_ms - (k - 1) * spec.slide_ms
    ring = {
        "count_star": np.zeros((W, G)), "count_0": np.zeros((W, G)),
        "sum_0": np.zeros((W, G)), "min_0": np.full((W, G), np.inf),
        "max_0": np.full((W, G), -np.inf),
    }
    for unit, rem, gid, x, valid in batches:
        for i in range(k):
            ok = np.ones(len(unit), bool)
            if SUB == 2 and i == k - 1:
                ok &= rem < edge
            w = unit - i
            ok &= (w >= 0) & (w < W)
            slot = (base_mod + w) % W
            np.add.at(ring["count_star"], (slot[ok], gid[ok]), 1)
            okv = ok & valid
            np.add.at(ring["count_0"], (slot[okv], gid[okv]), 1)
            np.add.at(ring["sum_0"], (slot[okv], gid[okv]), x[okv])
            np.minimum.at(ring["min_0"], (slot[okv], gid[okv]), x[okv])
            np.maximum.at(ring["max_0"], (slot[okv], gid[okv]), x[okv])
    return ring


def _fill(stripe, batches):
    for unit, rem, gid, x, valid in batches:
        stripe.add_batch(
            unit, rem, gid, x.reshape(-1, 1),
            None if valid.all() else valid.reshape(-1, 1), None,
        )


@pytest.mark.parametrize("case", sorted(CASES))
def test_pack_merge_emit_equals_the_f64_fold(case, reducer):
    G, length, slide = CASES[case][:3]
    spec = _spec(G, length, slide)
    stripe = HostPartialStripe(spec, G)
    assert stripe.SUB == (1 if length % slide == 0 else 2)
    batches = _rows(case, 7)
    _fill(stripe, batches)
    base_mod = 11
    packs = stripe.take_packed(base_mod)
    assert len(packs) == len({int(u) for b in batches for u in b[0]})
    state = sa.init_state(spec)
    for packed, a_pad, lean, dense in packs:
        # every size shipped was announced, so it was compiled before the
        # first batch
        assert a_pad == stripe.unit_cells if dense else (
            a_pad in stripe.transfer_buckets())
        assert packed.shape == (
            stripe.n_planes(lean) + (0 if dense else 1), a_pad + 2)
        assert lean == (not CASES[case][7])
        state = sa.merge_partials(
            spec, stripe.SUB, a_pad, lean, dense, state, jnp.asarray(packed)
        )
    want = _expected(spec, stripe.SUB, base_mod, batches)
    for label, w in want.items():
        got = np.asarray(state[label], np.float64)
        if label.startswith("count"):
            np.testing.assert_array_equal(got, w, err_msg=label)
        elif label.startswith("sum"):
            np.testing.assert_allclose(got, w, rtol=2e-6, err_msg=label)
        else:  # exact after float32 rounding
            np.testing.assert_array_equal(
                got.astype(np.float32), w.astype(np.float32), err_msg=label)

    # the stripe after a take is a freshly allocated one
    fresh = HostPartialStripe(spec, G)
    np.testing.assert_array_equal(stripe.rec, fresh.rec)
    assert stripe.is_empty() and stripe.u_base is None and stripe.u_hi == 0
    assert int(stripe._n_touched[0]) == 0 and not stripe.nulls_seen
    assert stripe.take_packed(base_mod) == []
    # and it counts what it did: one cell per distinct (unit, sub, group)
    edge = spec.length_ms - (spec.length_units - 1) * spec.slide_ms
    cells = {
        (int(u), int(stripe.SUB == 2 and r >= edge), int(g))
        for unit, rem, gid, _x, _v in batches
        for u, r, g in zip(unit, rem, gid)
    }
    assert stripe.cells_active == len(cells)
    assert len(cells) <= stripe.cells_shipped == sum(p[1] for p in packs)


@pytest.mark.parametrize("G,sub", [(128, 1), (4096, 1), (4096, 2),
                                   (200_064, 1), (10_000_000, 1)])
def test_every_layout_a_unit_can_take_was_announced(G, sub):
    spec = _spec(G, 1000, 1000 if sub == 1 else 400)
    stripe = HostPartialStripe.__new__(HostPartialStripe)  # no allocation
    stripe.spec, stripe.G, stripe.SUB = spec, G, sub
    stripe.unit_cells = stripe.block_cells = sub * G
    stripe._buckets = HostPartialStripe.buckets_for(sub * G)
    buckets = stripe.transfer_buckets()
    assert buckets == sorted(set(buckets))
    assert all(b & (b - 1) == 0 and 1024 <= b < sub * G for b in buckets)
    sizes = sorted({1, 2, 1023, 1024, 1025, sub * G // 3, sub * G - 1,
                    sub * G} | {b + d for b in buckets for d in (-1, 0, 1)})
    for n_planes in (4, 5, 6):
        for A in (a for a in sizes if 1 <= a <= sub * G):
            a_pad, dense = stripe.layout_for(A, n_planes)
            assert a_pad >= A
            if dense:
                assert a_pad == sub * G
            else:
                assert a_pad in buckets
                # padding within a factor of two, above the floor
                assert a_pad < 2 * A or a_pad == HostPartialStripe.MIN_BUCKET
                # and never more bytes than the dense layout would move
                assert (n_planes + 1) * a_pad <= n_planes * sub * G


def test_allocation_follows_the_units_a_stripe_spans_not_u_max():
    for G, units in ((128, 16), (200_064, 2), (1 << 20, 1)):
        stripe = HostPartialStripe(_spec(G), G)
        assert stripe.U == units
        assert stripe.host_bytes() == 5 * 8 * units * G  # 40 B a cell
    # ten million groups: 400 MB a unit, one unit (not 6.4 GB)
    assert 5 * 8 * max(1, (1 << 19) // 10_000_000) * 10_000_000 == 400_000_000


def test_a_sparse_stripes_cost_does_not_grow_with_the_capacity(reducer):
    touched = {}
    for G in (1 << 16, 1 << 18, 1 << 20):
        stripe = HostPartialStripe(_spec(G), G)
        rng = np.random.default_rng(3)
        n = 200
        gid = rng.integers(0, 1 << 12, n).astype(np.int32)
        stripe.add_batch(
            np.full(n, 9, np.int64), np.zeros(n, np.int32), gid,
            rng.uniform(10, 99, (n, 1)), None, None,
        )
        (packed, a_pad, _lean, dense), = stripe.take_packed(0)
        assert not dense and a_pad == 1024
        touched[G] = stripe.bytes_touched
        assert stripe.cells_active == len(np.unique(gid))
    assert len(set(touched.values())) == 1, touched
    # a byte a cell of the span at the most, once the stripe is not sparse
    G = 1 << 12
    stripe = HostPartialStripe(_spec(G), G)
    n = 2_000
    rng = np.random.default_rng(4)
    gid = rng.integers(0, G, n).astype(np.int32)
    stripe.add_batch(
        np.full(n, 9, np.int64), np.zeros(n, np.int32), gid,
        rng.uniform(10, 99, (n, 1)), None, None,
    )
    stripe.take_packed(0)
    A = len(np.unique(gid))
    assert stripe.bytes_touched == A * 8 + G + 2 * A * 40
