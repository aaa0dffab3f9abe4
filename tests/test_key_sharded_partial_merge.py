"""``partial_merge`` over a key-sharded mesh, on four of the CPU's virtual
devices: the host splits a packed unit by key block and every device folds
its own share with the program a single device runs; the ring, the finals
and the active bits stay split over the key axis through every program
that touches them; and the counters say which block the cells fell in."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe
from denormalized_tpu.parallel import sharded_state as ss
from denormalized_tpu.parallel.mesh import KEY_AXIS, make_mesh
from denormalized_tpu.sources.memory import MemorySource

N = 4
W = 16
AGGS = (("count", 0), ("sum", 0), ("min", 0), ("max", 0), ("avg", 0))

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < N, reason="needs four virtual devices"
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _spec(G, length=1000, slide=1000):
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for(list(AGGS))), num_value_cols=1,
        window_slots=W, group_capacity=G, length_ms=length, slide_ms=slide,
    )


def _rows(G, slide, n, seed, live=None, nulls=False):
    """``n`` rows over three slide units and the first ``live`` groups."""
    rng = np.random.default_rng(seed)
    ts = rng.integers(3 * slide, 6 * slide, n)
    unit = ts // slide
    return (
        unit.astype(np.int64), (ts - unit * slide).astype(np.int32),
        rng.integers(0, live or G, n).astype(np.int32),
        rng.uniform(10, 99, (n, 1)),
        (rng.random((n, 1)) > 0.2) if nulls else None,
    )


def _fill(stripe, rows):
    unit, rem, gid, x, valid = rows
    stripe.add_batch(unit, rem, gid, x, valid, None)


#: name -> (G, length, slide, rows, live groups, nulls): compact and dense
#: units, one and two sub-buckets, lean and full planes, a young key space
#: (every cell in block 0) and a full one
SPLITS = {
    "compact": (8192, 1000, 1000, 1500, None, False),
    "compact_sub2_nulls": (8192, 1000, 400, 1500, None, True),
    "compact_young": (8192, 1000, 1000, 600, 2000, False),
    "dense": (512, 1000, 1000, 3000, None, False),
    "dense_sub2_nulls": (512, 1000, 400, 3000, None, True),
}


@pytest.mark.parametrize("case", sorted(SPLITS))
def test_the_blocks_of_a_split_unit_add_up_to_the_unsplit_merge(case, mesh):
    G, length, slide, n, live, nulls = SPLITS[case]
    spec = _spec(G, length, slide)
    rows = _rows(G, slide, n, 11, live, nulls)
    whole, split = HostPartialStripe(spec, G), HostPartialStripe(spec, G, N)
    _fill(whole, rows)
    _fill(split, rows)
    packs1, packs4 = whole.take_packed(5), split.take_packed(5)
    assert len(packs1) == len(packs4) == 3
    G_block = G // N
    for (p1, a1, lean1, dense1), (p4, a4, lean4, dense4) in zip(packs1, packs4):
        assert lean1 == lean4 == (not nulls)
        assert dense4 == case.startswith("dense")
        rows4 = split.n_planes(lean4) + (0 if dense4 else 1)
        assert p4.shape == (N, rows4, a4 + 2)
        assert a4 == split.block_cells if dense4 else (
            a4 in split.transfer_buckets())
        # the header rides in every block
        assert (p4[:, 0, a4:] == p1[0, a1:]).all()
        if dense1 or dense4:
            continue
        # cell for cell: a block's ids, made global again, and its planes
        ids, planes = [], []
        for b in range(N):
            local = p4[b, 0, :a4]
            k = int((local >= 0).sum())
            assert (local[:k] >= 0).all() and (local[k:] == -1).all()
            assert (np.diff(local[:k]) > 0).all()  # ascending, distinct
            s, g = local[:k] // G_block, local[:k] % G_block
            ids.append(s * G + b * G_block + g)
            planes.append(p4[b, 1:, :k])
        ids, planes = np.concatenate(ids), np.concatenate(planes, axis=1)
        order = np.argsort(ids, kind="stable")
        A = int((p1[0, :a1] >= 0).sum())
        np.testing.assert_array_equal(ids[order], p1[0, :A])
        np.testing.assert_array_equal(planes[:, order], p1[1:, :A])
    assert split.cells_active == whole.cells_active
    assert split.cells_by_block.sum() == split.cells_active
    assert whole.cells_by_block.tolist() == [whole.cells_active]
    if live:  # a young key space: every cell in block 0, padding in the rest
        assert split.cells_by_block[1:].sum() == 0
    assert split.bytes_packed == sum(p[0].nbytes for p in packs4)

    # and on the devices: the four folds are the one fold
    state = sa.init_state(spec)
    for packed, a_pad, lean, dense in packs1:
        state = sa.merge_partials(
            spec, whole.SUB, a_pad, lean, dense, state, jnp.asarray(packed)
        )
    backend = ss.KeyShardedPartialMergeWindowState(spec, mesh)
    for packed, a_pad, lean, dense in packs4:
        backend._merge(packed, a_pad, lean, dense)
    got = backend.export()
    for label, want in jax.device_get(state).items():
        np.testing.assert_array_equal(got[label], want, err_msg=label)


def test_unpack_active_by_blocks():
    rng = np.random.default_rng(5)
    active = rng.random((3, N * 1024)) > 0.5
    side_by_side = np.concatenate(
        [np.asarray(sa.pack_active(jnp.asarray(blk)))
         for blk in np.split(active, N, axis=1)], axis=1,
    )
    np.testing.assert_array_equal(sa.unpack_active(side_by_side, N), active)
    np.testing.assert_array_equal(
        sa.unpack_active(np.asarray(sa.pack_active(jnp.asarray(active)))),
        active,
    )


def _assert_split_over_keys(mesh, tree, what):
    """Every array of ``tree`` is laid out ``P(None, keys)``: a device holds
    a quarter of the group axis and all of the other."""
    want = NamedSharding(mesh, P(None, KEY_AXIS))
    for label, arr in tree.items():
        assert arr.sharding.is_equivalent_to(want, arr.ndim), (what, label)
        for shard in arr.addressable_shards:
            assert shard.data.shape == (
                arr.shape[0], arr.shape[1] // N), (what, label)


def test_ring_and_finals_stay_split_through_every_program(mesh):
    G = 8192
    spec = _spec(G)
    backend = ss.KeyShardedPartialMergeWindowState(spec, mesh)
    assert backend.key_blocks == N and backend.group_capacity == G
    assert backend.spec.group_capacity == G // N
    _assert_split_over_keys(mesh, backend._state, "init")

    def feed():
        unit, rem, gid, x, valid = _rows(G, 1000, 4000, 2)
        backend.accumulate(unit - 3, rem, gid, x, valid, None, 0)
        backend.flush_pending()

    feed()
    _assert_split_over_keys(mesh, backend._state, "merge")
    # finals emission: n windows of every block's prefix, side by side
    backend.prepare_finals(AGGS)
    for live, width in ((300, 1024), (G, G // N)):
        out = backend.read_reset_block_finals_start(0, 2, live_groups=live)
        _assert_split_over_keys(mesh, out, "finals")
        assert out["__final_0__"].shape == (2, N * width)
        assert out[sa.ACTIVE_BITS].shape == (2, N * width // 8)
        _assert_split_over_keys(mesh, backend._state, "finals: ring")
        backend.read_reset_block_finish(out)
    feed()
    out = backend.read_reset_block_start(1, 1, live_groups=G, lean=True)
    _assert_split_over_keys(mesh, out, "gather")
    _assert_split_over_keys(mesh, backend._state, "gather: ring")
    feed()
    backend.reset_slot(2)
    _assert_split_over_keys(mesh, backend._state, "reset")
    assert (backend.read_slot(2)[sa.ROW_COUNT.label] == 0).all()
    # growth: the operator exports, builds a wider backend and imports
    host = backend.export()
    wider = ss.KeyShardedPartialMergeWindowState(
        dataclasses.replace(spec, group_capacity=2 * G), mesh
    )
    wider.import_(host)
    _assert_split_over_keys(mesh, wider._state, "import")
    got = wider.export()
    for label, want in host.items():
        np.testing.assert_array_equal(got[label][:, :G], want)
    snap = wider.export_start()
    _assert_split_over_keys(mesh, snap, "clone")


def _window_op(ctx):
    from denormalized_tpu.physical.window_exec import StreamingWindowExec

    node = ctx._last_physical
    while not isinstance(node, StreamingWindowExec):
        node = node.children[0]
    return node


def _run(make_batch, config, n_keys, slide_ms=None, finals=True):
    rng = np.random.default_rng(31)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(16):
        n = 1024
        ts = np.sort(t0 + b * 300 + rng.integers(0, 300, n))
        keys = np.array(
            [f"s{i}" for i in rng.integers(0, n_keys, n)], dtype=object
        )
        batches.append(make_batch(ts, keys, rng.normal(50, 5, n)))
    aggs = [
        F.count(col("reading")).alias("cnt"),
        F.sum(col("reading")).alias("s"),
        F.min(col("reading")).alias("mn"),
        F.max(col("reading")).alias("mx"),
        F.avg(col("reading")).alias("a"),
    ]
    if not finals:  # the variance family is finalized on the host
        aggs.append(F.stddev(col("reading")).alias("sd"))
    ctx = Context(config)
    res = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(["sensor_name"], aggs, 1000, slide_ms).collect()
    out = {}
    for i in range(res.num_rows):
        out[(int(res.column(WINDOW_START_COLUMN)[i]),
             res.column("sensor_name")[i])] = tuple(
            float(res.column(name)[i]) for name in ("cnt", "s", "mn", "mx", "a")
        )
    return ctx, out


@pytest.mark.parametrize("finals", [True, False], ids=["finals", "gather"])
@pytest.mark.parametrize("slide_ms", [None, 400], ids=["tumbling", "sliding"])
@pytest.mark.parametrize(
    "n_keys,capacity",
    [(300, 16384), (6000, 16384), (3000, 512)],
    ids=["young", "past_a_block", "grows"],
)
def test_mesh_of_four_delivers_what_one_device_delivers(
    make_batch, mesh, n_keys, capacity, slide_ms, finals,
):
    """Under the default strategies (``auto``, ``auto``): few keys (all in
    block 0's prefix), more keys than a block holds, and a ring that grows
    — each against the single device, window by window."""
    _c, want = _run(
        make_batch, EngineConfig(min_group_capacity=capacity), n_keys,
        slide_ms, finals,
    )
    ctx, got = _run(
        make_batch,
        EngineConfig(mesh_devices=N, min_group_capacity=capacity),
        n_keys, slide_ms, finals,
    )
    assert set(got) == set(want) and len(want) > n_keys
    for key, w in want.items():
        g = got[key]
        assert g[0] == w[0] and g[2] == w[2] and g[3] == w[3], key
        np.testing.assert_allclose(g[1], w[1], rtol=1e-5, err_msg=str(key))
        np.testing.assert_allclose(g[4], w[4], rtol=1e-5, err_msg=str(key))
    op = _window_op(ctx)
    m = op.metrics()
    assert m["strategy_resolved"] == "partial_merge/key_sharded"
    assert m["mesh_devices"] == N
    assert (m["grow_events"] > 0) == (capacity == 512)
    # the ring is still split after merges, emissions and growth
    _assert_split_over_keys(op._mesh, op._backend._state, "operator")
    # every active cell fell in one key block
    by_shard = m["merge_cells_by_shard"]
    assert len(by_shard) == N and sum(by_shard) == m["stripe_cells_active"] > 0
    assert by_shard == [m[f"merge_cells_shard_{i}"] for i in range(N)]
    # every byte of a packed matrix crossed once, to its own device
    assert m["bytes_h2d"] == m["stripe_bytes_packed"] > 0
    if n_keys == 300:  # ids are dealt in order: a young job is block 0's
        assert by_shard[1:] == [0] * (N - 1)


def test_one_device_reports_one_key_block(make_batch):
    ctx, _ = _run(make_batch, EngineConfig(), 300)
    m = _window_op(ctx).metrics()
    assert m["mesh_devices"] == 1
    assert m["merge_cells_by_shard"] == [m["stripe_cells_active"]]
    assert m["merge_cells_shard_0"] == m["stripe_cells_active"] > 0
    assert m["bytes_h2d"] == m["stripe_bytes_packed"] > 0
