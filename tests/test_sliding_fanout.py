"""A window five slides long over a ring so wide that a host stripe spans
ONE slide unit (``HostPartialStripe.U == 1``), fed by four partitions that
pass each unit boundary a batch apart — the shape of the ``sliding_10m``
deployment, at a size a test can hold.  Held against a numpy f64 fold:
every window whole, once, in ascending order, with and without the
post-aggregation filter, on one device and on the four-device key-sharded
mesh; and the counters that say what the deployment works (why each stripe
flush happened, the ring rows and entries the merges fold, the rows
emitted) add up."""

import jax
import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe
from denormalized_tpu.parallel.sharded_state import WindowStateBackend
from denormalized_tpu.physical.window_exec import StreamingWindowExec
from denormalized_tpu.sources.memory import MemorySource

T0 = 1_700_000_000_000
LENGTH, SLIDE, K = 10_000, 2_000, 5
PARTS, KEYS = 4, 3_000
# past MAX_STRIPE_CELLS / 2 groups, so a stripe holds one unit and no more
CAPACITY = 300_000
BATCH_MS, BATCH_ROWS, ROUNDS = 250, 192, 72  # 18 s of event time a partition
THRESHOLD, BAND = 45.0, 1e-4


def _feed(make_batch):
    """Four partitions in order, partition ``p`` lagging ``p`` batches: in
    the round-robin a unit boundary is passed by one partition a round, so
    around each the batches alternate between unit u + 1 and unit u."""
    rng = np.random.default_rng(33)
    parts, rows = [], []
    for p in range(PARTS):
        batches = []
        for i in range(ROUNDS):
            lo = T0 + (i - p + PARTS) * BATCH_MS
            ts = np.sort(lo + rng.integers(0, BATCH_MS, BATCH_ROWS))
            kid = rng.integers(0, KEYS, BATCH_ROWS)
            x = 40.0 + 2.0 * (kid % 10) + rng.standard_normal(BATCH_ROWS) * 10
            batches.append(make_batch(
                ts, np.array([f"key_{k:04d}" for k in kid], dtype=object), x
            ))
            rows.append((ts, kid, x))
        parts.append(batches)
    ts, kid, x = (np.concatenate(c) for c in zip(*rows))
    return parts, ts, kid, x


def _reference(ts, kid, x):
    """f64 fold: ``{(window start, key id): (count, avg)}``; a row at unit
    u belongs to the windows that start at units u - K + 1 .. u."""
    unit = ts // SLIDE
    cell = np.concatenate([(unit - i) * KEYS + kid for i in range(K)])
    ids, inv = np.unique(cell, return_inverse=True)
    cnt = np.bincount(inv)
    avg = np.bincount(inv, weights=np.tile(x, K)) / cnt
    return {
        (c // KEYS * SLIDE, c % KEYS): (n, a)
        for c, n, a in zip(ids.tolist(), cnt.tolist(), avg.tolist())
    }


def _run(parts, config, filtered):
    ctx = Context(config)
    ds = ctx.from_source(
        MemorySource(parts, timestamp_column="occurred_at_ms")
    ).window(
        ["sensor_name"],
        [F.count(col("reading")).alias("cnt"),
         F.avg(col("reading")).alias("avg")],
        LENGTH, SLIDE,
    )
    if filtered:
        ds = ds.filter(col("avg") > THRESHOLD)
    res = ds.collect()
    node = ctx._last_physical
    while not isinstance(node, StreamingWindowExec):
        node = node.children[0]
    return res, node


@pytest.mark.parametrize("filtered", [False, True], ids=["all", "filtered"])
@pytest.mark.parametrize("devices", [None, 4], ids=["one_device", "mesh_of_4"])
def test_five_way_fan_out_with_a_one_unit_stripe(make_batch, devices, filtered):
    if devices and len(jax.devices()) < devices:
        pytest.skip("needs four virtual devices")
    parts, ts, kid, x = _feed(make_batch)
    res, op = _run(
        parts,
        EngineConfig(min_group_capacity=CAPACITY, mesh_devices=devices),
        filtered,
    )
    assert op._backend._stripe.U == 1
    assert op._spec.length_units == K
    want = _reference(ts, kid, x)

    ws = np.asarray(res.column(WINDOW_START_COLUMN), dtype=np.int64)
    keys = np.array([int(k[4:]) for k in res.column("sensor_name")])
    cnt = np.asarray(res.column("cnt"))
    avg = np.asarray(res.column("avg"), dtype=np.float64)
    # windows leave in ascending order, a (window, key) once
    assert (np.diff(ws) >= 0).all()
    got = dict(zip(zip(ws.tolist(), keys.tolist()), zip(cnt.tolist(), avg.tolist())))
    assert len(got) == len(ws)
    # every window whole: each row the fold has is there (a filtered run:
    # those above the threshold, a row within the band either way)
    near = lambda a: abs(a - THRESHOLD) < BAND * THRESHOLD  # noqa: E731
    for cell, (n, a) in want.items():
        if filtered and near(a):
            continue
        if filtered and a <= THRESHOLD:
            assert cell not in got, cell
            continue
        g = got[cell]
        assert g[0] == n, cell
        assert g[1] == pytest.approx(a, rel=1e-5), cell
    assert all(c in want for c in got)

    m = op.metrics()
    assert m["late_rows"] == 0 and m["grow_events"] == 0
    assert m["rows_in"] == len(ts)
    # the operator emitted every row of the fold, whatever the filter kept
    assert m["emit_rows"] == len(want)
    assert m["emit_rows"] == len(got) if not filtered else m["emit_rows"] > len(got)
    # why each flush happened: the reasons add up to the flushes, and the
    # partitions passing a boundary a batch apart flush for the span
    reasons = {r: m[f"flush_reason_{r}"] for r in WindowStateBackend.FLUSH_REASONS}
    assert sum(reasons.values()) == m["device_steps"] == m["partial_merges"]
    n_units = int(ts.max() // SLIDE - ts.min() // SLIDE) + 1
    assert reasons["span"] >= (n_units - 1) * (PARTS - 1)
    assert reasons["close"] > 0 and reasons["lag"] == 0  # no emit lag on the CPU
    # a one-unit stripe merges one unit a flush, and no unit lies at the
    # ring's edge in an in-order stream: K ring rows a merge, every active
    # cell folded into K windows
    assert m["merge_window_folds"] == K * m["device_steps"]
    assert m["merge_fold_entries"] == K * m["stripe_cells_active"]


def _spec(length, slide, G=1024, W=16):
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for([("count", 0), ("avg", 0)])),
        num_value_cols=1, window_slots=W, group_capacity=G,
        length_ms=length, slide_ms=slide,
    )


@pytest.mark.parametrize(
    "length,slide,u_rel,folds,entries",
    [
        (10_000, 2_000, 7, 5, 5 * 6),     # inside the ring: K windows
        (10_000, 2_000, 1, 2, 2 * 6),     # windows below the ring's base take nothing
        (10_000, 2_000, 17, 3, 3 * 6),    # nor those past its last slot (W = 16)
        (10_000, 2_000, -1, 0, 0),
        (1_000, 400, 5, 3, 2 * 6 + 4),    # two sub-buckets: the oldest window takes sub 0 alone
    ],
    ids=["inside", "low_edge", "high_edge", "outside", "sub_buckets"],
)
def test_fold_counters_follow_the_ring_edges(length, slide, u_rel, folds, entries):
    """``merge_window_folds`` / ``merge_fold_entries`` count what
    ``merge_partials_body`` does with a packed unit: a fold a window inside
    the ring, the unit's entries into each — sub-bucket 0 alone into the
    oldest where the length is no multiple of the slide."""
    stripe = HostPartialStripe(_spec(length, slide), 1024)
    gid = np.arange(6, dtype=np.int32)
    # six cells; with two sub-buckets, four of them before the edge
    rem = np.array([0, 0, 0, 0, slide - 1, slide - 1], np.int32)
    stripe.add_batch(
        np.full(6, u_rel, np.int64), rem, gid, np.ones((6, 1)), None, None
    )
    (packed, a_pad, lean, dense), = stripe.take_packed(0)
    assert stripe.cells_active == 6
    assert (stripe.window_folds, stripe.fold_entries) == (folds, entries)
    # and the device agrees: ring rows the merge wrote = folds, a row of each
    # cell into each = entries
    spec = stripe.spec
    state = sa.merge_partials(
        spec, stripe.SUB, a_pad, lean, dense, sa.init_state(spec),
        jax.numpy.asarray(packed),
    )
    rows = np.asarray(state[sa.ROW_COUNT.label])
    assert int((rows.sum(axis=1) > 0).sum()) == folds
    assert int(rows.sum()) == entries
