"""Pallas dense-path (MXU/VPU) window kernel vs the scatter path: identical
results on tumbling and sliding workloads (interpret mode on CPU)."""


import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.sources.memory import MemorySource


def _run(strategy, batches, slide=None, expect_dense=None):
    from denormalized_tpu.ops import pallas_window as pw

    calls = {"n": 0}
    orig = pw.dense_update

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    pw.dense_update = spy
    try:
        return _run_inner(strategy, batches, slide, calls, expect_dense)
    finally:
        pw.dense_update = orig


def _run_inner(strategy, batches, slide, calls, expect_dense):
    ctx = Context(EngineConfig(device_strategy=strategy))
    res = (
        ctx.from_source(
            MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
        )
        .window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("cnt"),
                F.sum(col("reading")).alias("s"),
                F.min(col("reading")).alias("mn"),
                F.max(col("reading")).alias("mx"),
                F.avg(col("reading")).alias("a"),
            ],
            1000,
            slide,
        )
        .collect()
    )
    if expect_dense is not None:
        # the dense kernel must ACTUALLY run (or not) — guards against the
        # silent-fallback regression where both sides compared scatter
        assert (calls["n"] > 0) == expect_dense, calls
    return {
        (int(res.column(WINDOW_START_COLUMN)[i]), res.column("sensor_name")[i]): (
            int(res.column("cnt")[i]),
            float(res.column("s")[i]),
            float(res.column("mn")[i]),
            float(res.column("mx")[i]),
        )
        for i in range(res.num_rows)
    }


@pytest.mark.parametrize("slide", [None, 500, 200])
def test_pallas_dense_matches_scatter(make_batch, slide):
    # slide=200 is the BASELINE.md sliding config's shape (k=5): the k-way
    # fan-out rides the (TILE, k) rel matrix in a single kernel launch
    rng = np.random.default_rng(7)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(8):
        n = 400
        ts = np.sort(t0 + b * 600 + rng.integers(0, 600, n))
        keys = np.array(
            [f"k{i}" for i in rng.integers(0, 23, n)], dtype=object
        )
        batches.append(make_batch(ts, keys, rng.normal(50, 10, n)))
    scatter = _run("scatter", batches, slide, expect_dense=False)
    dense = _run("pallas_dense", batches, slide, expect_dense=True)
    assert set(scatter) == set(dense)
    for k in scatter:
        # counts and extrema are exact; sums may differ in f32 reduction
        # order (tile-tree vs sequential scatter)
        assert scatter[k][0] == dense[k][0], (k, scatter[k], dense[k])
        np.testing.assert_allclose(scatter[k][1], dense[k][1], rtol=1e-5)
        assert scatter[k][2] == dense[k][2]
        assert scatter[k][3] == dense[k][3]


def test_pallas_dense_with_nulls(sensor_schema):
    from denormalized_tpu.common.record_batch import RecordBatch

    t0 = 1_700_000_000_000
    batch = RecordBatch(
        sensor_schema,
        [
            np.array([t0 + 10, t0 + 20, t0 + 30, t0 + 1500], dtype=np.int64),
            np.array(["a", "a", "a", "a"], dtype=object),
            np.array([1.0, 99.0, 3.0, 0.0]),
        ],
        masks=[None, None, np.array([True, False, True, True])],
    )
    ctx = Context(EngineConfig(device_strategy="pallas_dense"))
    res = (
        ctx.from_source(
            MemorySource.from_batches([batch], timestamp_column="occurred_at_ms")
        )
        .window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("cnt"),
                F.sum(col("reading")).alias("s"),
                F.max(col("reading")).alias("mx"),
            ],
            1000,
        )
        .collect()
    )
    i = list(res.column(WINDOW_START_COLUMN)).index(t0)
    assert int(res.column("cnt")[i]) == 2
    assert float(res.column("s")[i]) == 4.0
    assert float(res.column("mx")[i]) == 3.0


def test_pallas_falls_back_on_high_cardinality(make_batch):
    """G beyond the dense limit must silently use the scatter path."""
    rng = np.random.default_rng(8)
    t0 = 1_700_000_000_000
    n = 4000
    keys = np.array([f"k{i}" for i in rng.integers(0, 3000, n)], dtype=object)
    batches = [
        make_batch(np.sort(t0 + rng.integers(0, 1500, n)), keys, rng.normal(0, 1, n))
    ]
    ctx = Context(EngineConfig(device_strategy="pallas_dense"))
    res = (
        ctx.from_source(
            MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("c")], 1000)
        .collect()
    )
    assert sum(int(c) for c in res.column("c")) == n


def test_pallas_dense_nan_behind_mask(sensor_schema):
    """NaN values behind an invalid mask must not poison dense sums
    (review regression: multiplicative masking 0*NaN)."""
    from denormalized_tpu.common.record_batch import RecordBatch

    t0 = 1_700_000_000_000
    batch = RecordBatch(
        sensor_schema,
        [
            np.array([t0 + 10, t0 + 20, t0 + 30, t0 + 1500], dtype=np.int64),
            np.array(["a"] * 4, dtype=object),
            np.array([1.0, np.nan, 3.0, 0.0]),
        ],
        masks=[None, None, np.array([True, False, True, True])],
    )
    ctx = Context(EngineConfig(device_strategy="pallas_dense"))
    res = (
        ctx.from_source(
            MemorySource.from_batches([batch], timestamp_column="occurred_at_ms")
        )
        .window(["sensor_name"], [F.sum(col("reading")).alias("s")], 1000)
        .collect()
    )
    i = list(res.column(WINDOW_START_COLUMN)).index(t0)
    assert float(res.column("s")[i]) == 4.0


def test_pallas_dense_small_bucket_falls_back(make_batch):
    """min_batch_bucket below the kernel tile must fall back, not crash."""
    t0 = 1_700_000_000_000
    batches = [make_batch([t0 + i * 100 for i in range(8)], ["a"] * 8, [1.0] * 8),
               make_batch([t0 + 2500], ["a"], [1.0])]
    ctx = Context(EngineConfig(device_strategy="pallas_dense", min_batch_bucket=64))
    res = (
        ctx.from_source(
            MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("c")], 1000)
        .collect()
    )
    assert sum(int(c) for c in res.column("c")) == 9


def test_dense_partials_tiles_the_group_axis():
    """Above GROUP_TILE groups the kernel runs one grid row per group tile
    and the wrapper regroups the (tile, col, lane) columns to (K, V, G):
    every plane must match a per-cell numpy fold, including groups in the
    second and third tiles."""
    from denormalized_tpu.ops import pallas_window as pw

    G, V, KREL, B = 1536, 2, 2, pw.TILE
    assert G // pw.group_tile(G) == 3
    rng = np.random.default_rng(3)
    values = rng.normal(0, 5, (B, V)).astype(np.float32)
    colvalid = rng.random((B, V)) > 0.2
    first = rng.integers(-1, pw.K_ACTIVE - 1, B)  # -1 = dropped row
    rel = np.stack([first, np.where(first >= 0, first + 1, -1)], axis=1)
    gid = rng.integers(0, G, B)
    gid[:4] = [0, pw.GROUP_TILE, 2 * pw.GROUP_TILE, G - 1]  # tile edges
    rowcnt, cnt, ssum, smin, smax = (
        np.asarray(a)
        for a in pw._dense_partials(
            values, colvalid, rel.astype(np.int32), gid.astype(np.int32),
            G=G, V=V, KREL=KREL, interpret=True,
        )
    )
    want_rows = np.zeros((pw.K_ACTIVE, G))
    want_cnt = np.zeros((pw.K_ACTIVE, V, G))
    want_sum = np.zeros((pw.K_ACTIVE, V, G))
    want_min = np.full((pw.K_ACTIVE, V, G), np.inf)
    want_max = np.full((pw.K_ACTIVE, V, G), -np.inf)
    for b in range(B):
        for j in rel[b]:
            if j < 0:
                continue
            want_rows[j, gid[b]] += 1
            for v in range(V):
                if colvalid[b, v]:
                    want_cnt[j, v, gid[b]] += 1
                    want_sum[j, v, gid[b]] += values[b, v]
                    want_min[j, v, gid[b]] = min(want_min[j, v, gid[b]], values[b, v])
                    want_max[j, v, gid[b]] = max(want_max[j, v, gid[b]], values[b, v])
    np.testing.assert_array_equal(rowcnt, want_rows)
    np.testing.assert_array_equal(cnt, want_cnt)
    np.testing.assert_allclose(ssum, want_sum, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(smin, want_min.astype(np.float32))
    np.testing.assert_array_equal(smax, want_max.astype(np.float32))
