"""Sliding windows, capacity growth, and null handling."""

import collections

import numpy as np

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.sources.memory import MemorySource


def test_sliding_window_fanout(sensor_schema, make_batch):
    """1s window / 200ms slide: every row lands in exactly 5 windows
    (the reference enumerates overlapping slides at
    streaming_window.rs:1063-1075; we fan out on device)."""
    rng = np.random.default_rng(1)
    t0 = 1_700_000_000_000
    batches = [
        make_batch(
            np.sort(t0 + i * 300 + rng.integers(0, 300, 50)),
            ["s"] * 50,
            rng.normal(0, 1, 50),
        )
        for i in range(10)
    ]
    ctx = Context()
    res = (
        ctx.from_source(
            MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("cnt")], 1000, 200)
        .collect()
    )
    starts = res.column(WINDOW_START_COLUMN)
    assert (np.diff(sorted(set(starts.tolist()))) == 200).all()
    assert sum(int(c) for c in res.column("cnt")) == 500 * 5


def test_sliding_window_non_multiple_slide(sensor_schema, make_batch):
    """Window length not a multiple of slide (1000ms/300ms): membership uses
    the exact ms bound, k = ceil(L/S) = 4 but some rows hit only 3 windows."""
    t0 = 1_700_000_000_000
    ts = t0 + np.arange(0, 3000, 10)
    batches = [make_batch(ts, ["s"] * len(ts), np.ones(len(ts)))]
    ctx = Context()
    res = (
        ctx.from_source(
            MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("cnt")], 1000, 300)
        .collect()
    )
    got = {
        int(res.column(WINDOW_START_COLUMN)[i]): int(res.column("cnt")[i])
        for i in range(res.num_rows)
    }
    oracle = collections.Counter()
    for t in ts.tolist():
        j = t // 300
        while j * 300 + 1000 > t:
            if j * 300 <= t:
                oracle[j * 300] += 1
            j -= 1
    assert got == dict(oracle)


def test_group_capacity_growth_first_batch(sensor_schema, make_batch):
    """More distinct keys in the first batch than the initial capacity (128):
    G must grow before any scatter drops data."""
    rng = np.random.default_rng(2)
    t0 = 1_700_000_000_000
    n = 5000
    ts = np.sort(t0 + rng.integers(0, 2000, n))
    keys = np.array([f"k{i}" for i in rng.integers(0, 2000, n)], dtype=object)
    vals = rng.normal(0, 1, n)
    ctx = Context()
    res = (
        ctx.from_source(
            MemorySource.from_batches(
                [make_batch(ts, keys, vals)], timestamp_column="occurred_at_ms"
            )
        )
        .window(["sensor_name"], [F.sum(col("reading")).alias("s")], 1000)
        .collect()
    )
    oracle = collections.defaultdict(float)
    for t, k, v in zip(ts, keys, vals):
        oracle[((t // 1000) * 1000, k)] += v
    got = {
        (int(res.column(WINDOW_START_COLUMN)[i]), res.column("sensor_name")[i]): float(
            res.column("s")[i]
        )
        for i in range(res.num_rows)
    }
    assert set(got) == set(oracle)
    for k in oracle:
        np.testing.assert_allclose(got[k], oracle[k], rtol=1e-4, atol=1e-4)


def test_window_ring_growth(sensor_schema, make_batch):
    """A single batch spanning 40 windows grows the ring (initial 16)."""
    t0 = 1_700_000_000_000
    ts = t0 + np.arange(0, 40_000, 100)
    ctx = Context()
    res = (
        ctx.from_source(
            MemorySource.from_batches(
                [make_batch(ts, ["a"] * len(ts), np.ones(len(ts)))],
                timestamp_column="occurred_at_ms",
            )
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("cnt")], 1000)
        .collect()
    )
    assert res.num_rows == 40
    assert all(int(c) == 10 for c in res.column("cnt"))


def test_null_values_excluded(sensor_schema):
    """Null readings are excluded from count/sum/avg/min/max
    (DataFusion null semantics the reference inherits)."""
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
    ctx = Context()
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


def test_multi_column_group_by():
    """2- and 3-column group keys (int64-packing fast path and the general
    row-dedup path) must match a per-row oracle exactly."""
    from denormalized_tpu.common.schema import DataType, Field, Schema

    schema = Schema(
        [
            Field("ts", DataType.INT64, nullable=False),
            Field("region", DataType.STRING, nullable=False),
            Field("sensor", DataType.STRING, nullable=False),
            Field("device_id", DataType.INT64, nullable=False),
            Field("v", DataType.FLOAT64),
        ]
    )
    rng = np.random.default_rng(3)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(5):
        n = 800
        ts = np.sort(t0 + b * 400 + rng.integers(0, 400, n))
        batches.append(
            RecordBatch(
                schema,
                [
                    ts,
                    np.array([f"r{i}" for i in rng.integers(0, 4, n)], dtype=object),
                    np.array([f"s{i}" for i in rng.integers(0, 7, n)], dtype=object),
                    rng.integers(0, 3, n).astype(np.int64),
                    rng.normal(0, 1, n),
                ],
            )
        )
    for group_cols in (["region", "sensor"], ["region", "sensor", "device_id"]):
        ctx = Context()
        res = (
            ctx.from_source(
                MemorySource.from_batches(batches, timestamp_column="ts")
            )
            .window(group_cols, [F.count(col("v")).alias("c")], 1000)
            .collect()
        )
        oracle = collections.Counter()
        for bt in batches:
            for i in range(bt.num_rows):
                key = tuple(bt.column(g)[i] for g in group_cols) + (
                    (int(bt.column("ts")[i]) // 1000) * 1000,
                )
                oracle[key] += 1
        got = {
            tuple(res.column(g)[i] for g in group_cols)
            + (int(res.column("window_start_time")[i]),): int(res.column("c")[i])
            for i in range(res.num_rows)
        }
        assert got == dict(oracle)


def test_single_numeric_group_column():
    """Review regression: grouping by one numeric column must produce a
    working reverse map and capacity accounting."""
    from denormalized_tpu.common.schema import DataType, Field, Schema
    from denormalized_tpu.ops.interner import GroupInterner

    g = GroupInterner(1)
    ids = g.intern([np.array([10, 20, 10, 30], dtype=np.int64)])
    assert ids.tolist() == [0, 1, 0, 2]
    assert len(g) == 3
    kv = g.keys_of(np.array([0, 1, 2]))
    assert kv[0].tolist() == [10, 20, 30]

    schema = Schema(
        [
            Field("ts", DataType.INT64, nullable=False),
            Field("device_id", DataType.INT64, nullable=False),
            Field("v", DataType.FLOAT64),
        ]
    )
    t0 = 1_700_000_000_000
    batch = RecordBatch(
        schema,
        [
            np.array([t0, t0 + 10, t0 + 20, t0 + 1500], dtype=np.int64),
            np.array([7, 8, 7, 7], dtype=np.int64),
            np.array([1.0, 2.0, 3.0, 4.0]),
        ],
    )
    ctx = Context()
    res = (
        ctx.from_source(MemorySource.from_batches([batch], timestamp_column="ts"))
        .window(["device_id"], [F.sum(col("v")).alias("s")], 1000)
        .collect()
    )
    got = {
        (int(res.column("device_id")[i]), int(res.column(WINDOW_START_COLUMN)[i])): float(
            res.column("s")[i]
        )
        for i in range(res.num_rows)
    }
    assert got == {(7, t0): 4.0, (8, t0): 2.0, (7, t0 + 1000): 4.0}


def test_unicode_group_keys_and_restore():
    from denormalized_tpu.ops.interner import GroupInterner

    keys = np.array(["München", "東京", "München", "naïve"], dtype=object)
    g = GroupInterner(1)
    ids = g.intern([keys])
    assert ids.tolist() == [0, 1, 0, 2]
    assert g.keys_of(np.array([1]))[0][0] == "東京"
    g2 = GroupInterner.restore(g.snapshot())
    assert g2.intern([keys]).tolist() == [0, 1, 0, 2]

    # numeric restore keeps id continuity (review regression)
    gnum = GroupInterner(1)
    gnum.intern([np.array([10, 20], np.int64)])
    gnum2 = GroupInterner.restore(gnum.snapshot())
    assert gnum2.intern([np.array([30, 10], np.int64)]).tolist() == [2, 0]


def test_trailing_nul_normalization_consistent():
    """Keys differing only by trailing NULs normalize to one id, the same
    way in native and fallback paths (documented S-dtype limitation)."""
    from denormalized_tpu.ops import interner as im

    keys = np.array(["a", "a\x00"], dtype=object)
    native = im.ColumnInterner()
    ids_native = native.intern_array(keys)
    fb = im.ColumnInterner()
    fb._h = None  # force fallback
    ids_fb = fb.intern_array(keys)
    assert ids_native.tolist() == ids_fb.tolist() == [0, 0]
