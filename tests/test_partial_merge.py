"""Parity suite for the ``partial_merge`` device strategy: host edge
reduction (native C++ / numpy fallback) + device merge must produce the
same results as the per-row ``scatter`` path across window shapes, nulls,
variance aggregates, late data, capacity growth, and checkpoint export."""

import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.sources.memory import MemorySource


def _run(batches, aggs, length_ms, slide_ms=None, *, strategy, groups=None,
         cfg_extra=None):
    cfg = EngineConfig(device_strategy=strategy, **(cfg_extra or {}))
    ctx = Context(cfg)
    ds = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(
        [col(g) for g in (groups if groups is not None else ["sensor_name"])],
        aggs(),
        length_ms,
        slide_ms,
    )
    result = ds.collect()
    keyed = {}
    group_cols = groups if groups is not None else ["sensor_name"]
    for i in range(result.num_rows):
        key = (int(result.column(WINDOW_START_COLUMN)[i]),) + tuple(
            result.column(g)[i] for g in group_cols
        )
        assert key not in keyed, f"duplicate emission {key}"
        keyed[key] = {
            n: result.column(n)[i]
            for n in result.schema.names
            if n not in group_cols
        }
    return keyed


def _assert_parity(a, b, rtol=1e-6):
    assert set(a) == set(b), (
        f"window/key sets differ: only-scatter={set(a) - set(b)} "
        f"only-partial={set(b) - set(a)}"
    )
    for k in a:
        for name, va in a[k].items():
            vb = b[k][name]
            if isinstance(va, (float, np.floating)):
                if np.isnan(va) and np.isnan(vb):
                    continue
                assert vb == pytest.approx(va, rel=rtol, abs=1e-9), (
                    k, name, va, vb
                )
            else:
                assert va == vb, (k, name, va, vb)


def _sensor_batches(make_batch, n_batches=24, rows=400, keys=10, span=250,
                    seed=0, nulls=False):
    from denormalized_tpu.common.record_batch import RecordBatch

    rng = np.random.default_rng(seed)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(n_batches):
        ts = np.sort(t0 + b * span + rng.integers(0, span, rows))
        names = rng.choice([f"s{i}" for i in range(keys)], size=rows)
        vals = rng.normal(50.0, 10.0, rows)
        batch = make_batch(ts, names, vals)
        if nulls:
            mask = rng.random(rows) > 0.15
            batch = RecordBatch(
                batch.schema, batch.columns, [None, None, mask]
            )
        batches.append(batch)
    return batches


def _std_aggs():
    return [
        F.count(col("reading")).alias("cnt"),
        F.min(col("reading")).alias("mn"),
        F.max(col("reading")).alias("mx"),
        F.avg(col("reading")).alias("av"),
        F.sum(col("reading")).alias("sm"),
    ]


@pytest.mark.parametrize(
    "length,slide",
    [(1000, None), (1000, 250), (500, 200)],  # tumbling; k=4; k=3 with sub
    ids=["tumbling", "sliding_divisible", "sliding_ragged"],
)
def test_partial_matches_scatter(make_batch, length, slide):
    batches = _sensor_batches(make_batch)
    a = _run(batches, _std_aggs, length, slide, strategy="scatter")
    b = _run(batches, _std_aggs, length, slide, strategy="partial_merge")
    assert len(a) > 10
    _assert_parity(a, b)


def test_partial_with_nulls(make_batch):
    batches = _sensor_batches(make_batch, nulls=True)
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    _assert_parity(a, b)


def test_partial_lean_to_full_transition(make_batch):
    """Null-free stripes ship the lean packed layout (per-column count
    planes aliased to the row-count plane); the first null switches the
    stripe to the full layout.  A stream whose nulls start mid-way must
    exercise both layouts and still match scatter exactly — counts in the
    null windows must reflect only valid rows."""
    from denormalized_tpu.common.record_batch import RecordBatch

    rng = np.random.default_rng(3)
    clean = _sensor_batches(make_batch, n_batches=12, seed=3)
    dirty = []
    for b in _sensor_batches(make_batch, n_batches=12, seed=4):
        # shift dirty batches after the clean ones in event time
        ts = np.asarray(b.column("occurred_at_ms")) + 12 * 250
        mask = rng.random(b.num_rows) > 0.2
        dirty.append(
            RecordBatch(b.schema, [ts, b.columns[1], b.columns[2]],
                        [None, None, mask])
        )
    batches = clean + dirty
    # oracle row counts per (window_start, key) INCLUDING null readings:
    # proves the dirty half really carried nulls (cnt < rows somewhere)
    rows_per_window: dict = {}
    for bt in batches:
        ts = np.asarray(bt.column("occurred_at_ms"))
        names = np.asarray(bt.column("sensor_name"))
        for t, nm in zip(ts, names):
            rows_per_window[(int(t) // 1000 * 1000, nm)] = (
                rows_per_window.get((int(t) // 1000 * 1000, nm), 0) + 1
            )
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    _assert_parity(a, b)
    assert any(
        v["cnt"] < rows_per_window[k[0], k[1]] for k, v in a.items()
    ), "no window lost rows to nulls — the full layout was never exercised"


def test_partial_host_pipeline_parity(make_batch):
    """host_pipeline=True moves backend.accumulate onto a worker thread;
    results must be identical to the synchronous path (same stream, same
    windows), including across growth and null batches."""
    batches = _sensor_batches(make_batch, keys=200, nulls=True)
    a = _run(batches, _std_aggs, 1000, 250, strategy="partial_merge")
    b = _run(batches, _std_aggs, 1000, 250, strategy="partial_merge",
             cfg_extra={"host_pipeline": True})
    _assert_parity(a, b)


def test_partial_host_pipeline_error_propagates(make_batch):
    """A failure inside the worker-threaded accumulate must surface on the
    stream thread (not vanish into the pool)."""
    from denormalized_tpu.parallel import sharded_state as ss

    batches = _sensor_batches(make_batch, n_batches=8)
    orig = ss._HostPartialMixin.accumulate
    calls = {"n": 0}

    def boom(self, *a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected stripe failure")
        return orig(self, *a, **k)

    ss._HostPartialMixin.accumulate = boom
    try:
        with pytest.raises(RuntimeError, match="injected stripe failure"):
            _run(batches, _std_aggs, 1000, strategy="partial_merge",
                 cfg_extra={"host_pipeline": True})
    finally:
        ss._HostPartialMixin.accumulate = orig


def test_partial_ungrouped(make_batch):
    batches = _sensor_batches(make_batch)
    a = _run(batches, _std_aggs, 1000, strategy="scatter", groups=[])
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge", groups=[])
    assert len(a) > 3
    _assert_parity(a, b)


def test_partial_variance_family(make_batch):
    batches = _sensor_batches(make_batch)

    def aggs():
        return [
            F.stddev(col("reading")).alias("sd"),
            F.var(col("reading")).alias("vr"),
            F.avg(col("reading")).alias("av"),
        ]

    a = _run(batches, aggs, 1000, strategy="scatter")
    b = _run(batches, aggs, 1000, strategy="partial_merge")
    _assert_parity(a, b, rtol=1e-5)


def test_partial_late_rows_dropped(make_batch):
    """A batch far behind the watermark must be dropped identically."""
    batches = _sensor_batches(make_batch, n_batches=12)
    # splice in a late batch (timestamps from 3 windows earlier)
    rng = np.random.default_rng(9)
    t0 = 1_700_000_000_000
    late = make_batch(
        np.sort(t0 + rng.integers(0, 200, 100)),
        rng.choice(["s0", "s1"], 100),
        rng.normal(0, 1, 100),
    )
    seq = batches[:8] + [late] + batches[8:]
    a = _run(seq, _std_aggs, 1000, strategy="scatter")
    b = _run(seq, _std_aggs, 1000, strategy="partial_merge")
    _assert_parity(a, b)


def test_partial_growth(make_batch):
    """Group capacity and window-slot growth mid-stream (stripe must be
    flushed across the recompilation boundary)."""
    rng = np.random.default_rng(3)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(30):
        rows = 300
        ts = np.sort(t0 + b * 200 + rng.integers(0, 200, rows))
        # cardinality ramps past the 128 default capacity
        hi = 20 + b * 12
        names = rng.choice([f"k{i}" for i in range(hi)], size=rows)
        vals = rng.normal(10.0, 3.0, rows)
        batches.append(make_batch(ts, names, vals))
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    assert len({k[1] for k in a}) > 128
    _assert_parity(a, b)


def test_partial_compensated(make_batch):
    batches = _sensor_batches(make_batch)
    a = _run(
        batches, _std_aggs, 1000, strategy="scatter",
        cfg_extra={"compensated_sums": True},
    )
    b = _run(
        batches, _std_aggs, 1000, strategy="partial_merge",
        cfg_extra={"compensated_sums": True},
    )
    _assert_parity(a, b)


def test_partial_giant_span_batch(make_batch):
    """One catch-up batch spanning far more slide units than a stripe can
    hold (> U_MAX=16) must be chunk-folded, not silently truncated."""
    rng = np.random.default_rng(13)
    t0 = 1_700_000_000_000
    n = 40_000
    ts = np.sort(t0 + rng.integers(0, 40_000, n))  # 40 one-second units
    names = rng.choice([f"s{i}" for i in range(6)], size=n)
    vals = rng.normal(1.0, 0.1, n)
    batches = [make_batch(ts, names, vals)]
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    assert len({k[0] for k in a}) >= 39  # windows across the whole span
    _assert_parity(a, b)


def test_partial_f32_overflow_parity(make_batch):
    """Sums overflowing f32 range: both strategies end at ±inf (the f32
    accumulator's honest answer), never NaN."""
    t0 = 1_700_000_000_000
    n = 64
    ts = np.arange(t0, t0 + n, dtype=np.int64)
    names = np.array(["a"] * n, dtype=object)
    vals = np.full(n, 1e38)
    tail = make_batch(
        np.arange(t0 + 2000, t0 + 2064, dtype=np.int64),
        np.array(["a"] * 64, dtype=object),
        np.ones(64),
    )
    batches = [make_batch(ts, names, vals), tail]
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    key = (t0 // 1000 * 1000, "a")
    assert np.isinf(a[key]["sm"]) and a[key]["sm"] > 0
    assert np.isinf(b[key]["sm"]) and b[key]["sm"] > 0


def test_partial_inf_values_propagate(make_batch):
    """Genuine ±inf inputs: sum must stay ±inf (as scatter yields), not
    NaN from the (hi, lo) split's inf - inf residual."""
    t0 = 1_700_000_000_000
    n = 32
    ts = np.arange(t0, t0 + n, dtype=np.int64)
    names = np.array(["a"] * n, dtype=object)
    vals = np.ones(n)
    vals[3] = np.inf
    tail = make_batch(
        np.arange(t0 + 2000, t0 + 2032, dtype=np.int64),
        np.array(["a"] * 32, dtype=object),
        np.ones(32),
    )
    batches = [make_batch(ts, names, vals), tail]
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    key = (t0 // 1000 * 1000, "a")
    assert np.isinf(a[key]["sm"]) and a[key]["sm"] > 0
    assert np.isinf(b[key]["sm"]) and b[key]["sm"] > 0


def test_partial_nan_values_propagate(make_batch):
    """NaN VALUES (valid, not null) must poison min/max identically on
    every strategy — a plain `x < mn` in the native reducer would skip
    them."""
    t0 = 1_700_000_000_000
    ts = np.arange(t0, t0 + 400, dtype=np.int64)
    names = np.array(["a", "b"] * 200, dtype=object)
    vals = np.ones(400)
    vals[7] = np.nan  # lands in key 'b'
    tail = make_batch(
        np.arange(t0 + 2000, t0 + 2100, dtype=np.int64),
        np.array(["a"] * 100, dtype=object),
        np.ones(100),
    )
    batches = [make_batch(ts, names, vals), tail]
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    key = (t0 // 1000 * 1000, "b")
    assert np.isnan(a[key]["mn"]) and np.isnan(a[key]["mx"])
    assert np.isnan(b[key]["mn"]) and np.isnan(b[key]["mx"])


def test_partial_nan_behind_mask(sensor_schema):
    """A NaN under a false validity bit is a NULL, not a value: it must
    reach no sum, count, min or max on either strategy (the native
    reducer and the scatter program both mask by selection, never by
    multiplying 0 * NaN)."""
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
    a = _run([batch], _std_aggs, 1000, strategy="scatter")
    b = _run([batch], _std_aggs, 1000, strategy="partial_merge")
    _assert_parity(a, b)
    got = b[(t0, "a")]
    assert (got["cnt"], got["sm"], got["mn"], got["mx"], got["av"]) == (
        2, 4.0, 1.0, 3.0, 2.0
    )


def test_partial_numpy_fallback_matches_native(make_batch, monkeypatch):
    from denormalized_tpu.ops import host_partial

    batches = _sensor_batches(make_batch, nulls=True)
    a = _run(batches, _std_aggs, 500, 200, strategy="partial_merge")
    monkeypatch.setattr(host_partial, "_LIB", None)
    monkeypatch.setattr(host_partial, "_LIB_TRIED", True)
    b = _run(batches, _std_aggs, 500, 200, strategy="partial_merge")
    _assert_parity(a, b, rtol=1e-12)


def test_partial_merge_key_sharded_mesh(make_batch):
    """partial_merge over an 8-device mesh (G-sharded merge under
    shard_map) must match the single-device scatter path exactly in
    shape and near-exactly in values."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device platform")
    rng = np.random.default_rng(23)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(20):
        n = 768
        ts = np.sort(t0 + b * 300 + rng.integers(0, 300, n))
        # cardinality ramps past the 8-device initial capacity (1024) so
        # growth re-lays the sharded state mid-stream
        hi = 100 + b * 80
        keys = np.array(
            [f"s{i}" for i in rng.integers(0, hi, n)], dtype=object
        )
        batches.append(make_batch(ts, keys, rng.normal(50, 5, n)))
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(
        batches, _std_aggs, 1000, strategy="partial_merge",
        cfg_extra={"mesh_devices": 8},
    )
    assert len({k[1] for k in a}) > 1024  # grew past the initial capacity
    _assert_parity(a, b)


def test_partial_merge_key_sharded_sliding(make_batch):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device platform")
    batches = _sensor_batches(make_batch, n_batches=20)
    a = _run(batches, _std_aggs, 500, 200, strategy="scatter")
    b = _run(
        batches, _std_aggs, 500, 200, strategy="partial_merge",
        cfg_extra={"mesh_devices": 8},
    )
    _assert_parity(a, b)


def test_partial_checkpoint_kill_restore(make_batch, tmp_path):
    """Kill/restore through the shared protocol driver with the
    partial_merge backend: the barrier snapshot must include host-striped
    rows (flush-before-snapshot), and run B resumes to golden."""
    from test_checkpoint import _kill_restore_roundtrip

    rng = np.random.default_rng(77)
    t0 = 1_700_000_000_000
    batches = []
    for b in range(12):
        n = 200
        ts = np.sort(t0 + b * 400 + rng.integers(0, 400, n))
        keys = np.array([f"s{i}" for i in rng.integers(0, 7, n)], dtype=object)
        batches.append(make_batch(ts, keys, rng.normal(50, 5, n)))

    def make_cfg(path):
        return EngineConfig(
            checkpoint=path is not None,
            checkpoint_interval_s=9999,
            state_backend_path=path,
            device_strategy="partial_merge",
            emit_lag_ms=0,  # prompt emission: the driver commits a barrier
            # between mid-stream emissions
        )

    golden, a, b = _kill_restore_roundtrip(
        batches, make_cfg, str(tmp_path / "state_pm")
    )
    combined = dict(a)
    combined.update(b)
    assert set(combined) == set(golden)
    # stripe boundaries differ across the restore, so f32 merge order (and
    # the last rounded digit of sums) may differ — counts stay exact
    for k, (cnt, sm, av) in golden.items():
        gc, gs, ga = combined[k]
        assert gc == cnt, (k, gc, cnt)
        assert gs == pytest.approx(sm, rel=1e-5)
        assert ga == pytest.approx(av, rel=1e-5)
    assert len(b) < len(golden) or len(a) == 0


def test_partial_device_finalize_parity(make_batch):
    """On-device finalization (finals planes + active bitmask,
    segment_agg._finals_and_reset) must match the component-transfer path
    (device_finalize=False) on the same feed — including nulls, where
    per-column counts diverge from row counts."""
    for nulls in (False, True):
        batches = _sensor_batches(make_batch, nulls=nulls, seed=11)
        a = _run(
            batches, _std_aggs, 1000, strategy="partial_merge",
            cfg_extra={"device_finalize": False},
        )
        b = _run(
            batches, _std_aggs, 1000, strategy="partial_merge",
            cfg_extra={"device_finalize": True},
        )
        assert len(a) > 10
        # finals emit fl(hi+lo) in f32 — up to 1 ulp from the host's
        # f64 hi+lo add
        _assert_parity(a, b, rtol=1e-5)


@pytest.mark.parametrize("device_finalize", [True, False])
@pytest.mark.parametrize("hold", [True, False])
def test_partial_emission_chunks_and_buffers(
    make_batch, monkeypatch, device_finalize, hold
):
    """An emission is filled ``EMIT_CHUNK_GROUPS`` group ids at a time
    into columns that are taken again once the consumer has let the batch
    go — and never while it holds one: ONE batch a window, the same rows as
    a whole-window build, whether the consumer keeps every batch or none."""
    import denormalized_tpu.physical.window_exec as we

    batches = _sensor_batches(make_batch, keys=40, seed=5)
    cfg = {"device_finalize": device_finalize}
    whole = _run(batches, _std_aggs, 1000, strategy="partial_merge",
                 cfg_extra=cfg)
    monkeypatch.setattr(we, "EMIT_CHUNK_GROUPS", 8)
    ctx = Context(EngineConfig(device_strategy="partial_merge", **cfg))
    ds = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window([col("sensor_name")], _std_aggs(), 1000)
    seen, starts, held, buffers = {}, [], [], []
    for b in ds.stream():
        ws = np.asarray(b.column(WINDOW_START_COLUMN))
        assert len(np.unique(ws)) == 1
        starts.append(int(ws[0]))
        buffers.append(b.column("sm").base.ctypes.data)
        if hold:
            held.append(b)
            continue
        for i in range(b.num_rows):
            key = (int(ws[i]), b.column("sensor_name")[i])
            seen[key] = {n: b.column(n)[i] for n in whole[key]}
        del b, ws
    for b in held:
        ws = np.asarray(b.column(WINDOW_START_COLUMN))
        for i in range(b.num_rows):
            key = (int(ws[i]), b.column("sensor_name")[i])
            seen[key] = {n: b.column(n)[i] for n in whole[key]}
    assert starts == sorted(set(starts)) and len(starts) > 3
    _assert_parity(whole, seen, rtol=0)
    # held batches never share a buffer; let go, two buffers alternate
    assert (len(set(buffers)) == len(buffers)) == hold
    assert hold or len(set(buffers)) <= 3


def test_partial_device_finalize_sharded(make_batch):
    """Finals emission over the 8-device mesh (borrowed single-device
    machinery, GSPMD-partitioned) matches scatter."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device platform")
    batches = _sensor_batches(make_batch, n_batches=20)
    a = _run(batches, _std_aggs, 1000, strategy="scatter")
    b = _run(
        batches, _std_aggs, 1000, strategy="partial_merge",
        cfg_extra={"mesh_devices": 8, "device_finalize": True},
    )
    _assert_parity(a, b, rtol=1e-5)


def test_partial_dense_upload_layout(make_batch):
    """High-density stripes take the index-free dense pack (fewer bytes
    than compact incl. the index row) and still match scatter; the layout
    decision is exercised both ways by spying take_packed."""
    from denormalized_tpu.ops.host_partial import HostPartialStripe

    layouts = []
    orig = HostPartialStripe.take_packed

    def spy(self, *args):
        packs = orig(self, *args)
        layouts.extend(dense for _packed, _a_pad, _lean, dense in packs)
        return packs

    HostPartialStripe.take_packed = spy
    try:
        batches = _sensor_batches(make_batch, keys=10)
        a = _run(batches, _std_aggs, 1000, strategy="scatter")
        b = _run(batches, _std_aggs, 1000, strategy="partial_merge")
    finally:
        HostPartialStripe.take_packed = orig
    # small G (128): a unit's 128 cells are fewer than the smallest
    # compact bucket (1024) — every unit should have gone dense
    assert layouts and all(layouts), layouts
    _assert_parity(a, b)


def test_partial_compact_upload_layout(make_batch):
    """Sparse stripes (few active cells in a grown ring) keep the compact
    indexed pack."""
    from denormalized_tpu.ops.host_partial import HostPartialStripe

    layouts = []
    orig = HostPartialStripe.take_packed

    def spy(self, *args):
        packs = orig(self, *args)
        layouts.extend(dense for _packed, _a_pad, _lean, dense in packs)
        return packs

    HostPartialStripe.take_packed = spy
    try:
        batches = _sensor_batches(make_batch, keys=5, n_batches=12)
        b = _run(
            batches, _std_aggs, 1000, strategy="partial_merge",
            cfg_extra={"min_group_capacity": 16384},
        )
        a = _run(batches, _std_aggs, 1000, strategy="scatter")
    finally:
        HostPartialStripe.take_packed = orig
    # G=16384: a dense unit is 16384 cells a plane, the ~5 active cells
    # fit the 1024 bucket — compact must win every unit
    assert layouts and not any(layouts), layouts
    _assert_parity(a, b)


def test_auto_strategy_never_row_ships_on_tpu(monkeypatch):
    """Round-3 VERDICT weak-7: 'auto' must PROVABLY never pick the
    row-shipping strategies on a narrow-link TPU backend.  With the
    backend reporting tpu, auto resolves to host edge-reduction
    (PartialMergeWindowState) whose strategy_name labels the bench."""
    import denormalized_tpu.parallel.sharded_state as ss
    from denormalized_tpu.ops import segment_agg as sa

    # the backend reports tpu for routing AND construction — the
    # prewarm ladders compile against the CPU platform here, which is
    # exactly what a restored-on-CPU state would do; the routing
    # decision is what this test pins
    monkeypatch.setattr(ss.jax, "default_backend", lambda: "tpu")
    spec = sa.WindowKernelSpec(
        components=tuple(sa.components_for([("count", 0)])),
        num_value_cols=1,
        window_slots=4,
        group_capacity=128,
        length_ms=1000,
        slide_ms=1000,
    )
    backend = ss.make_sharded_state(spec, None, "auto", "auto")
    assert isinstance(backend, ss.PartialMergeWindowState)
    assert backend.strategy_name == "partial_merge"


def test_auto_strategy_on_cpu_partial_merge_except_f64(monkeypatch):
    """'auto' on CPU picks host edge-reduction too (the native reducer
    beats XLA scatter adds), EXCEPT for f64 accumulators: the stripe's
    f32 hi/lo transport refuses finite f64 sums beyond f32 range
    (ops/host_partial.py), while CPU XLA scatter keeps f64 end-to-end —
    routing must not turn a working default-config f64 workload into a
    runtime OverflowError."""
    import jax.numpy as jnp

    import denormalized_tpu.parallel.sharded_state as ss
    from denormalized_tpu.ops import segment_agg as sa

    def spec_for(dtype):
        return sa.WindowKernelSpec(
            components=tuple(sa.components_for([("sum", 0)])),
            num_value_cols=1,
            window_slots=4,
            group_capacity=128,
            length_ms=1000,
            slide_ms=1000,
            accum_dtype=dtype,
        )

    monkeypatch.setattr(ss.jax, "default_backend", lambda: "cpu")
    assert isinstance(
        ss.make_sharded_state(spec_for(jnp.float32), None, "auto", "auto"),
        ss.PartialMergeWindowState,
    )
    f64 = ss.make_sharded_state(spec_for(jnp.float64), None, "auto", "auto")
    assert isinstance(f64, ss.SingleDeviceWindowState)
    assert "scatter" in f64.strategy_name
    # explicit partial_merge is still honored (the transport raises its
    # own actionable OverflowError only if an out-of-range sum occurs)
    assert isinstance(
        ss.make_sharded_state(spec_for(jnp.float64), None, "auto",
                              "partial_merge"),
        ss.PartialMergeWindowState,
    )


@pytest.mark.parametrize("backend", ["tpu", "cpu", "gpu"])
def test_auto_strategy_on_a_1d_mesh_follows_the_one_device_rule(
    monkeypatch, backend
):
    """On a 1-D mesh 'auto' / 'auto' is the one-device rule: host
    edge-reduction on every TPU and CPU backend (each device folding its
    own key block's share of the stripe), f64 on the CPU excepted — decided
    on four v5e chips at 40M groups (PERF.md section 6, PR 31), where the
    row-shipping key_sharded layout that 'auto' used to pick was held
    against it.  Row shipping stays reachable by name."""
    import jax
    import jax.numpy as jnp

    import denormalized_tpu.parallel.sharded_state as ss
    from denormalized_tpu.ops import segment_agg as sa
    from denormalized_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    monkeypatch.setattr(ss.jax, "default_backend", lambda: backend)
    # routing is what this test pins: keep the TPU's prewarm ladders out
    monkeypatch.setattr(ss, "_prewarm", lambda: False)
    mesh = make_mesh(4)

    def make(strategy, device_strategy, dtype=jnp.float32, G=8192):
        spec = sa.WindowKernelSpec(
            components=tuple(sa.components_for([("sum", 0)])),
            num_value_cols=1, window_slots=4, group_capacity=G,
            length_ms=1000, slide_ms=1000, accum_dtype=dtype,
        )
        return ss.make_sharded_state(spec, mesh, strategy, device_strategy)

    auto = make("auto", "auto")
    if backend == "gpu":  # neither measured nor covered: rows are shipped
        assert type(auto) is ss.KeyShardedWindowState
    else:
        assert type(auto) is ss.KeyShardedPartialMergeWindowState
        assert auto.strategy_name == "partial_merge/key_sharded"
        assert auto.key_blocks == 4
    if backend == "cpu":
        assert type(make("auto", "auto", jnp.float64)) is (
            ss.KeyShardedWindowState)
    # by name: a shard strategy, or row shipping, is taken as asked
    assert type(make("key_sharded", "auto")) is ss.KeyShardedWindowState
    assert type(make("partial_final", "auto")) is ss.PartialFinalWindowState
    assert type(make("auto", "scatter")) is ss.KeyShardedWindowState
    assert type(make("auto", "scatter", G=4096)) is ss.PartialFinalWindowState
    assert type(make("auto", "partial_merge")) is (
        ss.KeyShardedPartialMergeWindowState)


@pytest.mark.parametrize(
    "backend,expected_lag_s",
    [("cpu", 0.0), ("tpu", 0.2), ("gpu", 0.2)],
)
def test_emit_lag_backend_default(monkeypatch, make_batch, backend,
                                  expected_lag_s):
    """emit_lag_ms=None resolves per backend: 0 only on CPU (merges are
    memcpy-cheap and deferral would hold a paused stream's output); every
    accelerator — including GPU, which the routing measurements don't
    cover — keeps the 200ms round-trip amortization."""
    import denormalized_tpu.physical.window_exec as we

    monkeypatch.setattr(we.jax, "default_backend", lambda: backend)

    from denormalized_tpu import Context, col
    from denormalized_tpu.api import functions as F
    from denormalized_tpu.logical import plan as lp
    from denormalized_tpu.physical.simple_execs import CollectSink
    from denormalized_tpu.runtime.executor import build_physical
    from denormalized_tpu.sources.memory import MemorySource

    t0 = 1_700_000_000_000
    ctx = Context()
    ds = ctx.from_source(
        MemorySource.from_batches(
            [make_batch([t0], ["a"], [1.0])],
            timestamp_column="occurred_at_ms",
        )
    ).window(["sensor_name"], [F.count(col("reading")).alias("c")], 1000)
    root = build_physical(lp.Sink(ds._plan, CollectSink()), ctx)
    op, found = root, None
    while op is not None:
        if isinstance(op, we.StreamingWindowExec):
            found = op
            break
        op = getattr(op, "input_op", None)
    assert found is not None
    assert found._emit_lag_s == expected_lag_s
