"""The state observatory's native sketch pass against the NumPy kernels:
after every batch of a seeded sequence the two watches hold the same
Space-Saving slots, total, HLL registers and sample phase, bit for bit —
so the doctor's verdicts and the join's adaptation decisions cannot tell
which one ran."""

import numpy as np
import pytest

from denormalized_tpu import Context, col, obs
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.obs import statewatch as swm
from denormalized_tpu.obs.registry import MetricsRegistry
from denormalized_tpu.obs.statewatch import (
    JOIN_SKETCH_DECAY_ROWS,
    NULL_WATCH,
    SKETCH_ROW_CAP,
    StateWatch,
)
from denormalized_tpu.runtime.tracing import collect_metrics
from denormalized_tpu.sources.memory import MemorySource

BATCHES = 50


@pytest.fixture(autouse=True, scope="module")
def _native_library():
    # asked inside a fixture: collection builds nothing
    if swm._native() is None:
        pytest.skip("no native sketch pass: nothing for the fallback to differ from")


@pytest.fixture
def registry():
    reg = MetricsRegistry(enabled=True)
    prev = obs.use_registry(reg)
    yield reg
    obs.use_registry(prev)


def _numpy_watch(**kw) -> StateWatch:
    w = StateWatch("numpy", **kw)
    w._lib = None
    return w


def _state(w: StateWatch) -> dict:
    return {
        "keys": w.sketch.keys.copy(), "counts": w.sketch.counts.copy(),
        "errs": w.sketch.errs.copy(), "total": w.sketch.total,
        "registers": w.hll.registers.copy(), "phase": w._sample_phase,
    }


def _assert_same(a: StateWatch, b: StateWatch, where) -> None:
    sa, sb = _state(a), _state(b)
    for field in sa:
        assert np.array_equal(sa[field], sb[field]), (field, where)


def _uniform(rng, keys, rows):
    return rng.integers(0, keys, rows)


def _one_key_at_30_percent(rng, keys, rows):
    g = rng.integers(0, keys, rows)
    g[rng.random(rows) < 0.3] = 7 % keys
    return g


def _clustered(rng, keys, rows):
    # runs of one key, 64 rows long: a block sample sees other keys than
    # the batch as a whole
    return np.repeat(rng.integers(0, keys, rows // 64 + 1), 64)[:rows]


DISTRIBUTIONS = {
    "uniform": _uniform,
    "one_key_at_30_percent": _one_key_at_30_percent,
    "clustered": _clustered,
}


@pytest.mark.parametrize("decay", [0, JOIN_SKETCH_DECAY_ROWS],
                         ids=["monotone", "join_decay"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64], ids=["int32", "int64"])
# unsampled; exactly at the cap; just over it; the benchmark's batch; six
# times over
@pytest.mark.parametrize(
    "rows", [1, 700, SKETCH_ROW_CAP, SKETCH_ROW_CAP + 1, 18_750, 100_000]
)
@pytest.mark.parametrize("dist", sorted(DISTRIBUTIONS))
@pytest.mark.parametrize("keys", [10, 1_000, 100_000, 10_000_000])
def test_native_pass_leaves_the_numpy_state(keys, dist, rows, dtype, decay):
    rng = np.random.default_rng([keys, rows, len(dist), decay & 1])
    native = StateWatch("native", decay_every=decay)
    plain = _numpy_watch(decay_every=decay)
    for i in range(BATCHES):
        g = DISTRIBUTIONS[dist](rng, keys, rows).astype(dtype)
        native.update(g)
        plain.update(g)
        _assert_same(native, plain, i)
    assert native.sketch_native_batches == native.update_batches == BATCHES
    assert plain.sketch_native_batches == 0
    assert plain.update_batches == BATCHES
    assert native.summary()["sketch_native_batches"] == BATCHES
    assert plain.summary()["sketch_native_batches"] == 0


@pytest.mark.parametrize("capacity", [1, 8, 9, 64, 200])
def test_other_slot_counts(capacity):
    """K is the caller's (at least 8): fewer distinct ids than slots, as
    many, and far more."""
    rng = np.random.default_rng(capacity)
    native = StateWatch("native", capacity=capacity)
    plain = _numpy_watch(capacity=capacity)
    for i, keys in enumerate([3, 8, 9, 64, 65, 5_000] * 5):
        g = rng.integers(0, keys, 2_000).astype(np.int32)
        native.update(g)
        plain.update(g)
        _assert_same(native, plain, (i, keys))
    assert native.sketch_native_batches == 30


def test_ids_at_the_edges_of_their_width():
    rng = np.random.default_rng(5)
    top32 = np.iinfo(np.int32).max
    pools = {
        np.int32: np.array([0, 1, top32 - 1, top32], dtype=np.int64),
        np.int64: np.array([0, top32, top32 + 1, 1 << 40, (1 << 62) + 3]),
    }
    for dtype, pool in pools.items():
        native, plain = StateWatch("native"), _numpy_watch()
        for i in range(20):
            g = pool[rng.integers(0, len(pool), 3_000)].astype(dtype)
            native.update(g)
            plain.update(g)
            _assert_same(native, plain, (dtype, i))
    # one watch fed both widths: a tracked key no int32 holds is simply
    # not among an int32 batch's ids
    native, plain = StateWatch("native"), _numpy_watch()
    for i in range(20):
        dtype = (np.int64, np.int32)[i % 2]
        g = pools[dtype][rng.integers(0, 4, 3_000)].astype(dtype)
        native.update(g)
        plain.update(g)
        _assert_same(native, plain, i)


def test_mixed_batch_sizes_share_one_sample_phase():
    rng = np.random.default_rng(11)
    native = StateWatch("native", decay_every=JOIN_SKETCH_DECAY_ROWS)
    plain = _numpy_watch(decay_every=JOIN_SKETCH_DECAY_ROWS)
    for i in range(120):
        rows = int(rng.choice([1, 40, 9_000, 16_385, 20_000, 70_001]))
        g = _one_key_at_30_percent(rng, 50_000, rows).astype(np.int32)
        native.update(g)
        plain.update(g)
        _assert_same(native, plain, (i, rows))


@pytest.mark.parametrize("case", ["strided", "matrix_column", "uint32", "int16", "list"])
def test_what_the_native_pass_does_not_take_goes_the_numpy_way(case):
    rng = np.random.default_rng(3)
    watch, plain = StateWatch("native"), _numpy_watch()
    native_batches = 0
    for i in range(10):
        base = rng.integers(0, 300, 4_000).astype(np.int64)
        if case == "strided":
            g = base[::2]
            assert not g.flags.c_contiguous
        elif case == "matrix_column":
            g = base.reshape(2_000, 2)[:, 1]
            assert not g.flags.c_contiguous
        elif case == "uint32":
            g = base.astype(np.uint32)
        elif case == "int16":
            g = base.astype(np.int16)
        else:
            g = base.tolist()  # becomes an int64 array: the native pass
            native_batches += 1
        watch.update(g)
        plain.update(g)
        _assert_same(watch, plain, i)
    assert watch.sketch_native_batches == native_batches
    assert watch.update_batches == 10


def test_without_the_library_every_batch_goes_the_numpy_way(
    monkeypatch, registry
):
    reference = swm.make_watch("native")
    assert reference._lib is not None and reference._scratch is not None
    monkeypatch.setattr(swm, "_native", lambda: None)
    bare = swm.make_watch("no library")
    assert bare._lib is None and bare._scratch is None
    rng = np.random.default_rng(9)
    for i in range(10):
        g = rng.integers(0, 100_000, 18_750).astype(np.int32)
        bare.update(g)
        reference.update(g)
        _assert_same(bare, reference, i)
    assert bare.sketch_native_batches == 0 and bare.update_batches == 10
    assert reference.sketch_native_batches == 10
    assert bare.summary()["sketch_native_batches"] == 0


@pytest.mark.parametrize("rows", [700, SKETCH_ROW_CAP, 18_750])
def test_a_call_leaves_the_scratch_clean(rows):
    """A watch that has folded many batches and a watch fresh from its
    constructor give one batch the same state: nothing of an earlier call
    is left in the table."""
    rng = np.random.default_rng(rows)
    used = StateWatch("used")
    for _ in range(20):
        used.update(rng.integers(0, 10_000_000, rows).astype(np.int32))
        used.update(rng.integers(0, 10, rows).astype(np.int64))
    for i in range(10):
        g = rng.integers(0, 100_000, rows).astype((np.int32, np.int64)[i % 2])
        used.reset_sketches()
        used._sample_phase = 0
        fresh = StateWatch("fresh")
        used.update(g)
        fresh.update(g)
        _assert_same(used, fresh, i)


def test_disabled_and_empty_batches_touch_nothing():
    off = StateWatch("off", enabled=False)
    off.update(np.arange(100, dtype=np.int32))
    on = StateWatch("on")
    on.update(np.empty(0, dtype=np.int32))
    for w in (off, on):
        assert w.update_batches == 0 and w.sketch_native_batches == 0
        assert w.sketch.total == 0 and not w.hll.registers.any()
    assert NULL_WATCH.summary()["sketch_native_batches"] == 0
    assert NULL_WATCH.sketch_native_batches == 0


def test_the_window_operator_reports_the_native_batches(make_batch, registry):
    rng = np.random.default_rng(2)
    batches = [
        make_batch(
            1_700_000_000_000 + 1_000 * b + np.sort(rng.integers(0, 1_000, 500)),
            [f"k{i}" for i in rng.integers(0, 40, 500)],
            rng.random(500),
        )
        for b in range(6)
    ]
    ctx = Context(EngineConfig(min_batch_bucket=256))
    ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(
        [col("sensor_name")], [F.count(col("reading")).alias("count")], 1000
    ).collect()
    (window,) = [
        m for m in collect_metrics(ctx._last_physical).values()
        if "phase_ms_statewatch" in m
    ]
    assert window["sketch_update_batches"] == window["batches_in"] > 0
    assert window["sketch_native_batches"] == window["sketch_update_batches"]
