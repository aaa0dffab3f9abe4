"""``window.project``'s native passes against the NumPy body: every output
of :class:`WindowProjector` — slide units, remainders, the batch's
extremes, ``win_rel``, the late / dropped counts, the straddle flag and the
``keep`` mask — bit for bit, so the operator cannot tell which one ran; and
the same seeded stream through ``StreamingWindowExec`` with the library and
with it withheld."""

import itertools

import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.ops import window_project as wp
from denormalized_tpu.ops.window_project import WindowProjector
from denormalized_tpu.physical.window_exec import StreamingWindowExec
from denormalized_tpu.runtime.tracing import collect_metrics
from denormalized_tpu.sources.memory import MemorySource


@pytest.fixture(autouse=True, scope="module")
def _native_library():
    # asked inside a fixture: collection builds nothing
    if wp._native() is None:
        pytest.skip("no native pass: nothing for the NumPy body to differ from")


def _numpy_projector(*a, **kw) -> WindowProjector:
    p = WindowProjector(*a, **kw)
    p._lib = None
    return p


def _event_times(order, sign, slide_ms, n, seed):
    """``n`` event times spread over about six slide units."""
    rng = np.random.default_rng(seed)
    width = 6 * slide_ms
    start = {
        "positive": 1_700_000_000_000,
        "negative": -1_700_000_000_000,
        "mixed": -width // 2,
    }[sign]
    ts = start + rng.integers(0, width, n)
    if sign == "mixed" and n >= 2:
        ts[0], ts[-1] = -1, 0  # both sides of the epoch, whatever was drawn
    return np.sort(ts) if order == "sorted" else ts


# contiguous int64 timestamps take the native pass; a strided view and an
# int32 column must fall back, and ``native_batches`` must say so
CASES = [
    (order, sign, slide_ms, n, "int64")
    for order, sign, slide_ms, n in itertools.product(
        ("sorted", "shuffled"),
        ("positive", "negative", "mixed"),
        (1, 7, 200, 1000, 10_000),
        (1, 2, 17, 18_750),
    )
] + [
    ("sorted", "positive", 200, 18_750, "strided"),
    ("shuffled", "mixed", 7, 17, "strided"),
    ("sorted", "negative", 1000, 18_750, "int32"),
    ("shuffled", "mixed", 7, 17, "int32"),
]


@pytest.mark.parametrize(
    "order,sign,slide_ms,n,kind", CASES,
    ids=["-".join(map(str, c)) for c in CASES],
)
def test_the_native_pass_equals_the_numpy_body(order, sign, slide_ms, n, kind):
    ts = _event_times(order, sign, slide_ms, n, seed=n * 31 + slide_ms)
    if kind == "strided":
        wide = np.zeros(2 * n, np.int64)
        wide[::2] = ts
        ts = wide[::2]
        assert not ts.flags.c_contiguous or n == 1
    elif kind == "int32":
        ts = (ts % (1 << 30)).astype(np.int32) * (-1 if sign == "negative" else 1)
    falls_back = kind == "int32" or not ts.flags.c_contiguous
    # length % slide == 0 and != 0: 5 windows a unit, and 3 (2.5 rounded up)
    for length_units in (5, 3):
        native = WindowProjector(slide_ms, length_units, reuse_buffers=True)
        plain = _numpy_projector(slide_ms, length_units, reuse_buffers=False)
        got = native.units(ts)
        want = plain.units(ts)
        assert native.native_batches == (0 if falls_back else 1)
        assert plain.native_batches == 0
        units = got[0].copy()  # the buffer is the projector's, reused below
        got = (units,) + got[1:]
        units, rem, u_min, u_max, ts_min = got
        assert units.dtype == np.int64 and rem.dtype == np.int32
        for g, w in zip(got[:2], want[:2]):
            assert g.dtype == w.dtype and np.array_equal(g, w)
        assert got[2:] == want[2:]
        assert all(type(x) is int for x in got[2:])
        # the definition itself, floor semantics before the epoch included
        big = np.asarray(ts, np.int64)
        assert np.array_equal(units * slide_ms + rem, big)
        assert rem.min() >= 0 and rem.max() < slide_ms
        assert (u_min, u_max, ts_min) == (units.min(), units.max(), big.min())
        # none late / some behind the watermark's closable count, with and
        # without a row that straddles / all late / all behind
        mid = int(np.median(units))
        for first, closable in (
            (u_min - length_units + 1, 0),
            (u_min - length_units + 1, length_units - 1),
            (u_min - length_units + 1, length_units + 2),
            (mid - 1, 0),
            (mid - 1, 2),
            (u_max + 1, 0),
            (u_max - 2, 40),
        ):
            g = native.rebase(units, u_min, first, closable)
            w = plain.rebase(want[0], u_min, first, closable)
            where = (length_units, first - u_min, closable)
            assert g[0].dtype == np.int64 and np.array_equal(g[0], w[0]), where
            assert g[1:4] == w[1:4], where
            assert type(g[3]) is bool and type(w[3]) is bool
            n_late, n_behind = g[1], g[2]
            assert n_late == int((units < first).sum())
            assert n_behind == int((units - first < closable).sum())
            if n_behind == 0:
                assert g[4] is None and w[4] is None, where
            else:
                assert g[4].dtype == np.bool_
                assert np.array_equal(g[4], w[4]), where
                assert n - int(g[4].sum()) == n_behind
            # no mask wanted (a row-shipping backend): the same numbers
            for p in (native, plain):
                bare = p.rebase(units, u_min, first, closable, mask=False)
                assert np.array_equal(bare[0], w[0]), where
                assert bare[1:] == w[1:4] + (None,), where


def test_reused_buffers_hold_a_batch_until_the_next():
    proj = WindowProjector(200, 5, reuse_buffers=True)
    fresh = WindowProjector(200, 5, reuse_buffers=False)
    big = _event_times("sorted", "positive", 200, 1000, seed=1)
    small = _event_times("shuffled", "negative", 200, 10, seed=2)
    u1 = proj.units(big)[0]
    held = u1.copy()
    u2 = proj.units(small)[0]
    # the same memory, a shorter view: the first batch's units are gone
    assert np.shares_memory(u1, u2) and len(u2) == 10
    assert np.array_equal(u2, np.floor_divide(small, 200))
    f1 = fresh.units(big)[0]
    fresh.units(small)
    assert np.array_equal(f1, held)


def _stream(make_batch, t0):
    """Six seconds of a sliding job's input, 200 ms a batch.  Two batches
    in three are in step, so the global watermark (the highest least event
    time of a batch) follows the feed; the third has rows up to 1.3 s
    behind it — some behind every window still open (late on any path),
    some behind the watermark but inside a window that is not closable yet:
    under a deferred emission those straddle."""
    rng = np.random.default_rng(11)
    out = []
    for b in range(30):
        ts = t0 + b * 200 + rng.integers(0, 200, 400)
        if b % 3 == 1:
            ts[:40] -= rng.integers(200, 1_500, 40)
        out.append(
            make_batch(
                ts, [f"k{i}" for i in rng.integers(0, 30, 400)],
                rng.normal(50.0, 10.0, 400),
            )
        )
    return out


def _run(batches, **cfg):
    ctx = Context(EngineConfig(min_batch_bucket=256, **cfg))
    delivered = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(
        [col("sensor_name")],
        [
            F.count(col("reading")).alias("count"),
            F.avg(col("reading")).alias("avg"),
            F.min(col("reading")).alias("min"),
        ],
        1000, 200,
    ).collect()
    (window,) = [
        m for m in collect_metrics(ctx._last_physical).values()
        if "phase_ms_project" in m
    ]
    return delivered, window


@pytest.mark.parametrize(
    "t0,cfg",
    [
        (1_700_000_000_000,
         {"device_strategy": "partial_merge", "emit_lag_ms": 0}),
        (1_700_000_000_000,
         {"device_strategy": "partial_merge", "emit_lag_ms": 10_000}),
        (1_700_000_000_000,
         {"device_strategy": "partial_merge", "emit_lag_ms": 10_000,
          "host_pipeline": True}),
        (1_700_000_000_000, {"device_strategy": "scatter"}),
        # event times from before the epoch to after it
        (-3_000, {"device_strategy": "partial_merge", "emit_lag_ms": 10_000}),
    ],
    ids=["prompt", "deferred", "deferred-host_pipeline", "scatter",
         "deferred-across_the_epoch"],
)
def test_the_operator_delivers_the_same_with_and_without_the_library(
    make_batch, monkeypatch, t0, cfg
):
    batches = _stream(make_batch, t0)
    forced = []
    trigger = StreamingWindowExec._trigger

    def noting(self, force=False):
        forced.append(force)
        return trigger(self, force)

    monkeypatch.setattr(StreamingWindowExec, "_trigger", noting)
    with_lib, m_lib = _run(batches, **cfg)
    seen = list(forced)
    monkeypatch.setattr(wp, "_native", lambda: None)
    without, m_plain = _run(batches, **cfg)
    # the freeze-then-accumulate branch (a forced trigger before the batch
    # is folded) runs where emission is deferred, and at the same batches
    # either way
    assert any(seen) == (cfg.get("emit_lag_ms") == 10_000)
    assert forced[len(seen):] == seen
    assert m_lib["project_native_batches"] == m_lib["batches_in"] == len(batches)
    assert m_plain["project_native_batches"] == 0
    assert m_plain["batches_in"] == len(batches)
    assert m_lib["late_rows"] == m_plain["late_rows"] > 0
    assert with_lib.num_rows == without.num_rows > 0
    for name in with_lib.schema.names:
        a, b = with_lib.column(name), without.column(name)
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
