"""A post-aggregation filter straight over the ring's window operator is
applied where the window is emitted, before a row's key string and columns
are built (``StreamingWindowExec.set_emission_predicate``, handed down by
the planner's rule for ``lp.Filter``).  Held against a plain ``FilterExec``
over the unfiltered output of the same seeded stream: the same batches —
rows, order, dtypes, one batch a window — on every emission path; a
predicate the operator cannot take keeps a working ``FilterExec``; and the
plan's node ids, the addresses of checkpointed state, do not move."""

import warnings

import jax
import numpy as np
import pytest

from denormalized_tpu import Context, col, lit
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.api.udaf import Accumulator
from denormalized_tpu.common.constants import (
    CANONICAL_TIMESTAMP_COLUMN,
    WINDOW_END_COLUMN,
    WINDOW_START_COLUMN,
)
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.logical.plan import WindowType
from denormalized_tpu.physical.base import (
    EOS,
    WM_ANNOUNCE,
    ExecOperator,
    WatermarkHint,
)
from denormalized_tpu.physical.simple_execs import FilterExec
from denormalized_tpu.physical.window_exec import StreamingWindowExec
from denormalized_tpu.runtime.executor import build_physical
from denormalized_tpu.sources.memory import MemorySource
from denormalized_tpu.state import tiering
from denormalized_tpu.state.checkpoint import assign_node_ids, walk
from denormalized_tpu.state.lsm import LsmStore

T0 = 1_700_000_000_000
KEYS, BATCH_MS, BATCH_ROWS, BATCHES = 300, 250, 160, 40  # 10 s of event time
SPAN_MS = BATCHES * BATCH_MS
THRESHOLD = 45.1  # not a float32: float32(45.1) lies below it
# the float32 values around the threshold, a float32 ulp apart: as float64
# the first two lie below 45.1 and the third above; compared in float32 the
# second would EQUAL the threshold
ULP_AT = np.float32(THRESHOLD)
ULP = {
    "ulp_lo": float(np.nextafter(ULP_AT, np.float32(0))),
    "ulp_at": float(ULP_AT),
    "ulp_hi": float(np.nextafter(ULP_AT, np.float32(100))),
}

PREDICATES = {
    "avg_gt": lambda: col("avg") > THRESHOLD,
    "avg_ge": lambda: col("avg") >= THRESHOLD,
    "cnt_ge": lambda: col("cnt") >= 2,
    "both": lambda: (col("avg") > THRESHOLD) & (col("cnt") >= 2),
    # the first windows fail whole, the later ones pass whole
    "window_start": lambda: col(WINDOW_START_COLUMN) >= T0 + SPAN_MS // 2,
    "none_pass": lambda: col("avg") > 1e9,
}
WINDOWS = {"tumbling": (1000, None), "sliding5": (1000, 200)}


def _feed(make_batch):
    """One partition in order; the keys' means straddle the threshold, and
    three keys report once each, a float32 ulp apart around it."""
    rng = np.random.default_rng(34)
    batches = []
    for i in range(BATCHES):
        ts = np.sort(T0 + i * BATCH_MS + rng.integers(0, BATCH_MS, BATCH_ROWS))
        kid = rng.integers(0, KEYS, BATCH_ROWS)
        names = [f"key_{k:04d}" for k in kid]
        x = 40.0 + 2.0 * (kid % 10) + rng.standard_normal(BATCH_ROWS) * 10
        if i == BATCHES // 2:
            ts = np.concatenate([ts, ts[-1:].repeat(len(ULP))])
            names += list(ULP)
            x = np.concatenate([x, list(ULP.values())])
        batches.append(make_batch(ts, np.array(names, dtype=object), x))
    return batches


def _aggs():
    return [
        F.count(col("reading")).alias("cnt"),
        F.avg(col("reading")).alias("avg"),
    ]


def _find(root, cls):
    return next(op for op in walk(root) if isinstance(op, cls))


def _run(batches, config, window, predicate):
    ctx = Context(config)
    ds = ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(["sensor_name"], _aggs(), *window)
    if predicate is not None:
        ds = ds.filter(predicate)
    out = list(ds.stream())
    return out, ctx._last_physical


class _Replay(ExecOperator):
    """Stands in for an operator's input: yields the given items."""

    def __init__(self, schema, items):
        self.schema = schema
        self.items = items

    def run(self):
        yield from self.items


def _plain_filter(batches, predicate):
    """What a ``FilterExec`` makes of ``batches``."""
    if not batches:
        return []
    return list(FilterExec(_Replay(batches[0].schema, batches), predicate).run())


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.schema == w.schema
        assert g.num_rows == w.num_rows
        for f, a, b in zip(g.schema, g.columns, w.columns):
            assert a.dtype == b.dtype, f.name
            assert np.array_equal(a, b), f.name
    # one batch a window, windows ascending
    starts = [np.unique(b.column(WINDOW_START_COLUMN)) for b in got]
    assert all(len(s) == 1 for s in starts)
    assert (np.diff([int(s[0]) for s in starts]) > 0).all()


_unfiltered: dict = {}


def _unfiltered_run(make_batch, window, finalize, devices):
    """The unfiltered output of one configuration, run once a module."""
    key = (window, finalize, devices)
    if key not in _unfiltered:
        out, root = _run(
            _feed(make_batch),
            EngineConfig(device_finalize=finalize, mesh_devices=devices),
            WINDOWS[window], None,
        )
        _unfiltered[key] = out, _find(root, StreamingWindowExec).metrics()
    return _unfiltered[key]


@pytest.mark.parametrize("predicate", list(PREDICATES))
@pytest.mark.parametrize("devices", [None, 4], ids=["one_device", "mesh_of_4"])
@pytest.mark.parametrize(
    "finalize", [True, False], ids=["device_finals", "host_finalize"]
)
@pytest.mark.parametrize("window", list(WINDOWS))
def test_absorbed_predicate_delivers_what_a_filter_exec_would(
    make_batch, window, finalize, devices, predicate
):
    if devices and len(jax.devices()) < devices:
        pytest.skip("needs four virtual devices")
    pred = PREDICATES[predicate]()
    plain, plain_m = _unfiltered_run(make_batch, window, finalize, devices)
    got, root = _run(
        _feed(make_batch),
        EngineConfig(device_finalize=finalize, mesh_devices=devices),
        WINDOWS[window], pred,
    )
    flt, op = root, _find(root, StreamingWindowExec)
    assert isinstance(flt, FilterExec) and flt.absorbed
    assert flt.input_op is op
    assert (op._finals_specs is not None) == finalize
    want = _plain_filter(plain, pred)
    _assert_same_batches(got, want)

    m = op.metrics()
    # emit_rows keeps its meaning: the rows the closed windows held
    assert m["emit_rows"] == plain_m["emit_rows"]
    assert m["emit_rows"] == sum(b.num_rows for b in plain)
    assert m["emit_rows"] - m["emit_rows_filtered"] == sum(
        b.num_rows for b in got
    )
    # a window whose every row fails gives no batch and is emitted all the same
    assert m["windows_emitted"] == plain_m["windows_emitted"] == len(plain)
    assert m["mesh_devices"] == (devices or 1)
    if predicate == "none_pass":
        assert got == [] and m["emit_rows_filtered"] == m["emit_rows"] > 0
        return
    assert 0 < m["emit_rows_filtered"] < m["emit_rows"]
    assert len(got) < len(plain) or predicate != "window_start"
    # the end-of-stream flush obeys it too: the newest windows end after
    # the last row, so no watermark closed them
    ends = [int(b.column(WINDOW_END_COLUMN)[0]) for b in got]
    assert max(ends) >= T0 + SPAN_MS
    if predicate in ("avg_gt", "avg_ge"):
        # compared as float64, where the float32 at the threshold lies
        # below it: of the three single-reading keys only the upper passes
        names = np.concatenate([b.column("sensor_name") for b in got])
        assert [k for k in ULP if k in set(names)] == ["ulp_hi"]
        avgs = np.concatenate([b.column("avg") for b in got])
        assert set(avgs[names == "ulp_hi"].tolist()) == {ULP["ulp_hi"]}


@pytest.mark.parametrize(
    "finalize", [True, False], ids=["device_finals", "host_finalize"]
)
def test_a_predicate_of_arithmetic_over_extrema_and_a_deviation(
    make_batch, finalize
):
    """Positions that hold no row finalize to NaN and infinities: the
    predicate's arithmetic over them neither warns nor lets one out, and a
    single-reading key's NaN deviation fails as it would in a
    ``FilterExec``."""

    def run(pred):
        ctx = Context(EngineConfig(device_finalize=finalize))
        ds = ctx.from_source(MemorySource.from_batches(
            _feed(make_batch), timestamp_column="occurred_at_ms"
        )).window(
            ["sensor_name"],
            [F.min(col("reading")).alias("lo"), F.max(col("reading")).alias("hi"),
             F.stddev(col("reading")).alias("sd"), F.count(col("reading")).alias("cnt")],
            1000, 200,
        )
        out = list((ds if pred is None else ds.filter(pred)).stream())
        return out, ctx._last_physical

    pred = (col("hi") - col("lo") > 12.0) & (col("sd") * 2 > 9.0) & (
        lit(1.0) / col("cnt") < 0.6
    )
    plain, _ = run(None)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got, root = run(pred)
    assert root.absorbed
    want = _plain_filter(plain, pred)
    assert 0 < sum(b.num_rows for b in want) < sum(b.num_rows for b in plain)
    _assert_same_batches(got, want)


# -- the cold tier's due windows, through the same funnel -------------------


def _cold_items():
    """A watermark six seconds behind the feed: a span of open windows the
    budget sends to the cold tier, emitted from their stored planes."""
    schema = Schema([
        Field(CANONICAL_TIMESTAMP_COLUMN, DataType.TIMESTAMP_MS,
              nullable=False),
        Field("k", DataType.STRING, nullable=False),
        Field("v", DataType.FLOAT64),
    ])
    rng = np.random.default_rng(4)
    items = [WatermarkHint(WM_ANNOUNCE, kind="partition")]
    for b in range(20):
        base = T0 + b * 500
        ts = np.sort(base + rng.integers(0, 500, 100))
        kid = rng.integers(0, 50, 100)
        ks = np.asarray([f"k{i}" for i in kid], object)
        v = 40.0 + 2.0 * (kid % 10) + rng.standard_normal(100) * 10
        items.append(RecordBatch(schema, [ts, ks, v]))
        items.append(WatermarkHint(max(T0, base - 6000), kind="partition"))
    items += [WatermarkHint(T0 + 30_000, kind="partition"), EOS]
    return schema, items


def _cold_op(schema, items):
    return StreamingWindowExec(
        _Replay(schema, items), [col("k")],
        [F.count(col("v")).alias("cnt"), F.avg(col("v")).alias("avg")],
        WindowType.TUMBLING, 1000, None,
        # spilled windows leave through the host finalize
        device_finalize=False,
    )


def test_cold_tier_windows_obey_the_emission_predicate(tmp_path):
    schema, items = _cold_items()
    pred = (col("avg") > THRESHOLD) & (col("cnt") >= 2)
    plain = [
        b for b in _cold_op(schema, items).run() if isinstance(b, RecordBatch)
    ]
    store = LsmStore(str(tmp_path / "lsm"))
    try:
        ctrl = tiering.SpillController(store, budget_bytes=20_000)
        op = _cold_op(schema, items)
        op.set_emission_predicate(pred)
        op.enable_spill("0_win", ctrl)
        got = [b for b in op.run() if isinstance(b, RecordBatch)]
        stats = ctrl.spill_stats("0_win")
        ctrl.close()
    finally:
        store.close()
    assert stats["spill_blocks_total"] > 0
    _assert_same_batches(got, _plain_filter(plain, pred))
    m = op.metrics()
    assert m["emit_rows"] == sum(b.num_rows for b in plain)
    assert 0 < m["emit_rows_filtered"] < m["emit_rows"]
    assert m["windows_emitted"] == len(plain)


# -- what is not absorbed, and the addresses --------------------------------


class _Spread(Accumulator):
    def __init__(self):
        self.lo, self.hi = float("inf"), float("-inf")

    def update(self, values):
        if len(values):
            self.lo = min(self.lo, float(values.min()))
            self.hi = max(self.hi, float(values.max()))

    def merge(self, states):
        self.lo, self.hi = min(self.lo, states[0]), max(self.hi, states[1])

    def state(self):
        return [self.lo, self.hi]

    def evaluate(self):
        return self.hi - self.lo if self.hi >= self.lo else 0.0


def _ring(ds):
    return ds.window(["sensor_name"], _aggs(), 1000, 200)


_above = F.udf(lambda a: a > THRESHOLD, DataType.BOOL, "above")

#: (how the window is made, the engine's options, the predicate, the
#: operator the window plans to)
NOT_ABSORBED = {
    "names_the_key": (
        _ring, {}, lambda: (col("sensor_name") != "key_0001")
        & (col("avg") > THRESHOLD),
        "StreamingWindowExec",
    ),
    "is_null": (
        _ring, {}, lambda: ~col("avg").is_null() & (col("avg") > THRESHOLD),
        "StreamingWindowExec",
    ),
    "udf": (_ring, {}, lambda: _above(col("avg")), "StreamingWindowExec"),
    "zero_argument_function": (
        _ring, {}, lambda: (col("avg") > THRESHOLD) & (F.random() >= 0.0),
        "StreamingWindowExec",
    ),
    "session": (
        lambda ds: ds.session_window(["sensor_name"], _aggs(), 300), {},
        lambda: col("avg") > THRESHOLD, "SessionWindowExec",
    ),
    "udaf": (
        lambda ds: ds.window(
            ["sensor_name"],
            _aggs() + [F.udaf(_Spread, DataType.FLOAT64, "spread")("reading")],
            1000, 200,
        ),
        {}, lambda: col("avg") > THRESHOLD, "UdafWindowExec",
    ),
    "slice_windows": (
        _ring, {"slice_windows": True}, lambda: col("avg") > THRESHOLD,
        "SliceWindowExec",
    ),
}


@pytest.mark.parametrize("case", list(NOT_ABSORBED))
def test_an_ineligible_predicate_keeps_a_working_filter_exec(make_batch, case):
    window, options, predicate, planned = NOT_ABSORBED[case]
    pred = predicate()

    def run(filtered):
        ctx = Context(EngineConfig(**options))
        ds = window(ctx.from_source(MemorySource.from_batches(
            _feed(make_batch), timestamp_column="occurred_at_ms"
        )))
        out = list((ds.filter(pred) if filtered else ds).stream())
        return out, ctx._last_physical

    plain, _ = run(False)
    got, root = run(True)
    assert isinstance(root, FilterExec) and not root.absorbed
    op = root.input_op
    assert type(op).__name__ == planned
    assert "emit_filter" not in root.display()
    assert (op.metrics() or {}).get("emit_rows_filtered", 0) == 0
    want = _plain_filter(plain, pred)
    assert 0 < sum(b.num_rows for b in want) < sum(b.num_rows for b in plain)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g.columns, w.columns):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_node_ids_do_not_move_and_explain_shows_the_predicate(
    make_batch, capsys
):
    def plan(predicate, **options):
        ctx = Context(EngineConfig(**options))
        ds = _ring(ctx.from_source(MemorySource.from_batches(
            _feed(make_batch)[:1], timestamp_column="occurred_at_ms"
        ))).filter(predicate)
        return build_physical(ds._plan, ctx), ds

    absorbed, ds = plan(col("avg") > THRESHOLD)
    kept, _ = plan(_above(col("avg")))
    unoptimized, _ = plan(col("avg") > THRESHOLD, optimizer=False)
    assert absorbed.absorbed and not kept.absorbed
    # a physical rule: the optimizer's switch is not consulted
    assert unoptimized.absorbed

    def ids(root):
        by_op = assign_node_ids(root)
        return [by_op[id(op)] for op in walk(root)]

    assert ids(absorbed) == ids(kept)
    assert ids(absorbed)[:2] == ["0_FilterExec", "1_StreamingWindowExec"]

    shown = absorbed.display().splitlines()
    assert shown[0].startswith("FilterExec(") and "at emission" in shown[0]
    assert "emit_filter=(col('avg') > lit(45.1))" in shown[1]
    assert "emit_filter" not in kept.display()
    ds.explain()
    assert "emit_filter=(col('avg')" in capsys.readouterr().out
