"""A window's group keys leave the operator as dictionary codes where the key
is one string column in the interner's native table: a ``DictionaryColumn``
of group ids over the interner's key mirror
(``StreamingWindowExec._emit_window_batch``).  The column on its own; the
mirror's promise that a batch reads the same keys whenever and wherever it is
read; the operator's output held against the object lane it replaced
(``GroupInterner.key_dictionary`` withheld) on the same seeded stream; and
what the operators, sinks and codecs downstream make of it."""

import os
import sys
import threading

import numpy as np
import pytest

import test_emission_filter as ef
from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.cluster import framing
from denormalized_tpu.cluster.hashing import hash_rows
from denormalized_tpu.common.columns import (
    DictionaryColumn,
    StringColumn,
    as_numpy,
    column_from_spec,
    column_spec_and_buffers,
    concat_columns,
)
from denormalized_tpu.common.constants import WINDOW_START_COLUMN
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.formats.json_codec import JsonRowEncoder
from denormalized_tpu.ops.interner import GroupInterner
from denormalized_tpu.physical.join_exec import StreamingJoinExec
from denormalized_tpu.physical import window_exec
from denormalized_tpu.physical.window_exec import StreamingWindowExec
from denormalized_tpu.sources.memory import MemorySource
from denormalized_tpu.state import tiering
from denormalized_tpu.state.lsm import LsmStore

VALUES = ["b", "", "日本語", None, "a" * 40, "b"]


def _dict_col(idx=(5, 3, 0, 2, 2, 4, 1)):
    values = np.empty(len(VALUES), dtype=object)
    values[:] = VALUES
    return DictionaryColumn(np.asarray(idx, dtype=np.int32), values)


def _objects(vals):
    out = np.empty(len(vals), dtype=object)
    out[:] = vals
    return out


# -- the column -------------------------------------------------------------


SURFACE = {
    "as_object": lambda c: c.as_object(),
    "tolist": lambda c: c.tolist(),
    "__array__": lambda c: np.asarray(c),
    "as_numpy": lambda c: as_numpy(c),
    "get_one": lambda c: [c[i] for i in range(len(c))] + [c[-1]],
    "take": lambda c: c.take(np.array([6, 0, 3])),
    "slice": lambda c: c[2:5],
    "step_slice": lambda c: c[::2],
    "bool_mask": lambda c: c[np.array([1, 0, 1, 1, 0, 0, 1], dtype=bool)],
    "fancy": lambda c: c[np.array([4, 4, 1])],
    "iter": lambda c: list(c),
}


@pytest.mark.parametrize("op", list(SURFACE))
def test_the_column_reads_as_its_values(op):
    c = _dict_col()
    want = _objects([VALUES[i] for i in c.indices.tolist()])
    got = SURFACE[op](c)
    ref = want if op == "as_object" else SURFACE[op](want)
    assert list(got) == list(ref)
    if op in ("take", "slice", "step_slice", "bool_mask", "fancy"):
        # a take, slice or mask stays on the codes, over the same values
        assert isinstance(got, DictionaryColumn) and got.values is c.values
        assert got.indices.dtype == np.int32


def test_the_column_is_an_object_column_of_its_codes_bytes():
    c = _dict_col()
    assert c.dtype == np.dtype(object) and c.shape == (7,) and len(c) == 7
    assert c.nbytes == c.indices.nbytes == 28
    # materialized once, then cached
    assert c.as_object() is c.as_object()
    assert np.asarray(c, dtype=object) is c.as_object()
    assert c[1] is None and c[3] == "日本語" and c[-1] == ""
    with pytest.raises(IndexError):
        c[7]


@pytest.mark.parametrize(
    "other",
    ["string_column", "object_array", "dictionary_column"],
)
def test_concat_materializes_beside_any_other_column(other):
    c = _dict_col()
    tail = ["x", None, "b"]
    second = {
        "string_column": StringColumn.from_objects(_objects(tail)),
        "object_array": _objects(tail),
        "dictionary_column": DictionaryColumn(
            np.array([3, 5], np.int32), c.values
        ),
    }[other]
    got = concat_columns([c, second])
    assert isinstance(got, np.ndarray) and got.dtype == object
    assert got.tolist() == c.tolist() + list(np.asarray(second).tolist())


@pytest.mark.parametrize("idx", [(5, 3, 0, 2, 2, 4, 1), (0, 1), ()])
def test_spec_and_buffers_are_those_of_the_string_column(idx):
    c = _dict_col(idx)
    spec, bufs = column_spec_and_buffers(c)
    want_spec, want_bufs = column_spec_and_buffers(
        StringColumn.from_objects(c.as_object())
    )
    assert spec == want_spec
    assert len(bufs) == len(want_bufs)
    for a, b in zip(bufs, want_bufs):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    back = column_from_spec(spec, iter(bufs))
    assert isinstance(back, StringColumn) and back.tolist() == c.tolist()


def test_hashes_equal_the_object_lane():
    c = _dict_col()
    nums = np.arange(len(c), dtype=np.int64)
    assert np.array_equal(
        hash_rows([c, nums]), hash_rows([c.as_object(), nums])
    )
    assert np.array_equal(hash_rows([c]), hash_rows([c.tolist()]))


# -- the mirror: the same keys whenever and wherever a batch is read ---------


def _intern(interner, start, n):
    keys = _objects([f"key_{i:07d}" for i in range(start, start + n)])
    return interner.intern([StringColumn.from_objects(keys)])


def _more_interning(g, column):
    _intern(g, 100, 5)
    g.key_dictionary()


def _mirror_doubling(g, column):
    old = g._col_interners[0]._values_arr
    _intern(g, 100, 5000)
    g.key_dictionary()
    assert g._col_interners[0]._values_arr is not old
    assert column.values.base is old


def _checkpoint_restore(g, column):
    restored = GroupInterner.restore(g.snapshot())
    _intern(restored, 100, 3000)
    # the restored interner numbers the same keys alike
    assert restored.key_dictionary()[:100].tolist() == column.values.tolist()


def _other_threads(g, column):
    """More readers than cores materialize the column (a fresh one each
    time: the cache would hide the gather) while this thread interns and
    syncs the mirror through several doublings, switching every µs."""
    want = column.tolist()
    bad = []

    def read():
        for _ in range(200):
            if DictionaryColumn(column.indices, column.values).tolist() != want:
                bad.append(1)

    readers = [
        threading.Thread(target=read) for _ in range((os.cpu_count() or 1) + 2)
    ]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in readers:
            t.start()
        for start in range(100, 20_100, 500):
            _intern(g, start, 500)
            g.key_dictionary()
    finally:
        sys.setswitchinterval(switch)
        for t in readers:
            t.join(60)
    assert not any(t.is_alive() for t in readers) and bad == []


AFTER = {
    "more_interning": _more_interning,
    "mirror_doubling": _mirror_doubling,
    "checkpoint_restore": _checkpoint_restore,
    "other_threads": _other_threads,
}


@pytest.mark.parametrize("after", list(AFTER))
def test_an_emitted_column_keeps_its_keys(after):
    g = GroupInterner(1)
    _intern(g, 0, 100)
    values = g.key_dictionary()
    assert values is not None and len(values) == 100
    column = DictionaryColumn(np.array([99, 0, 42, 42], np.int32), values)
    AFTER[after](g, column)
    assert column.tolist() == [
        "key_0000099", "key_0000000", "key_0000042", "key_0000042"
    ]
    # what the interner hands out now still agrees on the old ids
    assert g.key_dictionary()[:100].tolist() == values.tolist()


def test_the_pool_hands_out_a_key_buffer_of_the_dtype_asked_for():
    pool = window_exec._EmissionColumns([np.dtype(object), np.dtype(np.int64)])
    kinds = [np.dtype(object), np.dtype(np.int32), np.dtype(np.int32),
             np.dtype(object)]
    for first in kinds:
        cols = pool.take(10, None if first == object else first)
        assert [c.dtype for c in cols] == [first, np.dtype(np.int64)]
        del cols
    # one set a dtype, each handed out again once let go
    assert sorted(str(s[0].dtype) for s in pool._sets) == ["int32", "object"]


def test_no_dictionary_for_numbers_or_several_columns():
    numeric = GroupInterner(1)
    numeric.intern([np.array([3, 1, 3])])
    assert numeric.key_dictionary() is None
    pair = GroupInterner(2)
    pair.intern([_objects(["a", "b"]), _objects(["c", "d"])])
    assert pair.key_dictionary() is None
    assert GroupInterner(1).key_dictionary() is None  # nothing interned yet


# -- the operator's output, held against the object lane ---------------------


@pytest.fixture
def object_lane(monkeypatch):
    """Withholds the dictionary: the window gathers its keys as before."""

    def withhold():
        monkeypatch.setattr(GroupInterner, "key_dictionary", lambda self: None)

    return withhold


def _as_strings(make_batch):
    """``make_batch`` with the key as a ``StringColumn``, as the Kafka
    decoders hand it over."""

    def make(ts, names, readings):
        b = make_batch(ts, names, readings)
        cols = list(b.columns)
        cols[1] = StringColumn.from_objects(np.asarray(names, dtype=object))
        return RecordBatch(b.schema, cols)

    return make


NULL_NAMES = ("key_0007", "key_0100", "key_0233")


def _with_nulls(make_batch):
    """``make_batch`` with three keys' rows reporting a null key: one group
    of null keys, next to the others, in every window."""

    def make(ts, names, readings):
        names = np.asarray(names, dtype=object).copy()
        names[np.isin(names, NULL_NAMES)] = None
        return make_batch(ts, names, readings)

    return make


KEY_FEEDS = {
    "object_keys": lambda mb: mb,
    "string_column_keys": _as_strings,
    "object_keys_with_nulls": _with_nulls,
    "string_column_keys_with_nulls": lambda mb: _with_nulls(_as_strings(mb)),
}


def _window_op(root):
    return ef._find(root, StreamingWindowExec)


def _assert_same_output(got, want, dict_lane=True):
    """The same batches as the object lane: rows, order, values, dtypes;
    the key column a ``DictionaryColumn`` on the dictionary lane."""
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.schema == w.schema and g.num_rows == w.num_rows
        key = g.columns[0]
        assert isinstance(key, DictionaryColumn) == dict_lane
        assert isinstance(w.columns[0], np.ndarray)
        for a, b in zip(g.columns, w.columns):
            assert a.dtype == b.dtype
            assert as_numpy(a).tolist() == b.tolist()
        for a, b in zip(g.materialized().columns, w.materialized().columns):
            assert isinstance(a, np.ndarray) and a.dtype == b.dtype
            assert a.tolist() == b.tolist()
        assert g.to_pydict() == w.to_pydict()
        assert g.to_pyarrow().equals(w.to_pyarrow())
        # a null key reads as null, as the object lane's None did
        name = g.schema.names[0]
        for e in (col(name).is_null(), col(name).is_not_null()):
            assert e.eval(g).tolist() == e.eval(w).tolist()


@pytest.mark.parametrize("chunk", [None, 64], ids=["one_chunk", "chunks_of_64"])
@pytest.mark.parametrize("keys", list(KEY_FEEDS))
@pytest.mark.parametrize("predicate", [None, "avg_gt", "none_pass"])
@pytest.mark.parametrize(
    "finalize", [True, False], ids=["device_finals", "host_finalize"]
)
@pytest.mark.parametrize("window", list(ef.WINDOWS))
def test_dictionary_lane_emits_what_the_object_lane_did(
    make_batch, object_lane, monkeypatch, window, finalize, predicate, keys,
    chunk,
):
    if chunk is not None:
        # a window's positions filled in several chunks
        monkeypatch.setattr(window_exec, "EMIT_CHUNK_GROUPS", chunk)
    make = KEY_FEEDS[keys](make_batch)
    pred = None if predicate is None else ef.PREDICATES[predicate]()
    config = EngineConfig(device_finalize=finalize)
    got, root = ef._run(ef._feed(make), config, ef.WINDOWS[window], pred)
    m = _window_op(root).metrics()
    object_lane()
    want, root_obj = ef._run(ef._feed(make), config, ef.WINDOWS[window], pred)
    m_obj = _window_op(root_obj).metrics()
    assert m["emit_keys_dict_rows"] == sum(b.num_rows for b in got)
    assert m["emit_keys_dict_rows"] == m["emit_rows"] - m["emit_rows_filtered"]
    assert m_obj["emit_keys_dict_rows"] == 0
    for k in ("emit_rows", "emit_rows_filtered", "windows_emitted"):
        assert m[k] == m_obj[k]
    if predicate == "none_pass":
        assert got == want == [] and m["emit_keys_dict_rows"] == 0
        return
    if keys.endswith("_with_nulls") and predicate is None:
        assert any(b.columns[0].validity is not None for b in got)
    _assert_same_output(got, want)


def test_cold_tier_windows_take_the_dictionary_lane(tmp_path, object_lane):
    schema, items = ef._cold_items()
    pred = (col("avg") > ef.THRESHOLD) & (col("cnt") >= 2)

    def run():
        store = LsmStore(str(tmp_path / f"lsm{len(runs)}"))
        try:
            ctrl = tiering.SpillController(store, budget_bytes=20_000)
            op = ef._cold_op(schema, items)
            op.set_emission_predicate(pred)
            op.enable_spill("0_win", ctrl)
            out = [b for b in op.run() if isinstance(b, RecordBatch)]
            spilled = ctrl.spill_stats("0_win")["spill_blocks_total"]
            ctrl.close()
        finally:
            store.close()
        runs.append((out, op.metrics(), spilled))

    runs: list = []
    run()
    object_lane()
    run()
    (got, m, spilled), (want, m_obj, _) = runs
    assert spilled > 0
    _assert_same_output(got, want)
    assert m["emit_keys_dict_rows"] == m["emit_rows"] - m["emit_rows_filtered"]
    assert m_obj["emit_keys_dict_rows"] == 0


KEYS_ON_THE_OBJECT_LANE = {
    # two key columns: gathered per column as before
    "two_columns": (["sensor_name", "bucket"], np.dtype(object)),
    # numbers: the interner's numeric path holds no native keys
    "numeric": (["bucket"], np.dtype(np.int64)),
}


@pytest.mark.parametrize("case", list(KEYS_ON_THE_OBJECT_LANE))
def test_other_keys_keep_the_object_lane(make_batch, case):
    keys, first_dtype = KEYS_ON_THE_OBJECT_LANE[case]
    ctx = Context()
    ds = ctx.from_source(MemorySource.from_batches(
        ef._feed(make_batch), timestamp_column="occurred_at_ms"
    )).with_column("bucket", (col("reading") * 0.1).cast(DataType.INT64))
    out = list(ds.window(keys, ef._aggs(), 1000).stream())
    assert out
    for b in out:
        assert all(isinstance(c, np.ndarray) for c in b.columns)
    assert out[0].columns[0].dtype == first_dtype
    m = _window_op(ctx._last_physical).metrics()
    assert m["emit_keys_dict_rows"] == 0 and m["emit_rows"] > 0


# -- downstream of the window ------------------------------------------------


def _windowed(make_batch, ctx=None):
    ctx = ctx or Context()
    return ctx, ctx.from_source(MemorySource.from_batches(
        ef._feed(make_batch), timestamp_column="occurred_at_ms"
    )).window(["sensor_name"], ef._aggs(), 1000)


DOWNSTREAM = {
    # names the key, so the filter stays a FilterExec over the codes
    "filter_on_the_key": lambda ds: ds.filter(
        (col("sensor_name") != "key_0001") & (col("avg") > ef.THRESHOLD)
    ),
    "is_null_on_the_key": lambda ds: ds.filter(col("sensor_name").is_null()),
    "is_not_null_on_the_key": lambda ds: ds.filter(
        col("sensor_name").is_not_null() & (col("cnt") >= 2)
    ),
    "second_window_by_the_key": lambda ds: ds.window(
        ["sensor_name"], [F.sum(col("cnt")).alias("total")], 2000
    ),
    "in_list_on_the_key": lambda ds: ds.filter(
        F.in_list(col("sensor_name"), ["key_0001", "key_0002", "ulp_hi"])
    ),
    "scalar_function_of_the_key": lambda ds: ds.with_column(
        "shout", F.upper(col("sensor_name"))
    ),
    "udf_over_the_key": lambda ds: ds.with_column(
        "n", F.udf(
            lambda s: np.array(
                [-1 if x is None else len(x) for x in s], np.int64
            ),
            DataType.INT64, "n",
        )(col("sensor_name")),
    ),
}


def _rows(batches):
    """Every row of ``batches`` as a tuple of Python values, in order."""
    out = []
    for b in batches:
        d = b.to_pydict()
        out += list(zip(*d.values()))
    return out


@pytest.mark.parametrize("case", list(DOWNSTREAM))
def test_operators_downstream_see_the_keys(make_batch, object_lane, case):
    """Over a stream in which some keys are null."""
    make = _with_nulls(make_batch)

    def run():
        _, ds = _windowed(make)
        return list(DOWNSTREAM[case](ds).stream())

    got = run()
    object_lane()
    want = run()
    assert len(got) == len(want) > 0
    assert _rows(got) == _rows(want)
    if case == "is_null_on_the_key":
        assert all(r[0] is None for r in _rows(got))


def test_a_join_on_the_key(make_batch, object_lane):
    make = _with_nulls(make_batch)

    def run():
        ctx, left = _windowed(make)
        _, right = _windowed(make, ctx)
        right = (
            right.with_column_renamed("sensor_name", "r_name")
            .with_column_renamed(WINDOW_START_COLUMN, "r_start")
            .with_column_renamed("window_end_time", "r_end")
            .with_column_renamed("cnt", "r_cnt")
            .with_column_renamed("avg", "r_avg")
        )
        res = left.join(
            right, "inner", ["sensor_name", WINDOW_START_COLUMN],
            ["r_name", "r_start"],
        ).collect()
        assert as_numpy(res.column("sensor_name")).tolist() == as_numpy(
            res.column("r_name")
        ).tolist()
        return sorted(_rows([res]), key=repr)

    got = run()
    object_lane()
    want = run()
    assert len(got) > 0 and got == want


def test_a_callback_sink_gets_object_arrays(make_batch):
    seen = []
    _, ds = _windowed(make_batch)
    ds.sink(seen.append)
    assert seen
    for b in seen:
        assert all(isinstance(c, np.ndarray) for c in b.columns)
        assert b.column("sensor_name").dtype == object
        assert all(isinstance(k, str) for k in b.column("sensor_name"))


def _emitted(make_batch, object_lane):
    """One window's batch on each lane, from the same stream."""
    _, ds = _windowed(make_batch)
    got = list(ds.stream())
    object_lane()
    _, ds = _windowed(make_batch)
    want = list(ds.stream())
    assert isinstance(got[0].columns[0], DictionaryColumn)
    return got[0], want[0]


def test_the_json_row_encoder_writes_the_same_bytes(make_batch, object_lane):
    got, want = _emitted(make_batch, object_lane)
    enc = JsonRowEncoder()
    assert enc.encode(got) == enc.encode(want)


def test_the_exchange_frame_carries_the_keys(make_batch, object_lane):
    got, want = _emitted(make_batch, object_lane)
    frame = framing.encode_data(got, 5)
    t, back, wm, _ = framing.decode_frame(
        frame[framing._HDR.size:], got.schema
    )
    assert t == "data" and wm == 5
    assert isinstance(back.columns[0], StringColumn)
    assert _rows([back]) == _rows([want])


def test_spill_and_snapshot_blobs_carry_the_keys(make_batch, object_lane):
    got, want = _emitted(make_batch, object_lane)
    back, extra = tiering.rb_from_blob(tiering.rb_to_blob(got, {"e": 1}),
                                       got.schema)
    assert extra == {"e": 1}
    assert isinstance(back.columns[0], StringColumn)
    assert _rows([back]) == _rows([want])
    # the join's retained rows pack through the same codec
    meta = {"strings": {}, "masked": []}
    arrays: dict = {}
    StreamingJoinExec._pack_side_cols(0, got, got.schema, meta, arrays)
    assert "sensor_name" in meta["columnar"]
    assert meta["strings"] == {}


def test_a_dictionary_key_field_stays_a_string_field():
    # the dictionary is a layout, not a type
    sch = Schema([Field("k", DataType.STRING), Field("v", DataType.INT64)])
    b = RecordBatch(sch, [_dict_col((0, 1)), np.array([1, 2])])
    assert b.schema.field("k").dtype is DataType.STRING
    assert b.materialized().columns[0].tolist() == ["b", ""]
