"""The native string interner against the dict fallback: ids equal element
for element over seeded batch sequences and across snapshot → restore, and
the table's tallies as the window operator's ``metrics()`` report them."""

import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.columns import StringColumn
from denormalized_tpu.ops import interner as interner_mod
from denormalized_tpu.ops.interner import GroupInterner
from denormalized_tpu.runtime.tracing import collect_metrics
from denormalized_tpu.sources.memory import MemorySource


@pytest.fixture(autouse=True, scope="module")
def _native_library():
    # asked inside a fixture: collection builds nothing
    if interner_mod._load_native_lib() is None:
        pytest.skip("no native interner: nothing for the fallback to differ from")


def _string_column(keys: list[bytes | None]) -> StringColumn:
    raw = [k or b"" for k in keys]
    offsets = np.zeros(len(raw) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in raw], out=offsets[1:])
    data = np.frombuffer(b"".join(raw), dtype=np.uint8)
    validity = np.array([k is not None for k in keys], dtype=bool)
    return StringColumn(offsets, data, None if validity.all() else validity)


def _draw(pool: list[bytes | None], rows: int, batches: int, seed: int):
    rng = np.random.default_rng(seed)
    return [
        [pool[i] for i in rng.integers(0, len(pool), rows)]
        for _ in range(batches)
    ]


def _by_length(*lengths: int) -> list[bytes]:
    # three keys a length (one for the empty key), no trailing NUL
    return [
        bytes((7 * i + 5 * salt + n) % 26 + 97 for i in range(n))
        for n in lengths for salt in range(3 if n else 1)
    ]


#: case → (key pool, rows a batch, batches)
CASES = {
    "10_keys": ([b"sensor_%d" % i for i in range(10)], 4096, 4),
    "100k_keys": ([b"key_%05d" % i for i in range(100_000)], 18_750, 6),
    "len_0_1_23_24_200": (_by_length(0, 1, 23, 24, 200), 257, 4),
    "len_around_words": (
        _by_length(7, 8, 9, 15, 16, 17, 22, 25, 31, 32, 33), 513, 4,
    ),
    "nuls_embedded_and_trailing": (
        [b"a", b"a\x00", b"a\x00\x00", b"a\x00b", b"a\x00\x00b", b"\x00",
         b"\x00a", b"b" * 23 + b"\x00", b"b" * 24 + b"\x00\x00",
         b"c" * 22 + b"\x00c", b"c" * 40 + b"\x00c" + b"\x00" * 5],
        300, 4,
    ),
    "nulls_via_validity": (
        [None, b"None", b"", b"x", b"\xc3\xbf", b"y" * 30, None], 300, 4,
    ),
    "non_ascii_utf8": (
        [s.encode() for s in (
            "é", "éé", "日本語", "日本語のキー", "ключ-сенсора-0001",
            "🙂", "🙂" * 6, "naïve-" + "ü" * 12, "a" * 21 + "é", "a" * 22 + "é",
        )],
        300, 4,
    ),
    # fewer rows than a block, exactly one, one over; and no rows at all
    "block_edges": ([b"k%d" % i for i in range(40)], 17, 5),
    "one_row_batches": ([b"k%d" % i for i in range(5)], 1, 9),
    "empty_batches": ([b"never"], 0, 3),
}


def _fallback_interner(monkeypatch) -> GroupInterner:
    with monkeypatch.context() as m:
        m.setattr(interner_mod, "_load_native", lambda: (None, None))
        g = GroupInterner(1)
    assert g._col_interners[0]._h is None
    return g


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_ids_equal_fallback_ids(case, monkeypatch):
    pool, rows, batches = CASES[case]
    seq = _draw(pool, rows, batches + 1, seed=len(case))
    native, plain = GroupInterner(1), _fallback_interner(monkeypatch)
    assert native._col_interners[0]._h is not None
    for keys in seq[:-1]:
        got = native.intern([_string_column(keys)])
        want = plain.intern([_string_column(keys)])
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
    assert len(native) == len(plain)
    # the id store gives every key back as the fallback holds it
    every = np.arange(len(native))
    assert native.keys_of(every)[0].tolist() == plain.keys_of(every)[0].tolist()
    # a checkpoint of either restores into the native table, ids in place
    # (load_values' restore-order assert), and a further batch agrees
    want = plain.intern([_string_column(seq[-1])])
    for snap in (native.snapshot(), plain.snapshot()):
        restored = GroupInterner.restore(snap)
        assert restored._col_interners[0]._h is not None
        np.testing.assert_array_equal(
            restored.intern([_string_column(seq[-1])]), want
        )
        assert len(restored) == len(plain)


def test_lanes_share_one_table(monkeypatch):
    """Offsets lane and PyObject lane of one column agree on every key,
    None and the NULL slot included."""
    pool = CASES["nulls_via_validity"][0] + CASES["len_0_1_23_24_200"][0]
    native, plain = GroupInterner(1), _fallback_interner(monkeypatch)
    for b, keys in enumerate(_draw(pool, 200, 6, seed=3)):
        column = _string_column(keys)
        if b % 2:
            column = column.as_object()
        np.testing.assert_array_equal(
            native.intern([column]), plain.intern([_string_column(keys)])
        )


T0 = 1_700_000_000_000

#: key shape → (its keys, is every one longer than a slot holds)
SHAPES = {
    "emit_sliding_keys": ([f"sensor_{i}" for i in range(10)], False),
    "keyed_100k_keys": ([f"key_{i:05d}" for i in range(0, 100_000, 997)], False),
    "200_byte_keys": ([f"{i:04d}".ljust(200, "k") for i in range(7)], True),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_window_metrics_intern_tallies(shape, make_batch):
    names, long_keys = SHAPES[shape]
    rng = np.random.default_rng(5)
    batches = []
    for b in range(6):
        ts = np.sort(T0 + b * 400 + rng.integers(0, 400, size=300))
        batches.append(make_batch(
            ts, rng.choice(names, size=300), rng.normal(50.0, 10.0, size=300)
        ))
    ctx = Context(EngineConfig(min_batch_bucket=256))
    ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms")
    ).window(
        [col("sensor_name")], [F.count(col("reading")).alias("count")], 1000
    ).collect()
    (m,) = [
        m for node, m in collect_metrics(ctx._last_physical).items()
        if node.endswith("StreamingWindowExec")
    ]
    assert m["rows_in"] == 6 * 300
    assert m["intern_rows"] == m["rows_in"]
    assert m["intern_overflow_rows"] == (m["rows_in"] if long_keys else 0)
    # linear probing in a table at most 3/4 full: a handful a row at worst
    assert 0 <= m["intern_extra_probes"] < 4 * m["intern_rows"]
