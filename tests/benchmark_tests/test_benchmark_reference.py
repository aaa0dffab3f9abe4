"""The plain reference against a pure-Python fold, the comparison's
numbers, the control (bfloat16), and the latency arithmetic."""

import numpy as np
import pytest

from benchmark.harness import events, reference, runner

FEED = events.Feed(seed=11, n_keys=10, key_prefix="sensor_", partitions=4,
                   chunk_ms=50, events_per_chunk=400)
SLIDING = dict(length=1000, slide=200, aggs=[("cnt", "count"), ("avg", "avg")],
               flt=("avg", 45.0))
TUMBLING = dict(length=1000, slide=1000,
                aggs=[("count", "count"), ("sum", "sum"), ("min", "min"),
                      ("max", "max"), ("avg", "avg")], flt=None)


def python_fold(feed, length, slide, start, end):
    """Row at a time, dictionaries only."""
    cells = {}
    for c in events.chunks_covering(feed, start, end):
        ts, kid, micro = events.chunk_arrays(feed, c)
        for t, k, m in zip(ts.tolist(), kid.tolist(), micro.tolist()):
            x = m / 1e6
            j = t // slide
            while j * slide + length > t:
                ws = j * slide
                if ws >= start and ws + length <= end:
                    cells.setdefault((ws, k), []).append(x)
                j -= 1
    return cells


@pytest.mark.parametrize("shape", [SLIDING, TUMBLING], ids=["sliding", "tumbling"])
def test_reference_equals_pure_python_fold(shape):
    start, end = events.T0 + 2000, events.T0 + 4000
    ref = reference.Reference(FEED, shape["length"], shape["slide"], start, end)
    want = python_fold(FEED, shape["length"], shape["slide"], start, end)
    fold = ref.fold()
    assert ref.n_windows == (6 if shape["slide"] == 200 else 2)
    assert int((ref.rows_per_cell > 0).sum()) == len(want)
    for (ws, k), xs in want.items():
        cell = (ws // shape["slide"] - ref.w0) * FEED.n_keys + k
        assert fold["count"][cell] == len(xs)
        assert fold["sum"][cell] == pytest.approx(sum(xs), rel=1e-12)
        assert fold["min"][cell] == min(xs) and fold["max"][cell] == max(xs)
        assert fold["avg"][cell] == pytest.approx(sum(xs) / len(xs), rel=1e-12)


def test_windows_before_the_feed_start_are_whole():
    ref = reference.Reference(FEED, 1000, 200, events.T0 - 800, events.T0 + 1200)
    assert list(ref.chunks) == list(range(24))
    assert ref.window_starts()[0] == events.T0 - 800
    first = ref.fold()["count"][:FEED.n_keys].sum()
    assert first == 4 * FEED.events_per_chunk  # only [T0, T0+200) has events


def put_in_place(ref, shape, fold=None):
    return reference.rows_of(fold or ref.fold(), ref, shape["aggs"], shape["flt"])


@pytest.mark.parametrize("shape", [SLIDING, TUMBLING], ids=["sliding", "tumbling"])
def test_reference_in_its_own_place_is_correct(shape):
    ref = reference.Reference(FEED, shape["length"], shape["slide"],
                              events.T0, events.T0 + 2000)
    n = reference.compare(ref, put_in_place(ref, shape), shape["aggs"], shape["flt"])
    assert n.pop("bad_windows") == 0
    ok, compared = reference.verdict(n)
    assert ok and compared["rel_err_max"]["value"] == 0.0
    assert n["rows_compared"] > 0


def test_f32_rounding_passes_and_each_fault_fails():
    shape = TUMBLING
    ref = reference.Reference(FEED, 1000, 1000, events.T0, events.T0 + 2000)
    good = put_in_place(ref, shape)
    f32 = dict(good, **{k: good[k].astype(np.float32).astype(np.float64)
                        for k in ("sum", "min", "max", "avg")})
    n = reference.compare(ref, f32, shape["aggs"], None)
    assert n["bad_windows"] == 0 and 0 < n["rel_err_max"] < 1e-6

    def numbers(**changed):
        got = dict(good, **changed)
        return reference.compare(ref, got, shape["aggs"], None)

    # an answer altered where it is produced
    avg = good["avg"].copy()
    avg[3] *= 1.001
    assert numbers(avg=avg)["rel_err_max"] > 5e-4
    cnt = good["count"].copy()
    cnt[0] -= 1
    assert numbers(count=cnt)["count_mismatch"] == 1
    lo = good["min"].copy()
    lo[1] += 1e-3
    assert numbers(min=lo)["minmax_mismatch"] == 1
    # a row left out, a row that should not be there
    part = {k: v[1:] for k, v in good.items()}
    n = reference.compare(ref, part, shape["aggs"], None)
    assert n["missing_rows"] == 1 and n["bad_windows"] == 1
    assert not reference.verdict(n)[0]
    # nothing compared is not correct either
    assert not reference.verdict({"rows_compared": 0})[0]


def test_filter_band_rows_may_be_there_or_not():
    ref = reference.Reference(FEED, 1000, 200, events.T0, events.T0 + 2000)
    good = put_in_place(ref, SLIDING)
    fold = ref.fold()
    # drop a row that passes clearly: missing; add one that fails clearly
    part = {k: v[1:] for k, v in good.items()}
    assert reference.compare(ref, part, SLIDING["aggs"], SLIDING["flt"])["missing_rows"] == 1
    failing = int(np.flatnonzero((ref.rows_per_cell > 0) & (fold["avg"] < 44.0))[0])
    extra = {"cells": np.append(good["cells"], failing),
             "cnt": np.append(good["cnt"], fold["count"][failing]),
             "avg": np.append(good["avg"], fold["avg"][failing])}
    assert reference.compare(ref, extra, SLIDING["aggs"], SLIDING["flt"])["unexpected_rows"] == 1


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
@pytest.mark.parametrize("shape", [SLIDING, TUMBLING], ids=["sliding", "tumbling"])
def test_control_in_bfloat16_comes_out_not_correct(shape, seed):
    """The control of "How correct is decided", at a size a test can hold:
    the reference folded in bfloat16, put in the engine's place."""
    feed = events.Feed(seed=seed, n_keys=10, key_prefix="sensor_", partitions=4,
                       chunk_ms=50, events_per_chunk=2000)
    ref = reference.Reference(feed, shape["length"], shape["slide"],
                              events.T0, events.T0 + 2000)
    n = reference.compare(ref, put_in_place(ref, shape, ref.bf16_fold()),
                          shape["aggs"], shape["flt"])
    n.pop("bad_windows")
    ok, compared = reference.verdict(n)
    assert not ok
    assert compared["rel_err_max"]["value"] > 50 * reference.LIMITS["rel_err_max"]
    assert compared["count_mismatch"]["value"] == 0  # counts stay exact


def test_merge_adds_counts_and_keeps_the_widest_gap():
    a = {"rows_compared": 10, "missing_rows": 1, "rel_err_max": 1e-7}
    b = {"rows_compared": 5, "missing_rows": 0, "rel_err_max": 3e-7}
    assert reference.merge([a, b]) == {
        "rows_compared": 15, "missing_rows": 1, "rel_err_max": 3e-7}


def test_sampled_blocks_one_in_every_group_from_the_seed():
    a = runner.sampled_blocks(5, 8)
    assert np.array_equal(a, runner.sampled_blocks(5, 8))
    assert not np.array_equal(a, runner.sampled_blocks(6, 8))
    assert (a[:800].reshape(100, 8).sum(axis=1) == 1).all()


def test_latency_arithmetic_with_a_missing_window():
    origin, slide = 100.0, 200
    due = runner.windows_due(origin, 101.0, 102.0, slide)
    assert sorted(due) == [events.T0 + 1000 + 200 * i for i in range(6)]
    assert due[events.T0 + 1400] == pytest.approx(101.4)
    arrival = {e: t + 0.3 for e, t in due.items()}
    del arrival[events.T0 + 1200]            # never delivered
    arrival[events.T0 + 2000] += 10.0        # delivered after the tail
    samples, undelivered = runner.latency_samples(due, arrival, t_tail=103.0)
    assert undelivered == 2 and len(samples) == 6
    assert sorted(samples)[:4] == pytest.approx([300.0] * 4)
    # each missing window counts as the wait until the tail's end
    assert sorted(samples)[4:] == pytest.approx([1000.0, 1800.0])
    assert runner.percentile(samples, 50) == pytest.approx(300.0)
    assert runner.percentile(samples, 95) == pytest.approx(1600.0)
