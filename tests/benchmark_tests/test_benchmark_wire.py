"""The seeded generator and the vectorised encoder, held to a plain
per-record encoder and the copied broker's parser."""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import events, manifest, wire


def feed(seed=7, n_keys=1000, prefix="key_", per_chunk=2000, chunk_ms=50, r=64):
    return events.Feed(seed=seed, n_keys=n_keys, key_prefix=prefix, partitions=4,
                       chunk_ms=chunk_ms, events_per_chunk=per_chunk,
                       records_per_batch=r)


def naive_blob(f, c, p, base):
    ts, kid, micro = events.chunk_arrays(f, c)
    names = f.key_names()
    t, k, m = ts[p::4], kid[p::4], micro[p::4]
    pay = [wire.payload_of(int(a), names[b], int(x)) for a, b, x in zip(t, k, m)]
    first_ts = events.T0 + c * f.chunk_ms
    r = f.records_per_batch
    return pay, b"".join(
        wire.encode_naive(base + i, first_ts, pay[i:i + r])
        for i in range(0, len(pay), r))


@pytest.mark.parametrize("r,per_chunk", [(64, 2000), (512, 4000), (512, 2048 * 4), (7, 400)])
def test_vectorised_encoder_equals_the_plain_one(r, per_chunk):
    f = feed(r=r, per_chunk=per_chunk, seed=3_000_000_019)
    enc = wire.Encoder(f.key_prefix, f.key_width, r)
    ts, kid, micro = events.chunk_arrays(f, 3)
    for p in range(4):
        _pay, want = naive_blob(f, 3, p, base=1000)
        got = enc.encode(1000, events.T0 + 3 * f.chunk_ms, ts[p::4], kid[p::4],
                         micro[p::4])
        assert got == want


def test_parser_round_trips_and_payloads_are_json():
    f = feed(n_keys=100000, prefix="key_")
    enc = wire.Encoder(f.key_prefix, f.key_width, f.records_per_batch)
    ts, kid, micro = events.chunk_arrays(f, 0)
    blob = enc.encode(500, events.T0, ts[1::4], kid[1::4], micro[1::4])
    recs = wire.parse_record_batches(blob)
    pay, _ = naive_blob(f, 0, 1, 500)
    assert [r[2] for r in recs] == pay
    assert [r[0] for r in recs] == list(range(500, 500 + len(pay)))
    first = json.loads(recs[0][2])
    assert first["occurred_at_ms"] == int(ts[1])
    assert first["sensor_name"] == f.key_names()[kid[1]]
    assert first["reading"] == events.reading_of(micro[1:2])[0]
    with pytest.raises(ValueError):
        wire.parse_record_batches(blob[:-3])


def test_same_seed_same_events_other_seed_other_events():
    a = events.chunk_arrays(feed(seed=2**31 + 5), 9)
    b = events.chunk_arrays(feed(seed=2**31 + 5), 9)
    c = events.chunk_arrays(feed(seed=2**31 + 6), 9)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    # every seed has the same sizes and spans; keys and readings differ
    assert a[0].shape == c[0].shape
    assert not np.array_equal(a[1], c[1]) and not np.array_equal(a[2], c[2])


def test_chunks_are_sorted_in_span_and_fixed_width():
    f = feed()
    for c in (0, 5):
        ts, kid, micro = events.chunk_arrays(f, c)
        lo = events.T0 + c * f.chunk_ms
        assert (np.diff(ts) >= 0).all() and ts[0] >= lo and ts[-1] < lo + f.chunk_ms
        assert kid.min() >= 0 and kid.max() < f.n_keys
        assert micro.min() >= events.MICRO_LO and micro.max() <= events.MICRO_HI
    assert f.events_per_second == 40000
    assert list(events.chunks_covering(f, events.T0 + 100, events.T0 + 260)) == [2, 3, 4, 5]
    assert list(events.chunks_covering(f, events.T0 - 800, events.T0 + 50)) == [0]


def test_digits_and_feed_validation():
    x = np.array([0, 7, 1234567, 99999999])
    assert wire.digits(x, 8).tobytes() == b"00000000000000070123456799999999"
    assert wire.digits(np.array([1700000000123]), 13).tobytes() == b"1700000000123"
    with pytest.raises(ValueError):
        feed(per_chunk=2001)
    with pytest.raises(ValueError):
        feed(chunk_ms=30)
    assert feed(n_keys=10, prefix="sensor_").key_names()[3] == "sensor_3"
    assert feed(n_keys=100000).key_names()[42] == "key_00042"


def test_feeder_module_never_loads_jax():
    code = ("import sys, benchmark.harness.feeder; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'denormalized_tpu')))")
    out = subprocess.run([sys.executable, "-c", code], cwd=manifest.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
