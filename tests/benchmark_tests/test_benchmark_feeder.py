"""The feeder: fetch-offset and backlog arithmetic of the wire server,
lateness, and the protocol of the process."""

import json
import socket
import struct
import subprocess
import sys
import time

import pytest

from benchmark.harness import events, feeder, manifest, wire

FEED = dict(seed=5, n_keys=10, key_prefix="sensor_", partitions=4, chunk_ms=50,
            events_per_chunk=400, records_per_batch=64)


def fetch_request(parts, max_wait=0, topic=b"t"):
    body = struct.pack(">iiiib", -1, max_wait, 1, 1 << 20, 0)
    body += struct.pack(">i", 1) + struct.pack(">h", len(topic)) + topic
    body += struct.pack(">i", len(parts))
    for part, off, maxb in parts:
        body += struct.pack(">iqi", part, off, maxb)
    return body


def parse_fetch(parts):
    raw = b"".join(parts)
    _throttle, ntopics = struct.unpack_from(">ii", raw, 0)
    pos = 8
    out = {}
    for _ in range(ntopics):
        (ln,) = struct.unpack_from(">h", raw, pos)
        pos += 2 + ln
        (nparts,) = struct.unpack_from(">i", raw, pos)
        pos += 4
        for _ in range(nparts):
            part, err, hw, _lso = struct.unpack_from(">ihqq", raw, pos)
            pos += 22
            _aborted, size = struct.unpack_from(">ii", raw, pos)
            pos += 8
            out[part] = (err, hw, raw[pos:pos + size])
            pos += size
    assert pos == len(raw)
    return out


def filled_broker(chunks=3):
    f = events.Feed(**FEED)
    enc = wire.Encoder(f.key_prefix, f.key_width, f.records_per_batch)
    b = feeder.Broker("t", f.partitions)
    for c in range(chunks):
        b.append(feeder.encode_chunk(f, enc, c), f.events_per_chunk // f.partitions)
    return f, b


def test_fetch_serves_whole_segments_and_counts_offsets_asked_for():
    f, b = filled_broker()
    per = f.events_per_chunk // f.partitions
    assert b.produced == [3 * per] * 4 and b.backlog() == 12 * per
    got = parse_fetch(b._fetch(fetch_request([(2, 0, 1 << 20)])))
    err, hw, blob = got[2]
    recs = wire.parse_record_batches(blob)
    assert err == 0 and hw == 3 * per
    assert [r[0] for r in recs] == list(range(3 * per))
    assert b.fetched == [0, 0, 0, 0]  # nothing asked for beyond offset 0
    # the next fetch asks for what follows: that is the advance
    got = parse_fetch(b._fetch(fetch_request([(2, 3 * per, 1 << 20)])))
    assert got[2][2] == b"" and b.fetched == [0, 0, 3 * per, 0]
    assert b.backlog() == 9 * per and b.take_backlog_min() == 9 * per
    assert b.take_backlog_min() is None
    assert len(b._segments[2]) == 0 and len(b._segments[0]) == 3


def test_fetch_keeps_within_max_bytes_but_serves_at_least_one_segment():
    f, b = filled_broker()
    per = f.events_per_chunk // f.partitions
    one = len(b._segments[0][0][2])
    got = parse_fetch(b._fetch(fetch_request([(0, 0, 10)])))
    assert len(got[0][2]) == one
    got = parse_fetch(b._fetch(fetch_request([(0, per, 2 * one)])))
    assert len(got[0][2]) == 2 * one
    assert wire.parse_record_batches(got[0][2])[0][0] == per
    assert b.fetched[0] == per and len(b._segments[0]) == 2


def test_fetch_waits_for_data_at_most_max_wait():
    _f, b = filled_broker(chunks=0)
    t0 = time.monotonic()
    got = parse_fetch(b._fetch(fetch_request([(1, 0, 1 << 20)], max_wait=150)))
    assert 0.1 < time.monotonic() - t0 < 2.0 and got[1][2] == b""
    assert b.take_backlog_min() == 0  # a fetch found the topic empty


def test_list_offsets_and_metadata_name_every_partition():
    f, b = filled_broker()
    per = f.events_per_chunk // f.partitions
    req = struct.pack(">ii", -1, 1) + struct.pack(">h", 1) + b"t"
    req += struct.pack(">i", 2) + struct.pack(">iq", 0, -2) + struct.pack(">iq", 3, -1)
    raw = b._list_offsets(req)
    offs = [struct.unpack_from(">ihqq", raw, 4 + 3 + 4 + 22 * i) for i in range(2)]
    assert [(o[0], o[3]) for o in offs] == [(0, 0), (3, 3 * per)]
    meta = b._metadata()
    assert struct.pack(">i", b.port) in meta and b"\x00\x01t" in meta
    b.stop()


def test_lateness_arithmetic():
    # chunk 4 of 10 ms chunks is due 50 ms after the origin
    assert feeder.lateness_ms(100.0532, 100.0, 4, 10) == pytest.approx(3.2)
    assert feeder.lateness_ms(100.049, 100.0, 4, 10) == pytest.approx(-1.0)


@pytest.mark.parametrize("mode", ["drain", "paced"])
def test_feeder_process_protocol(mode):
    params = {"topic": "t", "mode": mode, "lead_events": 2000,
              "ahead_chunks": 4, "encoders": 2, "feed": FEED}
    proc = subprocess.Popen(
        [sys.executable, "-m", "benchmark.harness.feeder", json.dumps(params)],
        cwd=manifest.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        def ask(cmd, **kw):
            proc.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
            proc.stdin.flush()
            return json.loads(proc.stdout.readline())

        port = json.loads(proc.stdout.readline())["ready"]
        origin = time.monotonic()
        assert ask("start", origin=origin) == {"started": True}
        # the encoder children take a moment to come up
        deadline = time.monotonic() + 30.0
        mark = ask("mark")
        late = list(mark["late_ms"])
        while sum(mark["produced"]) < 2000 and time.monotonic() < deadline:
            time.sleep(0.1)
            mark = ask("mark")
            late += mark["late_ms"]
        if mode == "drain":
            # nobody fetches: production stops at the lead
            time.sleep(0.3)
            mark = ask("mark")
            assert sum(mark["produced"]) == 2000 and late == []
        else:
            # open loop: a 50 ms chunk is due every 50 ms, whoever reads;
            # none is appended before it is due, and each reports how late
            due = int((mark["t"] - origin) / 0.05)
            assert 5 <= mark["chunks"] <= due
            assert len(late) == mark["chunks"] and min(late) >= 0.0
        assert mark["fetched"] == [0, 0, 0, 0] and mark["backlog_min"] is None
        # a consumer on the wire gets the seeded records
        with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
            body = struct.pack(">hhi", 1, 4, 77) + struct.pack(">h", 1) + b"c"
            body += fetch_request([(1, 0, 1 << 20)])
            s.sendall(struct.pack(">i", len(body)) + body)
            (size,) = struct.unpack(">i", feeder._recv_all(s, 4))
            raw = feeder._recv_all(s, size)
        assert struct.unpack_from(">i", raw, 0)[0] == 77
        recs = wire.parse_record_batches(parse_fetch([raw[4:]])[1][2])
        ts, kid, micro = events.chunk_arrays(events.Feed(**FEED), 0)
        assert recs[0][2] == wire.payload_of(
            int(ts[1]), "sensor_%d" % kid[1], int(micro[1]))
        assert ask("stop")["chunks"] >= mark["chunks"]
        proc.stdin.write('{"cmd": "quit"}\n')
        proc.stdin.flush()
        assert proc.wait(20) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        proc.stdin.close()
        proc.stdout.close()
