"""The load generator: a Kafka wire server and the seeded producer behind
it, in a process of its own.

    python -m benchmark.harness.feeder '<json parameters>'

Started by the harness with ``subprocess``; imports numpy only, never JAX,
so it never touches the chip and shares no interpreter lock with the
engine.  The wire server is a copy of the subset that
``denormalized_tpu/testing/mock_kafka.py`` speaks (Metadata v1, ListOffsets
v1, Fetch v4) serving pre-encoded record-batch blobs; it keeps no
per-record Python object and drops a blob once the consumer has asked for
an offset beyond it.

Protocol, one JSON object a line.  Feeder → parent on stdout:
``{"ready": port}`` once listening, then one reply per command.  Parent →
feeder on stdin: ``{"cmd": "start", "origin": t}`` (``t`` on
``time.monotonic``, which Linux shares between processes: production
begins, and in paced mode chunk ``c`` is due at ``t + (c+1)*chunk_ms``),
``{"cmd": "mark"}`` (reply: the clock, offsets fetched and produced per
partition, fetch requests served, and the least backlog and the chunk
lateness seen since the last mark), ``{"cmd": "stop"}`` (production ends; reply: chunks produced),
``{"cmd": "quit"}``.  Closing stdin quits too.

Modes.  Chunks are encoded ahead by ``encoders`` child processes of the
feeder (the same module, started with ``"encode": [i, n]``), up to
``ahead_chunks`` beyond the last one appended.  ``drain``: a chunk is
appended whenever the topic is less than ``lead_events`` ahead of the
offsets the consumer has asked for, so offered load is above capacity and
memory is bounded.  ``paced``: a chunk is appended at its due time, whatever
the consumer does (open loop); how late each append ran is reported.
"""

from __future__ import annotations

import collections
import json
import socket
import struct
import subprocess
import sys
import threading
import time

from benchmark.harness import events, wire


class Broker:
    """One topic, ``partitions`` logs of encoded segments."""

    def __init__(self, topic: str, partitions: int):
        self.topic = topic
        self.n = partitions
        self.cond = threading.Condition()
        # per partition: deque of (base offset, records, blob)
        self._segments = [collections.deque() for _ in range(partitions)]
        self.produced = [0] * partitions
        self.fetched = [0] * partitions  # highest fetch offset asked for
        self.backlog_min: int | None = None
        self.fetches = 0  # fetch requests served
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(16)
        self.host, self.port = self._sock.getsockname()
        self._stop = threading.Event()
        self._conns: list[socket.socket] = []

    # -- producer side ----------------------------------------------------

    def append(self, blobs: list[bytes], records: int) -> None:
        """One chunk: a blob of ``records`` records for every partition."""
        with self.cond:
            for p, blob in enumerate(blobs):
                self._segments[p].append((self.produced[p], records, blob))
                self.produced[p] += records
            self.cond.notify_all()

    def backlog(self) -> int:
        return sum(self.produced) - sum(self.fetched)

    def take_backlog_min(self) -> int | None:
        with self.cond:
            least, self.backlog_min = self.backlog_min, None
            return least

    # -- server -----------------------------------------------------------

    def start(self) -> None:
        threading.Thread(target=self._accept, daemon=True).start()

    def stop(self) -> None:
        self._stop.set()
        with self.cond:
            self.cond.notify_all()
            conns, self._conns = self._conns, []
        for s in [self._sock, *conns]:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            s.close()

    def _accept(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self.cond:
                self._conns.append(conn)
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn: socket.socket) -> None:
        try:
            while not self._stop.is_set():
                hdr = _recv_all(conn, 4)
                if hdr is None:
                    return
                body = _recv_all(conn, struct.unpack(">i", hdr)[0])
                if body is None:
                    return
                parts = self._handle(body)
                size = sum(len(p) for p in parts)
                conn.sendall(struct.pack(">i", size) + parts[0])
                for part in parts[1:]:
                    conn.sendall(part)
        except OSError:
            return  # the consumer went away, or stop() closed the socket
        finally:
            conn.close()

    def _handle(self, body: bytes) -> list[bytes]:
        api_key, _version, corr = struct.unpack_from(">hhi", body, 0)
        (client_len,) = struct.unpack_from(">h", body, 8)
        payload = body[10 + max(client_len, 0):]
        head = struct.pack(">i", corr)
        if api_key == 3:
            return [head + self._metadata()]
        if api_key == 2:
            return [head + self._list_offsets(payload)]
        if api_key == 1:
            out = self._fetch(payload)
            out[0] = head + out[0]
            return out
        return [head + struct.pack(">h", 35)]  # UNSUPPORTED_VERSION

    def _metadata(self) -> bytes:
        host, name = self.host.encode(), self.topic.encode()
        out = bytearray()
        out += struct.pack(">ii", 1, 0)  # one broker, node 0
        out += struct.pack(">h", len(host)) + host
        out += struct.pack(">i", self.port)
        out += struct.pack(">h", -1)  # rack
        out += struct.pack(">i", 0)  # controller
        out += struct.pack(">i", 1)  # one topic
        out += struct.pack(">h", 0)
        out += struct.pack(">h", len(name)) + name
        out += struct.pack(">b", 0)
        out += struct.pack(">i", self.n)
        for p in range(self.n):
            out += struct.pack(">hiii", 0, p, 0, 1)
            out += struct.pack(">iii", 0, 1, 0)
        return bytes(out)

    def _list_offsets(self, payload: bytes) -> bytes:
        pos = 4
        (ntopics,) = struct.unpack_from(">i", payload, pos)
        pos += 4
        out = bytearray(struct.pack(">i", ntopics))
        for _ in range(ntopics):
            (ln,) = struct.unpack_from(">h", payload, pos)
            name = payload[pos + 2:pos + 2 + ln]
            pos += 2 + ln
            (nparts,) = struct.unpack_from(">i", payload, pos)
            pos += 4
            out += struct.pack(">h", ln) + name + struct.pack(">i", nparts)
            for _ in range(nparts):
                part, ts = struct.unpack_from(">iq", payload, pos)
                pos += 12
                with self.cond:
                    segs = self._segments[part]
                    earliest = segs[0][0] if segs else self.produced[part]
                    off = earliest if ts == -2 else self.produced[part]
                out += struct.pack(">ihqq", part, 0, ts, off)
        return bytes(out)

    def _fetch(self, payload: bytes) -> list[bytes]:
        (max_wait,) = struct.unpack_from(">i", payload, 4)
        pos = 17  # replica, max_wait, min_bytes, max_bytes, isolation
        (ntopics,) = struct.unpack_from(">i", payload, pos)
        pos += 4
        reqs = []
        for _ in range(ntopics):
            (ln,) = struct.unpack_from(">h", payload, pos)
            name = payload[pos + 2:pos + 2 + ln]
            pos += 2 + ln
            (nparts,) = struct.unpack_from(">i", payload, pos)
            pos += 4
            parts = []
            for _ in range(nparts):
                parts.append(struct.unpack_from(">iqi", payload, pos))
                pos += 16
            reqs.append((name, parts))

        deadline = time.monotonic() + max_wait / 1000.0
        with self.cond:
            self.fetches += 1
            for _name, parts in reqs:
                for part, off, _maxb in parts:
                    if off > self.fetched[part]:
                        self.fetched[part] = off
                    segs = self._segments[part]
                    while segs and segs[0][0] + segs[0][1] <= off:
                        segs.popleft()
            backlog = self.backlog()
            if self.backlog_min is None or backlog < self.backlog_min:
                self.backlog_min = backlog
            self.cond.notify_all()  # the producer waits on the backlog
            while not self._stop.is_set() and not any(
                self.produced[part] > off
                for _name, parts in reqs for part, off, _maxb in parts
            ):
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self.cond.wait(left)
            picked = []
            for name, parts in reqs:
                rows = []
                for part, off, maxb in parts:
                    blobs, size = [], 0
                    # whole segments from the one that holds ``off``; at
                    # least one, then as many as stay within max_bytes
                    for base, _n, blob in self._segments[part]:
                        if blobs and size + len(blob) > maxb:
                            break
                        blobs.append(blob)
                        size += len(blob)
                    rows.append((part, self.produced[part], blobs, size))
                picked.append((name, rows))

        out: list[bytes] = []
        cur = bytearray(struct.pack(">ii", 0, len(picked)))  # throttle, topics
        for name, rows in picked:
            cur += struct.pack(">h", len(name)) + name
            cur += struct.pack(">i", len(rows))
            for part, hw, blobs, size in rows:
                cur += struct.pack(">ihqq", part, 0, hw, hw)
                cur += struct.pack(">ii", 0, size)  # no aborted txns; bytes
                out.append(bytes(cur))
                out.extend(blobs)
                cur = bytearray()
        if cur:
            out.append(bytes(cur))
        return out


def _recv_all(conn: socket.socket, n: int) -> bytes | None:
    buf = bytearray()
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def encode_chunk(feed: events.Feed, encoder: wire.Encoder, c: int) -> list[bytes]:
    """Chunk ``c`` as one blob a partition."""
    ts, kid, micro = events.chunk_arrays(feed, c)
    per = feed.events_per_chunk // feed.partitions
    first_ts = events.T0 + c * feed.chunk_ms
    return [
        encoder.encode(c * per, first_ts, ts[p::feed.partitions],
                       kid[p::feed.partitions], micro[p::feed.partitions])
        for p in range(feed.partitions)
    ]


def lateness_ms(appended_at: float, origin: float, c: int, chunk_ms: int) -> float:
    """How long after its due time chunk ``c`` was appended."""
    return (appended_at - (origin + (c + 1) * chunk_ms / 1000.0)) * 1000.0


class Producer:
    """Appends the seeded chunks, by one of the two traffic modes.

    Encoding a chunk costs more than serving it, and one interpreter cannot
    stay ahead of the engine, so ``encoders`` child processes encode (child
    ``i`` of ``n`` makes chunks ``i, i+n, ...`` and writes them to its pipe
    in order); a thread a child reads up to ``ahead_chunks`` beyond the last
    chunk appended, and one thread appends them in order, when the mode
    says so."""

    def __init__(self, broker: Broker, params: dict):
        feed = events.Feed(**params["feed"])
        mode = params["mode"]
        if mode not in ("drain", "paced"):
            raise ValueError(f"unknown traffic mode {mode!r}")
        self.broker, self.feed, self.mode = broker, feed, mode
        self.lead_events = int(params.get("lead_events", 0))
        self.ahead_chunks = max(1, int(params["ahead_chunks"]))
        self.encoders = max(1, int(params["encoders"]))
        self._params = params
        self._per = feed.events_per_chunk // feed.partitions
        self.chunks = 0  # appended so far
        self.late_ms: list[float] = []
        self._stop = threading.Event()
        self._ready: dict[int, list[bytes]] = {}
        self._ready_cond = threading.Condition()
        self._threads: list[threading.Thread] = []
        self._children: list[subprocess.Popen] = []

    def start(self, origin: float) -> None:
        self.origin = origin
        for i in range(self.encoders):
            child = subprocess.Popen(
                [sys.executable, "-m", "benchmark.harness.feeder", json.dumps(
                    {**self._params, "encode": [i, self.encoders]}
                )],
                stdout=subprocess.PIPE, stdin=subprocess.DEVNULL,
            )
            self._children.append(child)
            t = threading.Thread(
                target=self._read, args=(child, i), daemon=True
            )
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._append, daemon=True)
        t.start()
        self._threads.append(t)

    def stop(self) -> int:
        self._stop.set()
        with self.broker.cond:
            self.broker.cond.notify_all()
        with self._ready_cond:
            self._ready_cond.notify_all()
        for child in self._children:
            child.kill()
        for child in self._children:
            child.wait()
            child.stdout.close()
        self._children = []
        for t in self._threads:
            t.join(10.0)
        self._threads = []
        return self.chunks

    def take_late_ms(self) -> list[float]:
        late, self.late_ms = self.late_ms, []
        return late

    def _read(self, child: subprocess.Popen, index: int) -> None:
        c = index
        head = struct.Struct(">q%dI" % self.feed.partitions)
        while not self._stop.is_set():
            with self._ready_cond:
                if c - self.chunks >= self.ahead_chunks:
                    self._ready_cond.wait(0.05)
                    continue
            raw = child.stdout.read(head.size)
            if len(raw) < head.size:
                return  # the child was stopped
            got, *sizes = head.unpack(raw)
            blobs = [child.stdout.read(n) for n in sizes]
            if got != c or any(len(b) != n for b, n in zip(blobs, sizes)):
                return
            with self._ready_cond:
                self._ready[c] = blobs
                self._ready_cond.notify_all()
            c += self.encoders

    def _append(self) -> None:
        broker = self.broker
        dt = self.feed.chunk_ms / 1000.0
        while not self._stop.is_set():
            c = self.chunks
            if self.mode == "paced":
                wait = self.origin + (c + 1) * dt - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            else:
                with broker.cond:
                    if broker.backlog() >= self.lead_events:
                        broker.cond.wait(0.05)
                        continue
            with self._ready_cond:
                while c not in self._ready and not self._stop.is_set():
                    self._ready_cond.wait(0.05)
                if c not in self._ready:
                    return
                blobs = self._ready.pop(c)
            broker.append(blobs, self._per)
            if self.mode == "paced":
                self.late_ms.append(lateness_ms(
                    time.monotonic(), self.origin, c, self.feed.chunk_ms
                ))
            with self._ready_cond:
                self.chunks = c + 1
                self._ready_cond.notify_all()


def encode_forever(params: dict) -> int:
    """An encoder child: chunks ``i, i+n, ...`` framed onto standard output
    (chunk index and one length a partition, then the blobs) until the pipe
    closes or the parent kills it."""
    feed = events.Feed(**params["feed"])
    i, n = params["encode"]
    encoder = wire.Encoder(feed.key_prefix, feed.key_width, feed.records_per_batch)
    head = struct.Struct(">q%dI" % feed.partitions)
    out = sys.stdout.buffer
    c = i
    try:
        while True:
            blobs = encode_chunk(feed, encoder, c)
            out.write(head.pack(c, *(len(b) for b in blobs)))
            for b in blobs:
                out.write(b)
            out.flush()
            c += n
    except BrokenPipeError:
        return 0


def main(argv: list[str]) -> int:
    params = json.loads(argv[1])
    if "encode" in params:
        return encode_forever(params)
    broker = Broker(params["topic"], params["feed"]["partitions"])
    producer = Producer(broker, params)
    broker.start()

    def say(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    say({"ready": broker.port})
    try:
        for line in sys.stdin:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "start":
                producer.start(float(msg["origin"]))
                say({"started": True})
            elif cmd == "mark":
                with broker.cond:
                    now = time.monotonic()
                    fetched, produced = list(broker.fetched), list(broker.produced)
                    fetches = broker.fetches
                say({
                    "t": now, "fetched": fetched, "produced": produced,
                    "fetches": fetches,
                    "backlog_min": broker.take_backlog_min(),
                    "late_ms": producer.take_late_ms(),
                    "chunks": producer.chunks,
                })
            elif cmd == "stop":
                say({"chunks": producer.stop()})
            elif cmd == "quit":
                break
            else:
                say({"error": f"unknown command {cmd!r}"})
    finally:
        producer.stop()
        broker.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
