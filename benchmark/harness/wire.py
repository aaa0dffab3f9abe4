"""Kafka magic-2 record batches of fixed-width JSON records.

Two encoders of the same bytes.  ``encode_naive`` builds one record at a
time the way ``denormalized_tpu/testing/mock_kafka.py`` does (copied from
it, with its ``parse_record_batches``); it is the plain encoder the tests
hold the fast one to.  ``Layout.encode`` fills numpy digit arithmetic into
a byte template, a whole chunk at once and no Python per record: a run
serves tens of millions of records and the feeder has to stay ahead of the
engine.  numpy only.

A record is ``{"occurred_at_ms":<13 digits>,"sensor_name":"<prefix><W
digits>","reading":DD.dddddd}``: every record of a feed has the same
length, so every batch of ``r`` records has the same byte layout.
"""

from __future__ import annotations

import struct

import numpy as np

BATCH_HEADER = 61
TS_DIGITS = 13
READING_DIGITS = 8  # DD.dddddd as an integer of micro-units


def zigzag(n: int) -> bytes:
    z = ((n << 1) ^ (n >> 63)) & ((1 << 70) - 1)
    out = bytearray()
    while z >= 0x80:
        out.append((z & 0x7F) | 0x80)
        z >>= 7
    out.append(z)
    return bytes(out)


def _zigzag_dec(buf, pos: int) -> tuple[int, int]:
    acc = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        acc |= (b & 0x7F) << shift
        if not b & 0x80:
            return (acc >> 1) ^ -(acc & 1), pos
        shift += 7


def payload_of(ts: int, key: str, micro: int) -> bytes:
    """One record's JSON, written the plain way."""
    return (
        b'{"occurred_at_ms":%d,"sensor_name":"%s","reading":%d.%06d}'
        % (ts, key.encode(), micro // 1_000_000, micro % 1_000_000)
    )


def encode_naive(base_offset: int, first_ts: int, payloads: list[bytes]) -> bytes:
    """One magic-2 batch, record by record (CRC left 0: the native client,
    like a broker on read, trusts the transport)."""
    recs = bytearray()
    for i, payload in enumerate(payloads):
        rec = (
            b"\x00" + zigzag(0) + zigzag(i) + zigzag(-1)
            + zigzag(len(payload)) + payload + zigzag(0)
        )
        recs += zigzag(len(rec)) + rec
    body = struct.pack(
        ">hiqqqhii", 0, len(payloads) - 1, first_ts, first_ts, -1, -1, -1,
        len(payloads),
    ) + bytes(recs)
    return (
        struct.pack(">qiib", base_offset, len(body) + 9, -1, 2)
        + struct.pack(">I", 0) + body
    )


def parse_record_batches(blob: bytes) -> list[tuple[int, int, bytes]]:
    """magic-2 batches → [(offset, timestamp_ms, payload)]."""
    out = []
    mv = memoryview(blob)
    pos = 0
    while pos + BATCH_HEADER <= len(blob):
        base_offset, batch_len, _epoch, magic = struct.unpack_from(
            ">qiib", mv, pos
        )
        if magic != 2:
            raise ValueError(f"magic {magic} at byte {pos}")
        batch_end = pos + 12 + batch_len
        _attrs, _lod, first_ts, _max, _pid, _pep, _seq, nrec = (
            struct.unpack_from(">hiqqqhii", mv, pos + 21)
        )
        p = pos + BATCH_HEADER
        for _ in range(nrec):
            rec_len, p = _zigzag_dec(mv, p)
            rec_end = p + rec_len
            p += 1  # attributes
            ts_delta, p = _zigzag_dec(mv, p)
            off_delta, p = _zigzag_dec(mv, p)
            klen, p = _zigzag_dec(mv, p)
            p += max(klen, 0)
            vlen, p = _zigzag_dec(mv, p)
            out.append(
                (base_offset + off_delta, first_ts + ts_delta,
                 bytes(mv[p:p + vlen]))
            )
            p = rec_end
        if p != batch_end:
            raise ValueError("records do not fill the batch")
        pos = batch_end
    if pos != len(blob):
        raise ValueError("trailing bytes after the last batch")
    return out


_LUT4 = np.array(
    [b"%04d" % i for i in range(10_000)], dtype="S4"
).view(np.uint8).reshape(10_000, 4)
_LUT4_WORDS = _LUT4.view(np.uint32).reshape(10_000)


def digits(x: np.ndarray, width: int) -> np.ndarray:
    """ASCII decimal digits of non-negative ``x``, zero-padded to
    ``width``: shape ``x.shape + (width,)``, four digits a division and
    one table lookup."""
    groups = -(-width // 4)
    out = np.empty(x.shape + (groups,), np.uint32)
    for g in range(groups - 1, -1, -1):
        x, r = np.divmod(x, 10_000)
        out[..., g] = _LUT4_WORDS[r]
    return out.view(np.uint8)[..., 4 * groups - width:]


class Layout:
    """Byte layout of a batch of ``r`` fixed-width records, and the
    vectorised fill.  Records of one batch differ in header width only
    through the varint of their offset delta, so they fall into a few
    groups of equal size; each group is filled through one strided view."""

    def __init__(self, key_prefix: str, key_width: int, r: int):
        self.r = r
        self.key_width = key_width
        head = b'{"occurred_at_ms":'
        mid = b',"sensor_name":"' + key_prefix.encode()
        tail = b'","reading":'
        template = (
            head + b"0" * TS_DIGITS + mid + b"0" * key_width + tail
            + b"00.000000}"
        )
        self.payload_len = len(template)
        self._ts_at = len(head)
        self._key_at = self._ts_at + TS_DIGITS + len(mid)
        self._int_at = self._key_at + key_width + len(tail)
        self._frac_at = self._int_at + 3
        # groups of records with the same header: (first, last+1, byte
        # offset of the group within the batch, record size, payload offset)
        self.groups = []
        parts = [b"\x00" * BATCH_HEADER]
        at = BATCH_HEADER
        i = 0
        ts_pos = []
        while i < r:
            width = len(zigzag(i))
            j = i
            while j < r and len(zigzag(j)) == width:
                j += 1
            recs = []
            for k in range(i, j):
                body_head = (
                    b"\x00" + zigzag(0) + zigzag(k) + zigzag(-1)
                    + zigzag(self.payload_len)
                )
                rec_len = len(body_head) + self.payload_len + 1
                recs.append(
                    zigzag(rec_len) + body_head + template + zigzag(0)
                )
            size = len(recs[0])
            pay = size - 1 - self.payload_len
            self.groups.append((i, j, at, size, pay))
            ts_pos += [at + n * size + pay + self._ts_at for n in range(j - i)]
            parts.append(b"".join(recs))
            at += size * (j - i)
            i = j
        self.batch_len = at
        blob = bytearray(b"".join(parts))
        struct.pack_into(">qiib", blob, 0, 0, at - 12, -1, 2)
        struct.pack_into(
            ">Ihiqqqhii", blob, 17, 0, 0, r - 1, 0, 0, -1, -1, -1, r
        )
        self._template = np.frombuffer(bytes(blob), np.uint8)
        # where each record's nine high timestamp digits go
        self._ts_high = (
            np.array(ts_pos)[:, None] + np.arange(TS_DIGITS - 4)[None, :]
        )

    def encode(self, base_offset: int, first_ts: int, ts: np.ndarray,
               kid: np.ndarray, micro: np.ndarray) -> np.ndarray:
        """``len(ts) // r`` whole batches as one uint8 array.  All of
        ``ts`` share their digits above the lowest four with ``first_ts``
        (a chunk never crosses a multiple of ten seconds)."""
        r = self.r
        nb, rest = divmod(len(ts), r)
        if rest or nb == 0:
            raise ValueError(f"{len(ts)} records are not whole batches of {r}")
        high = first_ts // 10_000
        low = ts - high * 10_000
        if low.min() < 0 or low.max() >= 10_000:
            raise ValueError("timestamps leave the ten seconds of first_ts")
        batch = self._template.copy()
        batch[self._ts_high] = digits(np.array([high]), TS_DIGITS - 4)[0]
        struct.pack_into(">qq", batch.data, 27, first_ts, first_ts)
        out = np.tile(batch, nb).reshape(nb, self.batch_len)
        bases = base_offset + r * np.arange(nb, dtype=np.int64)
        out[:, 0:8] = bases.astype(">i8").view(np.uint8).reshape(nb, 8)
        d_ts = digits(low, 4).reshape(nb, r, 4)
        d_key = digits(kid, self.key_width).reshape(nb, r, self.key_width)
        d_val = digits(micro, READING_DIGITS).reshape(nb, r, READING_DIGITS)
        for i, j, at, size, pay in self.groups:
            view = out[:, at:at + size * (j - i)].reshape(nb, j - i, size)
            a = pay + self._ts_at + TS_DIGITS - 4
            view[:, :, a:a + 4] = d_ts[:, i:j]
            a = pay + self._key_at
            view[:, :, a:a + self.key_width] = d_key[:, i:j]
            a = pay + self._int_at
            view[:, :, a:a + 2] = d_val[:, i:j, 0:2]
            a = pay + self._frac_at
            view[:, :, a:a + 6] = d_val[:, i:j, 2:8]
        return out.reshape(-1)


class Encoder:
    """Encodes one partition's share of a chunk: whole batches of
    ``records_per_batch`` and one shorter batch for the rest."""

    def __init__(self, key_prefix: str, key_width: int, records_per_batch: int):
        self._prefix, self._width = key_prefix, key_width
        self.r = records_per_batch
        self._layouts: dict[int, Layout] = {}

    def _layout(self, r: int) -> Layout:
        if r not in self._layouts:
            self._layouts[r] = Layout(self._prefix, self._width, r)
        return self._layouts[r]

    def encode(self, base_offset: int, first_ts: int, ts, kid, micro) -> bytes:
        n = len(ts)
        whole = n - n % self.r
        parts = []
        if whole:
            parts.append(self._layout(self.r).encode(
                base_offset, first_ts, ts[:whole], kid[:whole], micro[:whole]
            ))
        if n > whole:
            parts.append(self._layout(n - whole).encode(
                base_offset + whole, first_ts, ts[whole:], kid[whole:],
                micro[whole:],
            ))
        return b"".join(part.tobytes() for part in parts)
