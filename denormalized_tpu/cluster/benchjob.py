"""Packaged cluster job factory for tools/soak.py (--pipeline cluster).

Worker processes import this by name ("denormalized_tpu.cluster.
benchjob:soak_job"), so the factory must rebuild the identical
deterministic source from job_args alone — the same contract as the
test jobs (tests/cluster_jobs.py), packaged so the committed artifact
(SOAK_CLUSTER.json) never depends on the test tree.

The job uses string keys (the crc32 lane) and integer-valued readings so
every aggregate is exact in f32 accumulators regardless of exchange
arrival order — the property the exactly-once comparison needs
(docs/cluster.md#determinism).
"""

from __future__ import annotations

import time

import numpy as np

from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.sources.base import (
    PartitionReader,
    Source,
    attach_canonical_timestamp,
    canonicalize_schema,
)

T0 = 1_700_000_000_000

SOAK_SCHEMA = Schema([
    Field("k", DataType.STRING, nullable=False),
    Field("v", DataType.FLOAT64, nullable=False),
    Field("ts", DataType.TIMESTAMP_MS, nullable=False),
])


class _SynthReader(PartitionReader):
    """Deterministic batch generator: in-order timestamps, keys spread
    over the key space, integer readings.  Seekable (pos-based) so
    checkpoint restore replays exactly."""

    def __init__(self, part: int, args: dict) -> None:
        self.part = part
        self.args = args
        self._pos = 0
        self._n = int(args.get("batches", 50))
        self._pace_s = float(args.get("pace_s", 0.0))

    def _batch(self, b: int) -> RecordBatch:
        a = self.args
        rows = int(a.get("rows", 8192))
        keys = int(a.get("keys", 1024))
        span = int(a.get("batch_span_ms", 250))
        base = T0 + b * span
        i = np.arange(rows, dtype=np.int64)
        ts = base + (i * span) // rows
        kid = (i * 7 + self.part * 3 + b) % keys
        v = ((i + self.part + b) % 16).astype(np.float64)
        k = np.array([f"s{x:05d}" for x in kid], dtype=object)
        return RecordBatch(SOAK_SCHEMA, [k, v, ts])

    def read(self, timeout_s=None):
        if self._pos >= self._n:
            return None
        if self._pace_s:
            time.sleep(self._pace_s)
        b = self._batch(self._pos)
        self._pos += 1
        return attach_canonical_timestamp(b, "ts", fallback_ms=0)

    def offset_snapshot(self) -> dict:
        return {"pos": self._pos}

    def offset_restore(self, snap: dict) -> None:
        self._pos = int(snap.get("pos", 0))


class SynthSource(Source):
    def __init__(self, args: dict) -> None:
        self._args = dict(args)
        self.name = "cluster_soak"
        self._schema = canonicalize_schema(SOAK_SCHEMA)

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def unbounded(self) -> bool:
        return False

    def partitions(self) -> list[PartitionReader]:
        return [
            _SynthReader(p, self._args)
            for p in range(int(self._args.get("partitions", 4)))
        ]


def _pipeline(ds, args: dict):
    from denormalized_tpu import col
    from denormalized_tpu.api import functions as F

    return ds.window(
        [col("k")],
        [
            F.count(col("v")).alias("count"),
            F.sum(col("v")).alias("total"),
            F.min(col("v")).alias("lo"),
            F.max(col("v")).alias("hi"),
        ],
        int(args.get("window_ms", 1000)),
    )


def soak_job(args: dict) -> dict:
    return {
        "source": SynthSource(args),
        "pipeline": lambda ds: _pipeline(ds, args),
        "engine": args.get("engine") or {},
    }


def oracle_rows(args: dict) -> list[tuple]:
    """Uninterrupted single-process oracle → canonical sorted tuples."""
    from denormalized_tpu.api.context import Context, EngineConfig
    from denormalized_tpu.common.constants import (
        WINDOW_END_COLUMN,
        WINDOW_START_COLUMN,
    )

    config = EngineConfig()
    config.partition_watermarks = True
    ctx = Context(config)
    src = SynthSource(args)
    got = _pipeline(ctx.from_source(src), args).collect()
    out = []
    for i in range(got.num_rows):
        out.append((
            int(got.column(WINDOW_START_COLUMN)[i]),
            int(got.column(WINDOW_END_COLUMN)[i]),
            str(got.column("k")[i]),
            int(got.column("count")[i]),
            float(got.column("total")[i]),
            float(got.column("lo")[i]),
            float(got.column("hi")[i]),
        ))
    return sorted(out)


def canonical_row(rec: dict) -> tuple:
    from denormalized_tpu.common.constants import (
        WINDOW_END_COLUMN,
        WINDOW_START_COLUMN,
    )

    return (
        int(rec[WINDOW_START_COLUMN]),
        int(rec[WINDOW_END_COLUMN]),
        str(rec["k"]),
        int(rec["count"]),
        float(rec["total"]),
        float(rec["lo"]),
        float(rec["hi"]),
    )
