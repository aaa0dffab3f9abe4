"""Seeded events, chunk by chunk.  numpy only: the feeder process and the
parent both import this, and neither may pull JAX in through it.

Chunk ``c`` of a feed covers event time ``[T0 + c*chunk_ms, T0 +
(c+1)*chunk_ms)`` and draws from ``default_rng([seed, c])``, so any chunk
can be made again alone: the feeder makes it to serve it, the parent makes
it again, after the window, to fold the reference over it.  Every seed
gives the same sizes and arrival times; only keys and readings differ.
"""

from __future__ import annotations

import dataclasses

import numpy as np

T0 = 1_700_000_000_000  # event-time origin, ms; a multiple of every window

# readings are fixed-width decimals DD.dddddd, carried as integer micro-units
MICRO_LO, MICRO_HI = 10_000_000, 99_999_999


@dataclasses.dataclass(frozen=True)
class Feed:
    """What the generator needs: sizes from the configuration and the
    cell's traffic file, and the seed."""

    seed: int
    n_keys: int
    key_prefix: str
    partitions: int
    chunk_ms: int
    events_per_chunk: int
    records_per_batch: int = 512

    def __post_init__(self):
        if self.events_per_chunk % self.partitions:
            raise ValueError(
                f"{self.events_per_chunk} events a chunk do not split over "
                f"{self.partitions} partitions"
            )
        if 1000 % self.chunk_ms:
            raise ValueError(f"chunk_ms {self.chunk_ms} does not divide 1000")

    @property
    def key_width(self) -> int:
        return len(str(self.n_keys - 1))

    @property
    def events_per_second(self) -> int:
        """Event density: events per second of event time."""
        return self.events_per_chunk * (1000 // self.chunk_ms)

    def key_names(self) -> np.ndarray:
        w = self.key_width
        return np.array(
            [f"{self.key_prefix}{i:0{w}d}" for i in range(self.n_keys)],
            dtype=object,
        )


def chunk_arrays(feed: Feed, c: int):
    """(timestamps ms, key ids, readings in micro-units) of chunk ``c``,
    sorted by time.  Partition ``p`` carries rows ``p::partitions``, so each
    partition is in order and all span the same event time."""
    rng = np.random.default_rng([feed.seed, c])
    n = feed.events_per_chunk
    ts = T0 + c * feed.chunk_ms + np.sort(rng.integers(0, feed.chunk_ms, n))
    kid = rng.integers(0, feed.n_keys, n)
    # per-key means two apart (chip_smoke.py's), so a post-aggregation
    # threshold between two means splits the keys with a margin
    micro = np.rint(
        _MEAN_MICRO[kid % 10] + rng.standard_normal(n) * 10e6
    ).astype(np.int64)
    np.clip(micro, MICRO_LO, MICRO_HI, out=micro)
    return ts, kid, micro


_MEAN_MICRO = (40.0 + 2.0 * np.arange(10)) * 1e6


def reading_of(micro: np.ndarray) -> np.ndarray:
    """The f64 a correct decimal parser gives for ``DD.dddddd``."""
    return micro / 1e6


def chunks_covering(feed: Feed, start_ms: int, end_ms: int) -> range:
    """Indices of the chunks holding event times in ``[start_ms, end_ms)``."""
    lo = max(0, (start_ms - T0) // feed.chunk_ms)
    hi = max(lo, -(-(end_ms - T0) // feed.chunk_ms))
    return range(lo, hi)
