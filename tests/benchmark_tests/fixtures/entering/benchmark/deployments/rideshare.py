"""A rideshare-shaped deployment, upstream's ``examples/kafka_rideshare.rs``
(mirrored by ``examples/kafka_rideshare.py``): nested IMU records, the
speed read through ``col("imu_measurement").field("gps").field("speed")``,
count, average and maximum of it by ``driver_id`` over a 5 s window sliding
by 1 s.  A record and a query that ``emit_measurements.py`` cannot serve,
entering the benchmark by new files alone.  Small sizes only: records are
written one at a time.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.harness import events, reference, wire

SAMPLE_EVENT = {
    "driver_id": "driver-0",
    "occurred_at_ms": 1,
    "imu_measurement": {
        "timestamp_ms": 1,
        "accelerometer": {"x": 0.0, "y": 0.0, "z": 0.0},
        "gyroscope": {"x": 0.0, "y": 0.0, "z": 0.0},
        "gps": {"latitude": 0.0, "longitude": 0.0, "altitude": 0.0, "speed": 0.0},
    },
    "meta": {"nonsense": "MORE NONSENSE"},
}
RECORD = (
    b'{"driver_id":"driver-%d","occurred_at_ms":%d,"imu_measurement":'
    b'{"timestamp_ms":%d,"accelerometer":{"x":0.0,"y":0.0,"z":0.0},'
    b'"gyroscope":{"x":0.0,"y":0.0,"z":0.0},"gps":{"latitude":0.0,'
    b'"longitude":0.0,"altitude":0.0,"speed":%d.%06d}},'
    b'"meta":{"nonsense":"MORE NONSENSE"}}'
)
COLUMNS = ["driver_id", "measurements", "avg_speed", "max_speed"]
MAX_SPEED_MICRO = 35_000_000  # speeds uniform in [0, 35) m/s, as upstream's
LIMITS = {"max_speed_mismatch": 0}


def encoder_params(config: dict, traffic: dict, base: dict) -> dict:
    return {**base, "drivers": config["drivers"],
            "records_per_batch": config["records_per_batch"]}


def chunk_arrays(params: dict, c: int):
    """(timestamps ms, driver ids, speeds in micro-units) of chunk ``c``."""
    rng = np.random.default_rng([params["seed"], c])
    n, chunk_ms = params["events_per_chunk"], params["chunk_ms"]
    ts = events.T0 + c * chunk_ms + np.sort(rng.integers(0, chunk_ms, n))
    driver = rng.integers(0, params["drivers"], n)
    speed = rng.integers(0, MAX_SPEED_MICRO, n)
    return ts, driver, speed


def encode_chunk(params: dict, c: int) -> list[bytes]:
    ts, driver, speed = chunk_arrays(params, c)
    n = params["partitions"]
    per = params["events_per_chunk"] // n
    r = params["records_per_batch"]
    first_ts = events.T0 + c * params["chunk_ms"]
    blobs = []
    for p in range(n):
        payloads = [
            RECORD % (d, t, t, s // 1_000_000, s % 1_000_000)
            for t, d, s in zip(ts[p::n].tolist(), driver[p::n].tolist(),
                               speed[p::n].tolist())
        ]
        blobs.append(b"".join(
            wire.encode_naive(c * per + i, first_ts, payloads[i:i + r])
            for i in range(0, per, r)
        ))
    return blobs


def build_stream(config: dict, bootstrap: str):
    from denormalized_tpu import Context, col
    from denormalized_tpu.api import functions as F
    from denormalized_tpu.api.context import EngineConfig

    q = config["query"]
    ctx = Context(EngineConfig(**config.get("engine", {})))
    speed = col("speed")
    ds = (
        ctx.from_topic(
            config["topic"], sample_json=json.dumps(SAMPLE_EVENT),
            bootstrap_servers=bootstrap, timestamp_column="occurred_at_ms",
        )
        .with_column("speed", col("imu_measurement").field("gps").field("speed"))
        .window(
            [col("driver_id")],
            [F.count(speed).alias("measurements"), F.avg(speed).alias("avg_speed"),
             F.max(speed).alias("max_speed")],
            q["length_ms"], q["slide_ms"],
        )
    )
    return ctx, ds


def kept_columns(config: dict) -> list[str]:
    return COLUMNS


def window_ms(config: dict) -> tuple[int, int]:
    return config["query"]["length_ms"], config["query"]["slide_ms"]


def _fold(params: dict, length: int, slide: int, start_ms: int, end_ms: int,
          precision):
    """count, avg and max per (window, driver) of the windows wholly inside
    ``[start_ms, end_ms)``, window by window, in ``precision``."""
    chunk_ms = params["chunk_ms"]
    lo = max(0, (start_ms - events.T0) // chunk_ms)
    hi = -(-(end_ms - events.T0) // chunk_ms)
    parts = [chunk_arrays(params, c) for c in range(lo, hi)]
    ts = np.concatenate([p[0] for p in parts])
    driver = np.concatenate([p[1] for p in parts])
    speed = (np.concatenate([p[2] for p in parts]) / 1e6).astype(precision)
    w0 = -(-start_ms // slide)
    starts = np.arange(w0, (end_ms - length) // slide + 1) * slide
    d = params["drivers"]
    count = np.zeros((len(starts), d))
    avg = np.full((len(starts), d), np.nan)
    top = np.full((len(starts), d), -np.inf)
    for w, ws in enumerate(starts.tolist()):
        inside = (ts >= ws) & (ts < ws + length)
        for k in range(d):
            x = speed[inside & (driver == k)]
            if len(x):
                count[w, k] = len(x)
                avg[w, k] = float(np.sum(x, dtype=precision) / precision(len(x)))
                top[w, k] = float(x.max())
    return w0, count.ravel(), avg.ravel(), top.ravel()


def _compare(fold, slide: int, d: int, ws, got) -> dict:
    w0, count, avg, top = fold
    ids = np.fromiter((int(k.rsplit("-", 1)[1]) for k in got["driver_id"]),
                      np.int64, len(ws))
    cells = (ws // slide - w0) * d + ids
    present = count > 0
    seen = np.zeros(len(count), bool)
    seen[cells] = True
    bad = present ^ seen
    off_count = np.asarray(got["measurements"], np.float64) != count[cells]
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.abs(np.asarray(got["avg_speed"], np.float64) - avg[cells]) / avg[cells]
    rel = np.where(np.isfinite(rel), rel, np.inf)
    off_max = (np.asarray(got["max_speed"], np.float32)
               != top[cells].astype(np.float32))
    bad[cells[off_count | (rel > reference.LIMITS["rel_err_max"]) | off_max]] = True
    return {
        "rows_compared": int(len(cells)),
        "missing_rows": int((present & ~seen).sum()),
        "unexpected_rows": int((seen & ~present).sum()),
        "count_mismatch": int(off_count.sum()),
        "rel_err_max": float(rel.max()) if len(rel) else 0.0,
        "max_speed_mismatch": int(off_max.sum()),
        "bad_windows": int(len(np.unique(np.flatnonzero(bad) // d))),
    }


def check(config: dict, params: dict, start_ms: int, end_ms: int, kept,
          control: bool = False) -> tuple[dict, dict | None]:
    length, slide = window_ms(config)
    d = params["drivers"]
    ref = _fold(params, length, slide, start_ms, end_ms, np.float64)
    ws, got = kept
    numbers = _compare(ref, slide, d, ws, got)
    if not control:
        return numbers, None
    # the control: the same fold in bfloat16, put in the engine's place
    from ml_dtypes import bfloat16

    w0, count, avg, top = _fold(params, length, slide, start_ms, end_ms, bfloat16)
    keep = np.flatnonzero(count > 0)
    rows = {"driver_id": np.array([f"driver-{k % d}" for k in keep.tolist()]),
            "measurements": count[keep], "avg_speed": avg[keep],
            "max_speed": top[keep]}
    return numbers, _compare(ref, slide, d, (w0 + keep // d) * slide, rows)
