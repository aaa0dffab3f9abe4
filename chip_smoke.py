"""Smoke test of the engine's main path on one TPU chip.

    python chip_smoke.py [--seed N] [--legs served,keyed,...] [--rehearsal]

One process, the entry points a user calls (``Context`` → ``DataStream`` →
planner → ``StreamingWindowExec``), at the sizes of the deployments
``BASELINE.json`` names, each leg checked against a plain numpy fold of the
same seeded events written in this file: counts and window/key sets exact,
min/max exact after f32 rounding, sum/avg relative 1e-5 (state is f32, the
reference f64).

Legs:

- ``served``  — upstream ``simple_aggregation`` over ``emit_measurements``:
  JSON over the Kafka wire (4 partitions of the in-process mock broker),
  ``ctx.from_topic`` → 1 s tumbling count/min/max/avg by sensor.
- ``keyed``   — 100,000 string keys, 1 s tumbling count/sum/min/max/avg
  from ``MemorySource``, under ``auto`` and under ``scatter``.
- ``sliding`` — 1 s window sliding by 200 ms with a post-aggregation
  filter, under ``auto``.
- ``restore`` — the keyed query with checkpointing, stopped after two
  committed epochs and restored through a fresh ``Context``: the union of
  both runs' emissions holds every reference window, and every emission of
  either run carries the reference's values (state is exactly-once,
  emission at-least-once).

It fails (non-zero exit, ``"ok": false``) when any leg is wrong or raised,
when a native library fell back to Python, and — before running anything,
printing no result — when JAX's platform is not ``tpu``.  ``--rehearsal``
shrinks every leg and accepts the CPU, so a test and a builder without a
chip run the same code path first; it says so in its output.  Times are
observations of a smoke run, not benchmark metrics.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from importlib import metadata

import numpy as np

T0 = 1_700_000_000_000  # event-time origin, ms
REL_TOL = 1e-5

# Sizes.  Full: BASELINE.json's deployments at their own widths, at 1M
# events per event-second.  Rehearsal: the same shapes a hundred times
# smaller.
FULL = {
    "rate": 1_000_000,
    "served": {"events": 4_000_000, "sensors": 10, "partitions": 4},
    "keyed": {"keys": 100_000, "rows": 8_000_000, "batch": 524_288,
              "capacity": 200_000},
    "sliding": {"keys": 10, "rows": 4_000_000, "batch": 131_072},
    "restore": {"batch": 131_072, "interval_s": 0.25},
    "deadline_s": 300.0,
}
REHEARSAL = {
    "rate": 10_000,
    "served": {"events": 40_000, "sensors": 10, "partitions": 4},
    "keyed": {"keys": 1_000, "rows": 80_000, "batch": 8_192,
              "capacity": 2_000},
    "sliding": {"keys": 10, "rows": 20_000, "batch": 1_024},
    "restore": {"batch": 2_048, "interval_s": 0.05},
    "deadline_s": 60.0,
}
LEGS = ("served", "keyed", "sliding", "restore")


# -- events and the plain reference ---------------------------------------


class Events:
    """One seeded feed: sorted event times, key ids and f64 readings."""

    def __init__(self, rng, n: int, rate: int, n_keys: int, prefix: str):
        self.ts = T0 + np.sort(rng.integers(0, n * 1000 // rate, n))
        self.kid = rng.integers(0, n_keys, n)
        # per-key means two apart, so a post-aggregation threshold splits
        # the keys with a margin far above any f32 rounding
        means = 40.0 + 2.0 * (np.arange(n_keys) % 10)
        self.reading = np.round(means[self.kid] + rng.normal(0, 10, n), 6)
        self.names = np.array(
            [f"{prefix}{i}" for i in range(n_keys)], dtype=object
        )
        self.key_col = self.names[self.kid]

    def batches(self, batch_rows: int):
        from denormalized_tpu.common.record_batch import RecordBatch
        from denormalized_tpu.common.schema import DataType, Field, Schema

        schema = Schema([
            Field("occurred_at_ms", DataType.INT64, nullable=False),
            Field("sensor_name", DataType.STRING, nullable=False),
            Field("reading", DataType.FLOAT64),
        ])
        return [
            RecordBatch(
                schema,
                [self.ts[a:a + batch_rows], self.key_col[a:a + batch_rows],
                 self.reading[a:a + batch_rows]],
            )
            for a in range(0, len(self.ts), batch_rows)
        ]

    def json_payloads(self) -> list[bytes]:
        """emit_measurements' wire format, one JSON object per event."""
        return [
            b'{"occurred_at_ms":%d,"sensor_name":"%s","reading":%r}'
            % (t, k.encode(), v)
            for t, k, v in zip(
                self.ts.tolist(), self.key_col.tolist(),
                self.reading.tolist(),
            )
        ]


class Reference:
    """f64 fold of one feed into every window it touches: a row at time t
    belongs to each window ``[j*slide, j*slide + length)`` that contains
    t.  Cells are (window, key) pairs, flat-indexed."""

    def __init__(self, ev: Events, length_ms: int, slide_ms: int):
        self.ev, self.length, self.slide = ev, length_ms, slide_ms
        self.n_keys = len(ev.names)
        unit = ev.ts // slide_ms
        fan = -(-length_ms // slide_ms)
        self.w0 = int(unit.min()) - fan + 1
        self.n_windows = int(unit.max()) - self.w0 + 1
        rows, wins = [], []
        for i in range(fan):
            j = unit - i
            inside = ev.ts < j * slide_ms + length_ms
            rows.append(np.flatnonzero(inside))
            wins.append(j[inside])
        self._rows = np.concatenate(rows)
        self._cell = (
            (np.concatenate(wins) - self.w0) * self.n_keys
            + ev.kid[self._rows]
        )
        self.n_cells = self.n_windows * self.n_keys
        self.rows_per_cell = np.bincount(self._cell, minlength=self.n_cells)
        self._fold: dict | None = None
        self._key_ids = {n: i for i, n in enumerate(ev.names.tolist())}

    def fold(self) -> dict:
        """count/sum/min/max/avg of ``reading`` per cell.  Readings are
        never null, so count == rows."""
        if self._fold is None:
            x = self.ev.reading[self._rows]
            total = np.bincount(self._cell, weights=x, minlength=self.n_cells)
            lo = np.full(self.n_cells, np.inf)
            hi = np.full(self.n_cells, -np.inf)
            np.minimum.at(lo, self._cell, x)
            np.maximum.at(hi, self._cell, x)
            with np.errstate(invalid="ignore", divide="ignore"):
                avg = total / self.rows_per_cell
            self._fold = {
                "count": self.rows_per_cell, "sum": total, "min": lo,
                "max": hi, "avg": avg,
            }
        return self._fold

    def cells_of(self, batch) -> np.ndarray:
        """Flat cell index of every emitted row (raises on a window or key
        the feed cannot have produced)."""
        ws = np.asarray(batch.column("window_start_time"), dtype=np.int64)
        we = np.asarray(batch.column("window_end_time"), dtype=np.int64)
        if (ws % self.slide).any() or ((we - ws) != self.length).any():
            raise AssertionError("emitted window bounds are off the grid")
        win = ws // self.slide - self.w0
        kid = np.fromiter(
            (self._key_ids[k] for k in batch.column("sensor_name")),
            np.int64, batch.num_rows,
        )
        if ((win < 0) | (win >= self.n_windows)).any():
            raise AssertionError("emitted a window outside the feed's span")
        return win * self.n_keys + kid

    def closable(self) -> np.ndarray:
        """Cell mask of windows an idle hint can close: it advances event
        time only to the largest timestamp seen."""
        j = self.w0 + np.arange(self.n_windows)
        ok = j * self.slide + self.length <= int(self.ev.ts.max())
        return np.repeat(ok, self.n_keys)


def compare(ref: Reference, batch, aggs, expected: np.ndarray,
            exact_set: bool = True) -> list[str]:
    """Problems found in one run's emitted rows.  ``aggs`` is
    ``[(output column, kind)]``; ``expected`` the mask of cells that
    must appear.  ``exact_set`` also forbids any other cell and any cell
    twice (a bounded run emits each window once)."""
    problems: list[str] = []
    cells = ref.cells_of(batch)
    if exact_set:
        if len(np.unique(cells)) != len(cells):
            problems.append("a (window, key) pair was emitted twice")
        extra = np.setdiff1d(cells, np.flatnonzero(expected))
        if len(extra):
            problems.append(f"{len(extra)} unexpected (window, key) pairs")
    missing = np.setdiff1d(np.flatnonzero(expected), cells)
    if len(missing):
        problems.append(
            f"{len(missing)} of {int(expected.sum())} expected (window, key)"
            " pairs missing"
        )
    for name, kind in aggs:
        got = np.asarray(batch.column(name), dtype=np.float64)
        want = ref.fold()[kind][cells]
        if kind == "count":
            bad = got != want
        elif kind in ("min", "max"):
            bad = got.astype(np.float32) != want.astype(np.float32)
        else:
            bad = ~(np.abs(got - want) <= REL_TOL * np.abs(want))
        if bad.any():
            i = int(np.flatnonzero(bad)[0])
            problems.append(
                f"{name}: {int(bad.sum())} of {len(got)} values off "
                f"(first: got {got[i]!r}, reference {want[i]!r})"
            )
    return problems


# -- driving the engine ---------------------------------------------------


def operator_metrics(ctx, having: str) -> dict:
    """Metrics of the last plan's operator that reports ``having``."""
    from denormalized_tpu.runtime.tracing import collect_metrics

    ops = collect_metrics(ctx._last_physical).values()
    return next(m for m in ops if having in m)


def report(leg: str, ctx, t_ctx: float, t_end: float, rows: int,
           windows: int, problems: list[str], want_strategy: str,
           **extra) -> dict:
    """Print one leg's line: what ran, whether it was right, and how long
    set-up (context creation to the first batch accepted: plan build,
    prewarm ladders, restore) and the stream after it took."""
    m = operator_metrics(ctx, "strategy_resolved")
    problems = list(problems)
    if m["strategy_resolved"] != want_strategy:
        problems.append(
            f"strategy_resolved {m['strategy_resolved']!r}, wanted "
            f"{want_strategy!r}"
        )
    if not m["device_steps"] > 0:
        problems.append("no device step ran")
    if m["late_rows"]:
        problems.append(f"{m['late_rows']} rows dropped as late")
    first = m.get("first_batch_at", t_end)
    line = {
        "leg": leg, "ok": not problems, "rows": rows,
        "rows_in": m["rows_in"], "windows": windows,
        "strategy_resolved": m["strategy_resolved"],
        "device_steps": m["device_steps"],
        "bytes_h2d": m["bytes_h2d"], "bytes_d2h": m["bytes_d2h"],
        "setup_s": round(first - t_ctx, 3),
        "stream_s": round(t_end - first, 3),
        **extra,
    }
    if problems:
        line["problems"] = problems
    print(json.dumps(line), flush=True)
    return line


def aggregates(*named):
    """Aggregates of ``reading``, one per ``(output column, kind)``: the
    engine's expressions and the matching ``compare`` specs."""
    from denormalized_tpu import col
    from denormalized_tpu.api import functions as F

    r = col("reading")
    return (
        [getattr(F, kind)(r).alias(name) for name, kind in named],
        list(named),
    )


# upstream simple_aggregation.rs, and BASELINE config 3 with every
# accumulator kind
SIMPLE_AGGS = (("count", "count"), ("min", "min"), ("max", "max"),
               ("average", "avg"))
KEYED_AGGS = (("count", "count"), ("sum", "sum"), ("min", "min"),
              ("max", "max"), ("avg", "avg"))


def memory_stream(ctx, batches, name):
    from denormalized_tpu.sources.memory import MemorySource

    return ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="occurred_at_ms"),
        name=name,
    )


def leg_served(size, rng) -> list[dict]:
    from denormalized_tpu import Context
    from denormalized_tpu.api.context import EngineConfig
    from denormalized_tpu.common.record_batch import RecordBatch
    from denormalized_tpu.testing.mock_kafka import MockKafkaBroker

    cfg = size["served"]
    t_gen = time.perf_counter()
    ev = Events(rng, cfg["events"], size["rate"], cfg["sensors"], "sensor_")
    ref = Reference(ev, 1000, 1000)
    must = ref.closable() & (ref.rows_per_cell > 0)
    payloads = ev.json_payloads()
    broker = MockKafkaBroker().start()
    try:
        parts = cfg["partitions"]
        broker.create_topic("emit_measurements", partitions=parts)
        for p in range(parts):
            # interleaved, so every partition spans the same event time
            broker.produce_batched("emit_measurements", p, payloads[p::parts])
        gen_s = time.perf_counter() - t_gen

        t_ctx = time.perf_counter()
        # the default engine, plus the idleness policy that lets the
        # windows of a topic gone quiet close at all
        ctx = Context(EngineConfig(source_idle_timeout_ms=1000))
        exprs, aggs = aggregates(*SIMPLE_AGGS)
        ds = ctx.from_topic(
            "emit_measurements",
            sample_json='{"occurred_at_ms": 100, "sensor_name": "foo", '
                        '"reading": 0.0}',
            bootstrap_servers=broker.bootstrap,
            timestamp_column="occurred_at_ms",
        ).window(["sensor_name"], exprs, 1000)
        out: list = []
        failure: list = []

        def drain():
            # an unbounded topic never ends: stop once every closable
            # window arrived.  Only this thread may close the iterator.
            seen = np.zeros(ref.n_cells, bool)
            it = ds.stream()
            try:
                for b in it:
                    out.append(b)
                    seen[ref.cells_of(b)] = True
                    if seen[must].all():
                        break
            except Exception:  # noqa: BLE001 — reported by the caller
                failure.append(traceback.format_exc())
            finally:
                it.close()

        th = threading.Thread(target=drain, daemon=True)
        th.start()
        th.join(size["deadline_s"])
        t_end = time.perf_counter()
        problems = list(failure)
        if th.is_alive():
            problems.append(
                f"closable windows still missing after {size['deadline_s']}s"
            )
            broker.stop()  # unsticks the readers so the thread can end
            th.join(10.0)
        if not out:
            raise AssertionError(f"served emitted nothing: {problems}")
        got = RecordBatch.concat(out)
        problems += compare(ref, got, aggs, must)
        fallback = operator_metrics(ctx, "decode_fallback_rows")
        if fallback["decode_fallback_rows"]:
            problems.append("rows decoded on the Python fallback path")
        return [report(
            "served", ctx, t_ctx, t_end, cfg["events"],
            len(np.unique(got.column("window_start_time"))), problems,
            "partial_merge", gen_s=round(gen_s, 3), partitions=parts,
            decode_fallback_rows=fallback["decode_fallback_rows"],
        )]
    finally:
        broker.stop()


def run_bounded(leg, config, ev, ref, batch_rows, build, aggs, expected,
                want_strategy) -> dict:
    from denormalized_tpu import Context

    batches = ev.batches(batch_rows)
    t_ctx = time.perf_counter()
    ctx = Context(config)
    ds = build(memory_stream(ctx, batches, leg.replace("/", "_")))
    got = ds.collect()
    t_end = time.perf_counter()
    return report(
        leg, ctx, t_ctx, t_end, len(ev.ts),
        len(np.unique(got.column("window_start_time"))),
        compare(ref, got, aggs, expected), want_strategy,
    )


def leg_keyed(size, rng, keep: dict) -> list[dict]:
    from denormalized_tpu.api.context import EngineConfig

    cfg = size["keyed"]
    ev = Events(rng, cfg["rows"], size["rate"], cfg["keys"], "key_")
    ref = Reference(ev, 1000, 1000)
    keep["keyed"] = (ev, ref)  # the restore leg replays the same feed
    exprs, aggs = aggregates(*KEYED_AGGS)
    lines = []
    for strategy, want in (("auto", "partial_merge"),
                           ("scatter", "row_shipping:scatter")):
        lines.append(run_bounded(
            f"keyed/{strategy}",
            EngineConfig(min_group_capacity=cfg["capacity"],
                         device_strategy=strategy),
            ev, ref, cfg["batch"],
            lambda ds: ds.window(["sensor_name"], exprs, 1000),
            aggs, ref.rows_per_cell > 0, want,
        ))
    return lines


def leg_sliding(size, rng) -> list[dict]:
    from denormalized_tpu import col
    from denormalized_tpu.api.context import EngineConfig

    cfg = size["sliding"]
    ev = Events(rng, cfg["rows"], size["rate"], cfg["keys"], "sensor_")
    ref = Reference(ev, 1000, 200)
    exprs, aggs = aggregates(("cnt", "count"), ("avg", "avg"))
    passes = (ref.rows_per_cell > 0) & (ref.fold()["avg"] > 45.0)
    return [run_bounded(
        "sliding/auto", EngineConfig(device_strategy="auto"),
        ev, ref, cfg["batch"],
        lambda ds: ds.window(["sensor_name"], exprs, 1000, 200)
        .filter(col("avg") > 45.0),
        aggs, passes, "partial_merge",
    )]


def leg_restore(size, rng, keep: dict) -> list[dict]:
    from denormalized_tpu import Context
    from denormalized_tpu.api.context import EngineConfig
    from denormalized_tpu.common.record_batch import RecordBatch
    from denormalized_tpu.state.lsm import close_global_state_backend

    if "keyed" not in keep:
        cfg = size["keyed"]
        ev = Events(rng, cfg["rows"], size["rate"], cfg["keys"], "key_")
        keep["keyed"] = (ev, Reference(ev, 1000, 1000))
    ev, ref = keep["keyed"]
    cfg = size["restore"]
    batches = ev.batches(cfg["batch"])
    exprs, aggs = aggregates(*KEYED_AGGS)
    expected = ref.rows_per_cell > 0
    nothing = np.zeros_like(expected)  # a partial run owes no window
    state_dir = tempfile.mkdtemp(prefix="chip_smoke_state_")

    def config(**over):
        return EngineConfig(
            min_group_capacity=size["keyed"]["capacity"], checkpoint=True,
            state_backend_path=state_dir, **over,
        )

    try:
        # run A: stop mid-stream once two epochs are committed.  The feed
        # replays at full speed and barriers come on the wall clock, so
        # the cadence is short and the pull loop pauses between windows.
        # emit_lag_ms=0: on an accelerator the default 200 ms emission
        # deferral is timed from the stripe's first row, and a snapshot
        # after every batch empties the stripe — no window would be
        # emitted, and this loop given no chance to stop, before the
        # feed's end (seen on the v5e; PERF.md, PR 21).
        t_ctx = time.perf_counter()
        ctx = Context(config(checkpoint_interval_s=cfg["interval_s"],
                             emit_lag_ms=0))
        ds = memory_stream(ctx, batches, "restore").window(
            ["sensor_name"], exprs, 1000
        )
        out_a, epochs = [], 0
        it = ds.stream()
        for b in it:
            out_a.append(b)
            epochs = len(ctx._last_coord.committed_history)
            if epochs >= 2:
                break
            time.sleep(cfg["interval_s"])
        it.close()
        t_end = time.perf_counter()
        if epochs < 2:
            raise AssertionError(
                f"the feed ended with {epochs} committed epochs; the "
                "restore leg needs two before its cut"
            )
        got_a = RecordBatch.concat(out_a)
        line_a = report(
            "restore/run", ctx, t_ctx, t_end, len(ev.ts),
            len(np.unique(got_a.column("window_start_time"))),
            compare(ref, got_a, aggs, nothing, exact_set=False),
            "partial_merge", committed_epochs=epochs,
        )
        close_global_state_backend()

        # run B: a fresh Context on the same state path resumes from the
        # committed cut and runs to the end of the feed, with the default
        # deferral and the bench's barrier cadence (each snapshot of the
        # full ring is ~64 MB)
        t_ctx = time.perf_counter()
        ctx = Context(config(checkpoint_interval_s=2.0))
        ds = memory_stream(ctx, batches, "restore").window(
            ["sensor_name"], exprs, 1000
        )
        # stream() again: checkpoint keys are plan node ids, and collect()
        # would put a sink node at the root and shift them all
        got_b = RecordBatch.concat(list(ds.stream()))
        t_end = time.perf_counter()
        problems = compare(ref, got_b, aggs, nothing, exact_set=False)
        union = RecordBatch.concat([got_a, got_b])
        # every emission of either run was value-checked above; together
        # they must cover the reference
        problems += compare(ref, union, [], expected, exact_set=False)
        rows_in = operator_metrics(ctx, "strategy_resolved")["rows_in"]
        if not rows_in < len(ev.ts):
            # a run that restored nothing starts over and reads them all
            problems.append(
                f"the restored run read all {len(ev.ts)} rows: it did not "
                "resume from the cut"
            )
        line_b = report(
            "restore/restored", ctx, t_ctx, t_end, len(ev.ts),
            len(np.unique(got_b.column("window_start_time"))), problems,
            "partial_merge",
        )
        return [line_a, line_b]
    finally:
        close_global_state_backend()
        shutil.rmtree(state_dir, ignore_errors=True)


# -- the installation ------------------------------------------------------


def native_libraries() -> dict:
    """Load (building from source where needed) every native library the
    legs use; False marks one whose caller would run its Python fallback."""
    from denormalized_tpu.formats import native_json
    from denormalized_tpu.obs import statewatch
    from denormalized_tpu.ops import host_partial, interner
    from denormalized_tpu.sources import kafka
    from denormalized_tpu.state import lsm

    def loads(fn) -> bool:
        try:
            return fn() is not None
        except Exception:  # noqa: BLE001 — a failed build is the finding
            traceback.print_exc()
            return False

    intern_lib, intern_pyobjects = interner._load_native()
    return {
        "partial_agg": loads(host_partial._native),
        "interner": intern_lib is not None,
        "interner_pyobjects": intern_pyobjects is not None,
        "json_parser": loads(native_json._lib),
        "kafka_client": loads(kafka._lib),
        "lsmkv": loads(lsm._load_native),
        "sketch_update": loads(statewatch._native),
    }


def count_files(path: str | None) -> int | None:
    if not path or not os.path.isdir(path):
        return None
    return sum(len(files) for _, _, files in os.walk(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of {','.join(LEGS)}")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes, any platform: a dry run of this "
                         "script, not a check of the chip")
    args = ap.parse_args(argv)
    legs = args.legs.split(",")
    unknown = set(legs) - set(LEGS)
    if unknown:
        ap.error(f"unknown legs {sorted(unknown)}")

    import jax

    from denormalized_tpu.api.context import enable_compilation_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearsal:
        print(
            f"chip_smoke.py needs a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}).  --rehearsal runs a "
            "shrunken dry run on any platform.",
            file=sys.stderr,
        )
        return 2
    size = REHEARSAL if args.rehearsal else FULL
    t_start = time.perf_counter()
    cache_dir = enable_compilation_cache()
    native = native_libraries()
    print(json.dumps({
        "chip_smoke": "rehearsal (shrunken sizes; proves the script, not "
                      "the chip)" if args.rehearsal else "full",
        "seed": args.seed, "device": device,
        "jax": jax.__version__, "jaxlib": metadata.version("jaxlib"),
        "libtpu": _version_or_none("libtpu"),
        "cache_dir": cache_dir, "cache_files": count_files(cache_dir),
        "native": native,
        "native_build_s": round(time.perf_counter() - t_start, 3),
    }), flush=True)

    keep: dict = {}
    runners = {
        "served": lambda rng: leg_served(size, rng),
        "keyed": lambda rng: leg_keyed(size, rng, keep),
        "sliding": lambda rng: leg_sliding(size, rng),
        "restore": lambda rng: leg_restore(size, rng, keep),
    }
    failed = [f"native:{k}" for k, ok in native.items() if not ok]
    for i, leg in enumerate(LEGS):
        if leg not in legs:
            continue
        # a leg's feed depends on the seed and the leg, not on which
        # other legs ran
        rng = np.random.default_rng([args.seed, i])
        try:
            lines = runners[leg](rng)
        except Exception:  # noqa: BLE001 — a leg that raised is a failed leg
            traceback.print_exc()
            print(json.dumps({"leg": leg, "ok": False,
                              "problems": ["raised; traceback on stderr"]}),
                  flush=True)
            failed.append(leg)
            continue
        failed += [ln["leg"] for ln in lines if not ln["ok"]]

    result = {"ok": not failed}
    if failed:
        result["failed"] = failed
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps({
        "cache_files": count_files(cache_dir),
        "total_s": round(time.perf_counter() - t_start, 3),
    }), flush=True)
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0 if not failed else 1


def _version_or_none(dist: str) -> str | None:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
