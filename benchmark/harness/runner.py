"""One run of one cell: feeder, engine, warm-up, measured window, and the
comparison with the reference.

The entry the window drives is the user's: ``Context(EngineConfig(...))``
→ ``ctx.from_topic(...)`` → ``.window(...)`` [→ ``.filter(...)``] →
``ds.stream()``, consumed on a thread of its own.  Everything the harness
measures it takes itself (host clock, the feeder's offsets, the profiler's
trace) or reads by name from the engine's counters; the engine is given
nothing but the broker's address.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import select
import shutil
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

from benchmark.harness import events, lastline, manifest, reference, trace_reduce

SAMPLE_JSON = '{"occurred_at_ms": 100, "sensor_name": "foo", "reading": 0.0}'
OUT_DIR = os.path.join(manifest.ROOT, ".bench_out")


class RunFailed(RuntimeError):
    """The run cannot give a result line: exit non-zero, print none."""


# -- the feeder process ---------------------------------------------------


class Feeder:
    """The feeder as a child process, spoken to over its pipes."""

    def __init__(self, params: dict):
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.harness.feeder",
             json.dumps(params)],
            cwd=manifest.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True,
        )
        self.port = int(self._read(30.0)["ready"])

    def _read(self, timeout_s: float) -> dict:
        ready, _, _ = select.select([self._proc.stdout], [], [], timeout_s)
        line = self._proc.stdout.readline() if ready else ""
        if not line:
            raise RunFailed(
                f"the feeder gave no answer (exit code {self._proc.poll()})"
            )
        return json.loads(line)

    def ask(self, cmd: str, **fields) -> dict:
        self._proc.stdin.write(json.dumps({"cmd": cmd, **fields}) + "\n")
        self._proc.stdin.flush()
        return self._read(30.0)

    def close(self) -> None:
        if self._proc.poll() is None:
            try:
                self._proc.stdin.write('{"cmd": "quit"}\n')
                self._proc.stdin.close()
            except OSError:
                pass
            try:
                self._proc.wait(10.0)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait()
        self._proc.stdout.close()


def feeder_params(cell: manifest.Cell, seed: int) -> dict:
    cfg, tr = cell.config, cell.traffic
    return {
        "topic": cfg["topic"],
        "mode": tr["mode"],
        "lead_events": tr.get("lead_events", 0),
        "ahead_chunks": tr["ahead_chunks"],
        "encoders": tr["encoders"],
        "feed": {
            "seed": seed,
            "n_keys": cfg["keys"]["count"],
            "key_prefix": cfg["keys"]["prefix"],
            "partitions": cfg["partitions"],
            "chunk_ms": tr["chunk_ms"],
            "events_per_chunk": tr["events_per_second"] * tr["chunk_ms"] // 1000,
            "records_per_batch": cfg["records_per_batch"],
        },
    }


# -- the engine, as a user calls it ----------------------------------------


def build_stream(cell: manifest.Cell, bootstrap: str):
    from denormalized_tpu import Context, col
    from denormalized_tpu.api import functions as F
    from denormalized_tpu.api.context import EngineConfig

    cfg = cell.config
    q = cfg["query"]
    ctx = Context(EngineConfig(**cfg.get("engine", {})))
    aggs = [
        getattr(F, kind)(col("reading")).alias(name)
        for name, kind in q["aggregates"]
    ]
    ds = ctx.from_topic(
        cfg["topic"], sample_json=SAMPLE_JSON, bootstrap_servers=bootstrap,
        timestamp_column="occurred_at_ms",
    )
    if q["slide_ms"] == q["length_ms"]:
        ds = ds.window(q["keys"], aggs, q["length_ms"])
    else:
        ds = ds.window(q["keys"], aggs, q["length_ms"], q["slide_ms"])
    if q.get("filter"):
        ds = ds.filter(col(q["filter"]["column"]) > q["filter"]["gt"])
    return ctx, ds


def sampled_blocks(seed: int, every: int, n: int = 100_000) -> np.ndarray:
    """Which blocks of event time the comparison covers: one in each group
    of ``every`` consecutive blocks, drawn from the seed before the run, so
    the consumer keeps those rows and no others, and a run that covers two
    groups always has one to compare."""
    groups = n // every
    pick = np.random.default_rng([seed, 0x5A]).integers(0, every, groups)
    out = np.zeros(groups * every, bool)
    out[np.arange(groups) * every + pick] = True
    return out


class Consumer(threading.Thread):
    """Pulls ``ds.stream()``.  Notes when each window first arrived, keeps
    the rows of windows that lie wholly inside a sampled block of event
    time, and lets the rest go."""

    def __init__(self, ds, cell: manifest.Cell, seed: int):
        super().__init__(daemon=True, name="bench-consumer")
        self._ds = ds
        q, chk = cell.config["query"], cell.traffic["check"]
        self._length = q["length_ms"]
        self._block_ms = chk["block_ms"]
        self.sampled = sampled_blocks(seed, chk["every"])
        self.columns = [q["keys"][0]] + [a[0] for a in q["aggregates"]]
        self.arrival: dict[int, float] = {}  # window end -> first arrival
        self.kept: dict[int, list] = {}  # block -> [(window starts, columns)]
        self.rows = 0
        self.error: str | None = None
        self.stop = threading.Event()

    def run(self) -> None:
        it = self._ds.stream()
        try:
            for batch in it:
                self._note(time.monotonic(), batch)
                if self.stop.is_set():
                    break
        except Exception:  # noqa: BLE001 — reported by the run as its failure
            self.error = traceback.format_exc()
        finally:
            it.close()

    def _note(self, now: float, batch) -> None:
        ws = np.asarray(batch.column("window_start_time"), dtype=np.int64)
        self.rows += len(ws)
        for end in np.unique(ws).tolist():
            self.arrival.setdefault(end + self._length, now)
        rel = ws - events.T0
        block = rel // self._block_ms
        inside = (
            (rel >= 0) & (rel + self._length <= (block + 1) * self._block_ms)
        )
        inside &= self.sampled[np.clip(block, 0, len(self.sampled) - 1)]
        if not inside.any():
            return
        cols = {c: np.asarray(batch.column(c))[inside] for c in self.columns}
        for b in np.unique(block[inside]).tolist():
            sel = block[inside] == b
            self.kept.setdefault(b, []).append(
                (ws[inside][sel], {c: v[sel] for c, v in cols.items()})
            )


class CompileCounter(logging.Handler):
    """One record per real compilation: each XLA compilation logs one
    "Finished XLA compilation" (``bench.py``'s counter, copied).  Programs
    served from the persistent cache log none."""

    LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.count = 0
        self.last_at = 0.0
        self._saved = []

    def emit(self, record) -> None:
        if record.getMessage().startswith("Finished XLA compilation"):
            self.count += 1
            self.last_at = time.monotonic()

    def install(self) -> None:
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.addHandler(self)
            lg.setLevel(logging.DEBUG)
            lg.propagate = False

    def remove(self) -> None:
        for lg, level, propagate in self._saved:
            lg.removeHandler(self)
            lg.setLevel(level)
            lg.propagate = propagate
        self._saved = []


def engine_counters(ctx) -> dict:
    """The counters the per-layer readers name: the window operator's
    ``metrics()``, the source's, and the registry's per-operator times."""
    from denormalized_tpu import obs
    from denormalized_tpu.runtime.tracing import collect_metrics

    out: dict = {}
    root = getattr(ctx, "_last_physical", None)
    if root is not None:
        for m in collect_metrics(root).values():
            if "strategy_resolved" in m or "decode_fallback_rows" in m:
                out.update(m)
    for inst in obs.registry().instruments():
        if inst.name in ("dnz_op_batch_ms", "dnz_op_input_wait_ms"):
            op = dict(inst.labels).get("op")
            out[f"{inst.name}.{op}"] = float(inst.sum)
    return out


def counter_deltas(before: dict, after: dict) -> dict:
    return {
        k: after[k] - before.get(k, 0) for k in after
        if isinstance(after[k], (int, float)) and not isinstance(after[k], bool)
    }


# -- the device -------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if require_tpu and (d.platform != "tpu" or len(devices) < chips):
        raise RunFailed(
            f"the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {d.platform} device(s)"
        )
    with open(os.path.join(manifest.BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if require_tpu and d.device_kind not in peaks:
        raise RunFailed(f"no peaks for device kind {d.device_kind!r}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def start_trace(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


# -- the comparison -----------------------------------------------------------


def check_blocks(cell: manifest.Cell, seed: int, consumer: Consumer,
                 control: bool) -> tuple[dict, dict | None, int]:
    """Fold the reference over the sampled blocks the run covered and hold
    the kept rows against it.  Returns the numbers, the control's numbers
    (the bf16 fold put in the engine's place) when asked for, and the count
    of windows with a row missing or wrong."""
    cfg, chk = cell.config, cell.traffic["check"]
    q = cfg["query"]
    feed = events.Feed(**feeder_params(cell, seed)["feed"])
    aggs = [tuple(a) for a in q["aggregates"]]
    flt = (q["filter"]["column"], q["filter"]["gt"]) if q.get("filter") else None
    block_ms = chk["block_ms"]
    if not consumer.arrival:
        return {"rows_compared": 0}, None, 0
    newest = max(consumer.arrival)  # end of the newest window delivered
    oldest = min(consumer.arrival) - q["length_ms"]
    covered = [
        b for b in np.flatnonzero(consumer.sampled).tolist()
        if events.T0 + b * block_ms >= oldest
        and events.T0 + (b + 1) * block_ms <= newest
    ][-chk["max_blocks"]:]
    numbers, controls, bad_windows = [], [], 0
    for b in covered:
        ref = reference.Reference(
            feed, q["length_ms"], q["slide_ms"],
            events.T0 + b * block_ms, events.T0 + (b + 1) * block_ms,
        )
        parts = consumer.kept.get(b, [])
        ws = np.concatenate([p[0] for p in parts]) if parts else np.zeros(0, np.int64)
        got = {
            c: np.concatenate([p[1][c] for p in parts]) if parts else np.zeros(0)
            for c in consumer.columns
        }
        got["cells"] = ref.cells_of(ws, got[q["keys"][0]].tolist())
        n = reference.compare(ref, got, aggs, flt)
        bad_windows += n.pop("bad_windows")
        numbers.append(n)
        if control:
            c = reference.compare(
                ref, reference.rows_of(ref.bf16_fold(), ref, aggs, flt), aggs, flt
            )
            c.pop("bad_windows")
            controls.append(c)
    merged = reference.merge(numbers) if numbers else {"rows_compared": 0}
    merged["blocks_compared"] = len(covered)
    return merged, (reference.merge(controls) if controls else None), bad_windows


# -- one run --------------------------------------------------------------------


def percentile(samples: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(samples, dtype=np.float64), q))


def windows_due(origin: float, t_open: float, t_close: float,
                slide_ms: int) -> dict[int, float]:
    """``{window end (event ms): due time}`` of the windows whose end falls
    inside ``[t_open, t_close]`` on the feeder's schedule: event time runs
    at wall rate from ``origin``, so the end of a window is due when its
    last contributing event is created."""
    first = -(-int((t_open - origin) * 1000) // slide_ms) * slide_ms
    return {
        events.T0 + rel: origin + rel / 1000.0
        for rel in range(first, int((t_close - origin) * 1000) + 1, slide_ms)
    }


def latency_samples(due: dict[int, float], arrival: dict[int, float],
                    t_tail: float) -> tuple[list[float], int]:
    """Milliseconds from each window's due time to the arrival of its first
    result, and the count of windows not delivered by ``t_tail``: each of
    those counts as the worst sample it can be, the wait until then."""
    samples, undelivered = [], 0
    for end, due_at in due.items():
        at = arrival.get(end)
        if at is None or at > t_tail:
            undelivered += 1
            at = t_tail
        samples.append((at - due_at) * 1000.0)
    return samples, undelivered


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             control: bool = False, log=None) -> str:
    """Run the cell once and return the result line (checked).  Raises
    ``RunFailed`` when there is no result to print."""
    t_start = time.monotonic() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    tr = cell.traffic
    device = device_info(cell.chips, require_tpu)
    phases = {"device_s": time.monotonic() - t_start}
    from denormalized_tpu.api.context import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    compiles = CompileCounter()
    compiles.install()
    feeder = Feeder(feeder_params(cell, seed))
    consumer = None
    trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
    tracing = False
    try:
        ctx, ds = build_stream(cell, f"127.0.0.1:{feeder.port}")
        consumer = Consumer(ds, cell, seed)
        consumer.start()
        phases["stream_started_s"] = time.monotonic() - t_start

        def alive() -> None:
            if consumer.error or not consumer.is_alive():
                raise RunFailed(f"the stream ended in warm-up:\n{consumer.error}")
            if time.monotonic() > deadline:
                raise RunFailed(
                    f"no steady emissions after {tr['warmup_timeout_s']} s "
                    f"({len(consumer.arrival)} windows, {compiles.count} compiles)"
                )

        # the schedule starts when the engine's readers ask for data, so an
        # open-loop feed does not pile up behind the plan's own set-up
        deadline = time.monotonic() + tr["warmup_timeout_s"]
        while not feeder.ask("mark")["fetches"]:
            alive()
            time.sleep(0.05)
        phases["first_fetch_s"] = time.monotonic() - t_start
        origin = time.monotonic() + 0.05
        feeder.ask("start", origin=origin)

        # warm-up: the cell's own traffic, until windows have arrived for
        # ``warmup_s``, nothing has compiled for a second and, in an open
        # loop, the engine has caught up with the feed (less than a tenth of
        # a second of events behind, twice in a row)
        caught_up_below = tr["events_per_second"] // 10
        first_at, caught_up = None, 0
        while True:
            alive()
            now = time.monotonic()
            if first_at is None and consumer.arrival:
                first_at = now
                phases["first_window_s"] = now - t_start
            if (first_at is not None and now - first_at >= tr["warmup_s"]
                    and now - compiles.last_at >= 1.0):
                if tr["mode"] != "paced":
                    break
                mark = feeder.ask("mark")
                behind = sum(mark["produced"]) - sum(mark["fetched"])
                caught_up = caught_up + 1 if behind <= caught_up_below else 0
                if caught_up >= 2:
                    break
                time.sleep(0.2)
            time.sleep(0.05)

        if trace:
            start_trace(trace_dir)
            tracing = True
        t_traced = time.monotonic()
        compiles_before = compiles.count
        counters_open = engine_counters(ctx)
        mark_open = feeder.ask("mark")
        t_open = time.monotonic()
        setup_s = t_open - t_start
        time.sleep(max(0.0, t_open + seconds - time.monotonic()))
        mark_close = feeder.ask("mark")
        t_close = time.monotonic()
        counters_close = engine_counters(ctx)
        compiles_in_window = compiles.count - compiles_before
        traced_s = time.monotonic() - t_traced
        if trace:
            stop_trace()
            tracing = False

        # windows whose end was due inside the measured window (paced: event
        # time runs at wall rate from ``origin``)
        due: dict[int, float] = {}
        if tr["mode"] == "paced":
            due = windows_due(
                origin, t_open, t_close, cell.config["query"]["slide_ms"]
            )
            tail_end = t_close + tr["tail_s"]
            while time.monotonic() < tail_end and not all(
                e in consumer.arrival for e in due
            ):
                time.sleep(0.02)
        t_tail = time.monotonic()
        consumer.stop.set()
        consumer.join(30.0)
        if consumer.is_alive():
            raise RunFailed("the stream did not stop within 30 s of the window")
        if consumer.error:
            raise RunFailed(f"the stream raised:\n{consumer.error}")
        feeder.ask("stop")
        final = engine_counters(ctx)
        peak = memory_peak_bytes(cell.chips)
        if require_tpu and not peak > 0:
            raise RunFailed("the device reports no peak_bytes_in_use")
    finally:
        if tracing:
            stop_trace()
        compiles.remove()
        if consumer is not None:
            consumer.stop.set()
        feeder.close()
        if consumer is not None:
            consumer.join(30.0)

    # free the engine's state before the reference runs
    del ctx, ds
    gc.collect()

    window_s = mark_close["t"] - mark_open["t"]
    fetched = sum(mark_close["fetched"]) - sum(mark_open["fetched"])
    in_window = [
        e for e, t in consumer.arrival.items() if t_open <= t <= t_close
    ]
    obs = {
        "window_s": window_s,
        "counters": counter_deltas(counters_open, counters_close),
        "feeder": {
            "backlog_min": mark_close["backlog_min"],
            "late_ms": mark_close["late_ms"],
            "fetched": fetched,
        },
        "compiles": compiles_in_window,
        "windows_delivered": len(in_window),
        "trace": None,
    }
    metrics: dict[str, tuple[float, str]] = {}
    undelivered = 0
    if tr["mode"] == "paced":
        samples, undelivered = latency_samples(due, consumer.arrival, t_tail)
        if not samples:
            raise RunFailed("no window was due inside the measured window")
        obs["latency_ms"] = samples
        latency = {
            "window_latency_p50_ms": percentile(samples, 50),
            "window_latency_p95_ms": percentile(samples, 95),
        }
        attempted = len(due)
    else:
        latency = {}
        attempted = len(in_window)
    values = {"events_per_s": fetched / window_s, "setup_s": setup_s, **latency}
    for name, unit in cell.end_to_end.items():
        if name not in values:
            raise RunFailed(f"the harness computes no end-to-end metric {name!r}")
        metrics[name] = (values[name], unit)

    breakdown = None
    if trace:
        try:
            reduced = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(trace_dir), cell.chips
            )
        except trace_reduce.NoDeviceEvents as e:
            raise RunFailed(str(e)) from e
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        obs["trace"] = {"busy_s": reduced["busy_s"], "window_s": traced_s}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = traced_s
        breakdown = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        for name, unit in cell.per_layer.items():
            value = cell.readers[name](obs)
            if value is not None:
                metrics[name] = (float(value), unit)
    device["memory_peak_bytes"] = peak

    numbers, control_numbers, bad_windows = check_blocks(
        cell, seed, consumer, control
    )
    numbers["windows_undelivered"] = undelivered
    numbers["late_rows"] = int(final.get("late_rows", 0))
    numbers["decode_fallback_rows"] = int(final.get("decode_fallback_rows", 0))
    correct, compared = reference.verdict(numbers)
    failed = undelivered + bad_windows
    log(json.dumps({
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "cache_dir": cache_dir, "window_s": window_s,
        "strategy_resolved": final.get("strategy_resolved"),
        "windows_delivered": len(in_window), "rows_delivered": consumer.rows,
        "blocks_compared": numbers.get("blocks_compared"),
        "rows_compared": numbers.get("rows_compared"),
        "compiles_in_window": compiles_in_window,
        "backlog_min": mark_close["backlog_min"],
        "backlog_open": sum(mark_open["produced"]) - sum(mark_open["fetched"]),
        "backlog_close": sum(mark_close["produced"]) - sum(mark_close["fetched"]),
        "late_ms_p95": (
            percentile(mark_close["late_ms"], 95) if mark_close["late_ms"] else None
        ),
        "setup_phases_s": phases,
        "latency_ms": [round(x, 1) for x in obs.get("latency_ms", [])],
        "metrics": {k: v for k, (v, _u) in metrics.items()},
        "counters": obs["counters"],
    }))
    if control_numbers is not None:
        c_ok, c_compared = reference.verdict(control_numbers)
        log("control (bf16 fold in the engine's place): correct=%s %s"
            % (c_ok, json.dumps(c_compared)))
    # a per-layer reader that found nothing to read reports nothing, and a
    # line without a metric the cell lists is refused here, not by the driver
    wanted = cell.per_layer if trace else cell.end_to_end
    text = lastline.build(
        correct=correct, attempted=attempted, failed=failed, metrics=metrics,
        device=device, wanted=wanted, traced=trace, breakdown=breakdown,
        compared=compared,
    )
    log("compared: " + json.dumps(compared))
    return text
