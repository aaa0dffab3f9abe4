"""Engine-level observability integration: per-operator collect_metrics
key sets (stable, documented in docs/observability.md), node-id keying
across checkpoint/restore, the Prometheus endpoint scraped during a
running query, JSONL + Perfetto exporters through EngineConfig, and the
metrics-disabled engine path."""

import json
import urllib.request

import numpy as np
import pytest

from denormalized_tpu import Context, col, obs
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.api.udaf import Accumulator
from denormalized_tpu.common.schema import DataType
from denormalized_tpu.obs.registry import MetricsRegistry
from denormalized_tpu.runtime.tracing import collect_metrics
from denormalized_tpu.sources.memory import MemorySource


@pytest.fixture
def registry():
    reg = MetricsRegistry(enabled=True)
    prev = obs.use_registry(reg)
    yield reg
    obs.use_registry(prev)


T0 = 1_700_000_000_000


def _batches(make_batch, n_batches=8, rows=200, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        ts = np.sort(T0 + b * 400 + rng.integers(0, 400, size=rows))
        names = rng.choice([f"sensor_{i}" for i in range(5)], size=rows)
        vals = rng.normal(50.0, 10.0, size=rows)
        out.append(make_batch(ts, names, vals))
    return out


def _mem(batches):
    return MemorySource.from_batches(
        batches, timestamp_column="occurred_at_ms"
    )


def _by_class(metrics_by_node):
    out = {}
    for node_id, m in metrics_by_node.items():
        cls = node_id.split("_", 1)[1]
        out.setdefault(cls, {}).update(m)
    return out


#: the documented per-operator metric key sets (docs/observability.md
#: compatibility-view section) — changing one is an API break for every
#: consumer of collect_metrics (bench, soak, dashboards), so it must be
#: a conscious diff here
SOURCE_KEYS = {
    "rows_out", "batches_out", "decode_fallback_rows", "salvaged_rows",
    # span counters of the fetch + decode layer (docs/observability.md,
    # Spans): 0 where no pump or no Kafka reader runs
    "prefetch_read_ms", "prefetch_blocked_ms", "kafka_fetch_ms",
    "kafka_decode_ms", "queue_wait_ms",
}
WINDOW_KEYS = {
    "rows_in", "batches_in", "late_rows", "windows_emitted", "emit_rows",
    # rows of emit_rows a filter handed down to the operator kept out
    "emit_rows_filtered",
    "device_steps", "partial_merges", "grow_events", "hint_path_ms",
    "bytes_h2d", "bytes_d2h", "strategy_resolved", "first_batch_at",
    # exclusive host milliseconds per phase of the operator, and the
    # whole flush (the sum of its two)
    "phase_ms_project", "phase_ms_intern", "phase_ms_statewatch",
    "phase_ms_reduce", "phase_ms_acc_wait", "phase_ms_update",
    "phase_ms_trigger", "phase_ms_flush_send", "phase_ms_flush_pack",
    "phase_ms_flush", "phase_ms_gather",
    "phase_ms_d2h_wait", "phase_ms_finalize", "phase_ms_other",
    # deferred emission blocks taken once landed / waited for, and what
    # their bytes cost on the -d2h worker, beside the phases above
    "emit_blocks_overlapped", "emit_blocks_waited", "d2h_fetch_ms",
    # what the statewatch phase ran: batches sketched, natively of those
    "sketch_update_batches", "sketch_native_batches",
    # batches whose timestamps window.project's native pass took
    "project_native_batches",
    # what the host stripe's flushes touched and sent
    "stripe_cells_active", "stripe_cells_shipped", "stripe_bytes_touched",
    "stripe_bytes_packed",
    # what the merges of those stripes fold on the device: ring rows, and
    # packed entries x windows fed; and why each flush happened, one
    # counter a reason (they add up to device_steps on a stripe backend)
    "merge_window_folds", "merge_fold_entries",
    "flush_reason_span", "flush_reason_rows", "flush_reason_close",
    "flush_reason_lag", "flush_reason_forced",
    # the active cells again by key block (one block without a mesh), as a
    # list and one by one; and the devices the ring is laid out over
    "merge_cells_by_shard", "merge_cells_shard_0", "mesh_devices",
    # the native interner's tallies (docs/observability.md, Spans)
    "intern_rows", "intern_extra_probes", "intern_overflow_rows",
}
SESSION_KEYS = {
    "rows_in", "sessions_emitted", "late_rows", "salvage_rows_scanned",
}
UDAF_KEYS = {"rows_in", "windows_emitted", "late_rows"}
JOIN_KEYS = {"rows_out", "evicted", "hot_keys", "adaptations"}


def test_collect_metrics_window_pipeline_keys(make_batch, registry):
    ctx = Context(EngineConfig(min_batch_bucket=256))
    ds = ctx.from_source(_mem(_batches(make_batch))).window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count")],
        1000,
    )
    ds.collect()
    per_class = _by_class(collect_metrics(ctx._last_physical))
    assert set(per_class["SourceExec"]) == SOURCE_KEYS
    assert set(per_class["StreamingWindowExec"]) == WINDOW_KEYS
    assert per_class["StreamingWindowExec"]["rows_in"] == 8 * 200
    # the registry sees the same counts the dict view reports
    c = registry.counter("dnz_op_rows_in_total", op="window")
    assert c.value == 8 * 200


def test_collect_metrics_session_pipeline_keys(make_batch, registry):
    ctx = Context(EngineConfig(min_batch_bucket=256))
    ds = ctx.from_source(_mem(_batches(make_batch))).session_window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count")],
        300,
    )
    ds.collect()
    per_class = _by_class(collect_metrics(ctx._last_physical))
    assert set(per_class["SessionWindowExec"]) == SESSION_KEYS


def test_collect_metrics_udaf_pipeline_keys(make_batch, registry):
    class Spread(Accumulator):
        def __init__(self):
            self.lo, self.hi = float("inf"), float("-inf")

        def update(self, values):
            if len(values):
                self.lo = min(self.lo, float(values.min()))
                self.hi = max(self.hi, float(values.max()))

        def merge(self, states):
            self.lo = min(self.lo, states[0])
            self.hi = max(self.hi, states[1])

        def state(self):
            return [self.lo, self.hi]

        def evaluate(self):
            return self.hi - self.lo if self.hi >= self.lo else 0.0

    spread = F.udaf(Spread, DataType.FLOAT64, "spread")
    ctx = Context(EngineConfig(min_batch_bucket=256))
    ds = ctx.from_source(_mem(_batches(make_batch))).window(
        [col("sensor_name")],
        [spread(col("reading")).alias("spread")],
        1000,
    )
    ds.collect()
    per_class = _by_class(collect_metrics(ctx._last_physical))
    assert set(per_class["UdafWindowExec"]) == UDAF_KEYS


def test_collect_metrics_join_pipeline_keys(make_batch, registry):
    ctx = Context(EngineConfig(min_batch_bucket=256))
    left = ctx.from_source(
        _mem(_batches(make_batch, seed=1)), name="l"
    ).window(
        [col("sensor_name")], [F.avg(col("reading")).alias("a")], 1000
    )
    right = (
        ctx.from_source(_mem(_batches(make_batch, seed=2)), name="r")
        .window([col("sensor_name")], [F.avg(col("reading")).alias("b")], 1000)
        .with_column_renamed("sensor_name", "rs")
        .with_column_renamed("window_start_time", "rws")
        .with_column_renamed("window_end_time", "rwe")
    )
    ds = left.join(
        right, "inner", ["sensor_name", "window_start_time"], ["rs", "rws"]
    )
    ds.collect()
    per_class = _by_class(collect_metrics(ctx._last_physical))
    assert set(per_class["StreamingJoinExec"]) == JOIN_KEYS
    assert per_class["StreamingJoinExec"]["rows_out"] > 0


def test_node_id_keying_survives_checkpoint_restore(make_batch, tmp_path):
    """collect_metrics keys by the same DFS node ids checkpoints use —
    the keying must come out identical in a restored incarnation of the
    same query, or dashboards lose series continuity across restarts."""
    from denormalized_tpu.state.lsm import close_global_state_backend

    def run_once():
        cfg = EngineConfig(
            min_batch_bucket=256,
            checkpoint=True,
            checkpoint_interval_s=0.05,
            state_backend_path=str(tmp_path / "state"),
        )
        ctx = Context(cfg)
        ds = ctx.from_source(_mem(_batches(make_batch))).window(
            [col("sensor_name")],
            [F.count(col("reading")).alias("count")],
            1000,
        )
        ds.collect()
        keys = set(collect_metrics(ctx._last_physical))
        close_global_state_backend()
        return keys

    keys1 = run_once()
    keys2 = run_once()  # restores from the first run's checkpoint
    assert keys1 == keys2
    assert any("StreamingWindowExec" in k for k in keys1)
    assert any("SourceExec" in k for k in keys1)


def test_prometheus_endpoint_during_running_query(make_batch, registry):
    """Acceptance: a scrape against the opt-in endpoint DURING a running
    query returns every registered instrument in valid exposition
    format."""
    from denormalized_tpu.obs.catalog import INSTRUMENTS

    ctx = Context(EngineConfig(min_batch_bucket=256, prometheus_port=0))
    ds = ctx.from_source(_mem(_batches(make_batch, n_batches=12))).window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count")],
        1000,
    )
    it = ds.stream()
    got_rows = 0
    try:
        first = next(it)  # query is now mid-stream, exporters live
        got_rows += first.num_rows
        port = ctx._last_exporters.prometheus.port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        )
        assert body.headers["Content-Type"].startswith("text/plain")
        text = body.read().decode()
    finally:
        for b in it:
            got_rows += b.num_rows
    # all registered instruments present, each with HELP + TYPE
    for name, (kind, _help, *_r) in INSTRUMENTS.items():
        assert f"# HELP {name} " in text, name
        assert f"# TYPE {name} {kind}" in text, name
    # live series from this very query
    assert 'dnz_op_rows_in_total{op="window"}' in text
    assert "dnz_op_batch_ms_bucket" in text
    assert got_rows > 0
    # endpoint is down after the stream finishes (exporters stopped)
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=1
        )


def test_jsonl_and_perfetto_exporters_via_config(
    make_batch, tmp_path, registry
):
    jsonl_path = tmp_path / "telemetry.jsonl"
    trace_path = tmp_path / "trace.json"
    ctx = Context(EngineConfig(
        min_batch_bucket=256,
        metrics_jsonl_path=str(jsonl_path),
        metrics_jsonl_interval_s=0.05,
        trace_path=str(trace_path),
    ))
    ds = ctx.from_source(_mem(_batches(make_batch))).window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count")],
        1000,
    )
    try:
        ds.collect()
    finally:
        from denormalized_tpu.obs import spans as obs_spans

        obs_spans.disable_span_recording()
    from denormalized_tpu.obs.jsonl import last_stats, read_stream

    snaps = read_stream(jsonl_path)
    assert snaps, "no telemetry snapshots written"
    rows_in = last_stats(snaps, 'dnz_op_rows_in_total{op="window"}')
    assert rows_in == 8 * 200
    batch_stats = last_stats(snaps, 'dnz_op_batch_ms{op="window"}')
    assert batch_stats["count"] == 8
    # Perfetto trace: valid chrome trace JSON with the engine's spans
    trace = json.loads(trace_path.read_text())
    names = {e["name"] for e in trace["traceEvents"]}
    assert "window.process_batch" in names
    assert all("ts" in e and "ph" in e for e in trace["traceEvents"])


def test_metrics_disabled_engine_runs_clean(make_batch, registry):
    ctx = Context(EngineConfig(min_batch_bucket=256, metrics_enabled=False))
    ds = ctx.from_source(_mem(_batches(make_batch))).window(
        [col("sensor_name")],
        [F.count(col("reading")).alias("count")],
        1000,
    )
    out = ds.collect()
    assert out.num_rows > 0
    # nothing bound: the registry stayed empty, the dict view still works
    assert registry.instruments() == []
    per_class = _by_class(collect_metrics(ctx._last_physical))
    assert per_class["StreamingWindowExec"]["rows_in"] == 8 * 200
    obs.set_enabled(True)


@pytest.mark.slow
def test_metrics_overhead_within_noise(make_batch):
    """Overhead guard: default-level metrics must not measurably slow the
    windowed pipeline.  Threshold is deliberately loose."""
    import time as _time

    batches = _batches(make_batch, n_batches=40, rows=2000)

    def once(enabled):
        reg = MetricsRegistry(enabled=enabled)
        prev = obs.use_registry(reg)
        try:
            ctx = Context(EngineConfig(
                min_batch_bucket=2048, metrics_enabled=enabled
            ))
            ds = ctx.from_source(_mem(batches)).window(
                [col("sensor_name")],
                [F.count(col("reading")).alias("count")],
                1000,
            )
            t0 = _time.perf_counter()
            ds.collect()
            return _time.perf_counter() - t0
        finally:
            obs.use_registry(prev)

    once(True)  # warm compile caches
    best = {True: float("inf"), False: float("inf")}
    for _ in range(3):
        for enabled in (True, False):
            best[enabled] = min(best[enabled], once(enabled))
    assert best[True] <= best[False] * 1.25, best
