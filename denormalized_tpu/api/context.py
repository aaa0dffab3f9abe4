"""Session context — the framework entry point.

Mirror of the reference's ``Context`` (crates/core/src/context.rs:24-89) and
its Python wrapper (py-denormalized python/denormalized/context.py): builds
the session with streaming defaults, registers topics/sources as named
tables, and hands out :class:`DataStream` builders.  Where the reference
configures DataFusion (batch_size=32, coalesce off, custom planner/optimizer,
context.rs:27-58), we configure the TPU execution profile: batch bucketing,
accumulator dtype, state capacities, device mesh, and the checkpoint backend.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax.numpy as jnp

from denormalized_tpu.common.errors import PlanError
from denormalized_tpu.common.schema import Schema
from denormalized_tpu.logical import plan as lp
from denormalized_tpu.sources.base import Source


@dataclass
class EngineConfig:
    """Engine tuning knobs (the reference's SessionConfig + the
    ``denormalized_config`` extension, config_extensions/denormalized_config.rs:4-13).

    The reference runs 32-row micro-batches with coalescing disabled to keep
    latency low on CPU; a TPU step amortizes dispatch over much larger
    buckets, so the default bucket is 8192 rows and sources should aim for
    ms-scale batches."""

    # logical optimizer (projection pruning / project merge / filter
    # pushdown — the reference's curated rule list analog,
    # utils/default_optimizer_rules.rs:29-65)
    optimizer: bool = True
    # checkpoint flag — mirror of denormalized_config.checkpoint
    checkpoint: bool = False
    checkpoint_interval_s: float = 10.0  # orchestrator cadence (orchestrator.rs:58)
    state_backend_path: str | None = None

    # device execution profile.  accum_dtype=jnp.float64 additionally
    # requires jax.config.update("jax_enable_x64", True) — the engine
    # REFUSES to run f64 without it (JAX would silently compute in f32).
    accum_dtype: Any = jnp.float32
    # compensated (Kahan-style) summation: sum components keep a (hi, lo)
    # buffer pair and each batch folds in via exact TwoSum.  Error bound vs
    # an f64 oracle: ~1e-6 relative at 1M f32 values per group (see
    # segment_agg.WindowKernelSpec.compensated); plain f32 drifts ~1e-4.
    compensated_sums: bool = False
    # streaming joins: rows older than the join watermark by more than this
    # are evicted (and emitted unmatched for outer joins)
    join_retention_ms: int = 300_000
    # band-aware eviction for interval joins (docs/joins.md): when set,
    # a retained row is also evictable once its band value falls more
    # than this slack below the horizon the other side's band watermark
    # implies — rows a band strictly tighter than retention can never
    # match stop occupying state.  The slack absorbs band-space
    # lateness: 0 is exact for per-side in-order band values, and with
    # event-time-like band expressions set it to your allowed lateness.
    # None (default) disables band-aware eviction (retention-only, the
    # pre-existing semantics: matches exist while co-retained).
    join_band_slack_ms: int | None = None
    # closed-loop skew adaptation (obs/doctor/actions.py): when a key's
    # sketched share crosses the skewed-join-side verdict thresholds, the
    # policy migrates it into a dense hot sub-partition (and folds it
    # back on decay).  Emissions are byte-identical either way — this is
    # a performance layout, not a semantics switch (docs/joins.md).
    join_adaptive: bool = True
    join_adapt_interval_s: float = 1.0
    min_batch_bucket: int = 256
    min_group_capacity: int = 128
    min_window_slots: int = 16
    emit_on_close: bool = True

    # idle sources: when EVERY partition of a live source has produced no
    # rows for this long, emit a WatermarkHint advancing event time to the
    # max timestamp seen, so windows over a quiet topic still close.
    # None (default) = reference behavior: the last windows of a quiet
    # stream wait for more data forever.
    source_idle_timeout_ms: int | None = None
    # per-partition watermarks: the source-level watermark is the MIN over
    # each partition's own max-of-batch-min-ts, so one fast-draining
    # partition cannot race the watermark ahead and drop the slower
    # partitions' backlog as late (replay/catch-up skew — the reference's
    # global max-of-min rule shares this flaw).  'auto' (default) enables
    # it for multi-partition sources whose liveness is guaranteed: bounded
    # sources, or unbounded ones with source_idle_timeout_ms set (quiet
    # partitions then leave the min instead of stalling it).  True forces
    # it on, False keeps reference semantics everywhere.
    partition_watermarks: bool | str = "auto"

    # sharding (parallel/): number of devices to shard group-state over;
    # None = single device
    mesh_devices: int | None = None
    # 2-D layout: split mesh_devices into this many row-parallel slices
    # (keys sharded within each slice, cross-slice merge at emission only
    # — the dp x tp analog; see parallel/sharded_state.TwoLevelWindowState)
    mesh_slices: int | None = None
    # 'auto' | 'key_sharded' | 'partial_final' | 'two_level'
    # (see parallel/sharded_state.py); a strategy named here ships rows
    shard_strategy: str = "auto"
    # single-device kernel strategy:
    #   'scatter'       — ship rows, device scatters them into the window
    #                     ring (general; right when host↔device bandwidth
    #                     is plentiful)
    #   'partial_merge' — reduce each batch on host (native C++ single
    #                     pass) and ship per-(slide-unit, group) partials;
    #                     the device merges them into the ring.  Traffic
    #                     scales with cardinality, not rows — the right
    #                     choice behind a narrow host↔device link
    #   'auto'          — partial_merge on a TPU (one device: a rule
    #                     chosen on an earlier installation, ROADMAP S2;
    #                     a 1-D mesh with shard_strategy 'auto': measured
    #                     on four v5e chips, PERF.md PR 31 — each device
    #                     folds its own key block's share of the stripe)
    #                     and on the CPU (it beats XLA scatter adds
    #                     there), except
    #                     f64 accumulators on CPU, which keep scatter:
    #                     the partial stripe's f32 hi/lo transport cannot
    #                     carry finite f64 sums beyond f32 range.  On
    #                     backends neither covers (e.g. a GPU) 'auto'
    #                     keeps row shipping
    device_strategy: str = "auto"
    # partial_merge pacing: merge the host stripe after this many rows even
    # if no window closed, and defer emission up to emit_lag_ms after a
    # window becomes closable so replay-speed runs batch several windows
    # per device round-trip.  None = backend default: 0 on CPU (merges
    # are memcpy-cheap, and deferral would hold a paused live stream's
    # final windows until the next rowful batch), 200ms on every
    # accelerator backend (TPU, GPU, ...) to amortize the merge
    # round-trip (not measured on this installation; ROADMAP S2)
    partial_merge_rows: int = 4_000_000
    emit_lag_ms: int | None = None
    # run backend.accumulate (native stripe reduction, GIL-releasing) on a
    # worker thread so batch N's reduction overlaps batch N+1's
    # decode/eval/intern.  Default OFF: on CPU JAX the worker contends
    # with device programs for the same cores (measured 13-21% SLOWER);
    # worth A/B-ing on a real chip where device work leaves the host idle
    host_pipeline: bool = False
    # on-device finalization: emission ships the FINAL output columns
    # (count/sum/min/max/avg, computed on device in accum dtype) plus an
    # active-group bitmask, instead of the raw component planes — fewer
    # bytes per emitted window on a narrow link, and no host finalize.
    # Falls back per-operator when an aggregate isn't finalizable on
    # device (variance family) or the state layout doesn't support it.
    device_finalize: bool = True
    # -- observability (denormalized_tpu/obs, docs/observability.md) ----
    # default-level metrics: typed registry instruments across every
    # layer (per-operator batch time + rows, watermark/emit lag, kafka
    # consumer lag, prefetch depth/restarts, checkpoint/LSM timings).
    # False binds every handle to a shared no-op null — the hot paths
    # then do literally nothing (pinned by tests/test_obs.py)
    metrics_enabled: bool = True
    # opt-in Prometheus text-exposition endpoint on a stdlib HTTP server
    # (127.0.0.1); 0 = ephemeral port (read it back from
    # ctx._last_exporters.prometheus.port), None = off
    prometheus_port: int | None = None
    # periodic JSONL registry snapshots (soak/bench telemetry stream);
    # None = off
    metrics_jsonl_path: str | None = None
    metrics_jsonl_interval_s: float = 1.0
    # Chrome trace-event JSON (Perfetto-loadable) dumped at stream end
    # from the ring-buffered span recorder; None = off.  trace_events
    # sizes the ring (newest events win; 0 = default 65536)
    trace_path: str | None = None
    trace_events: int = 0
    # -- pipeline doctor (obs/doctor, docs/observability.md §doctor) ----
    # live query introspection: every execution registers its physical
    # plan (node-id keyed) with per-operator busy/queue-wait stats and
    # ranked bottleneck attribution, served at /queries[/<id>/plan] on
    # the Prometheus HTTP server and via df.explain_analyze().  Costs a
    # few plain attribute adds per batch; False opts a query out.
    doctor_enabled: bool = True
    # sampled record lineage: tag every Nth row per partition at ingest
    # with (source, partition, offset, event time) and follow it through
    # operator handoffs into window emission — "why is this window late"
    # becomes GET /queries/<id>/lineage.  None (default) = off; when on,
    # adds an O(rows) timestamp min/max per batch per operator.
    lineage_sample_every: int | None = None
    lineage_max_samples: int = 256
    # on-demand sampling profiler (sys._current_frames folded stacks for
    # flamegraphs): started per query via the HTTP surface or
    # QueryHandle.start_profiler(); this sets only the sample rate
    profiler_hz: float = 100.0
    # -- state observatory (obs/statewatch.py, docs §state observatory) -
    # soft budget for TOTAL live keyed state across a query's stateful
    # operators: GET /queries/<id>/state projects time-to-budget from
    # each operator's growth ring and raises state-budget-pressure
    # verdicts as the projection closes in.  None = no budget (growth
    # forecasts still reported, without a time-to-budget).
    state_budget_bytes: int | None = None
    # tiered state (state/tiering.py, docs/state_spill.md): when a budget
    # AND a state backend are both configured, stateful operators evict
    # their coldest key/batch/window blocks to the LSM once accounted
    # state crosses the budget, and reload them on touch — the query
    # degrades to disk speed instead of OOMing.  'auto' (default) =
    # active exactly when budget + state_backend_path are set; False
    # disables (budget stays forecast-only, PR-8 semantics); True
    # additionally REQUIRES a backend path (loud error instead of a
    # silently forecast-only budget).
    state_spill: bool | str = "auto"

    # -- multi-query engine (docs/multi_query.md) -----------------------
    # slice-folding window path: tumbling/sliding windows with builtin
    # (foldable) aggregates run on SliceWindowExec — per-(group,
    # slide-unit) partials accumulated once per batch, windows folded
    # from slice partials instead of scattering each row into every
    # overlapping window.  This is the kernel the multi-query sharing
    # runtime (runtime/multi_query.py) always uses; setting True here
    # additionally applies it to SINGLE queries planned through the
    # normal executor (the sliding-window fast path).  Default False: the
    # device ring operator stays the single-query default pending a
    # real-chip A/B — slice folds are host-side f64, so emitted floats
    # can differ from the f32 device ring in the last ulp.
    slice_windows: bool = False
    # explicit slice width for the slice path (must divide the window's
    # length AND slide; None = their gcd).  The fold grouping is part of
    # a query's numeric contract — f64 sums round per fold tree — so an
    # independent oracle comparing byte-identically against a shared
    # group pins the group's gcd unit here (tests/bench do).
    slice_unit_ms: int | None = None
    # pin the slice store's lexsort accumulation lane (add-only
    # component sets otherwise take the faster bincount lane, which
    # associates long-segment adds differently).  A shared group whose
    # aggregate UNION carries min/max always sorts, so an add-only
    # member's byte-identity oracle sets this True to match.
    slice_sort_lane: bool = False
    # approximate aggregates (approx_distinct / approx_top_k /
    # approx_percentile_cont / approx_median) as first-class sketch
    # planes on the slice path — constant state per group regardless of
    # value cardinality (ops/sketches.py).  Only takes effect with
    # slice_windows=True; False lowers them to their exact accumulator
    # UDAFs everywhere (the historical behavior, and the bench's A/B
    # control for the approx_scale sweep).
    approx_native: bool = True
    # predicate-subsumption sharing in the multi-query runtime: a query
    # whose filter is provably implied by another's (conjunct
    # containment over equality/range/IN bounds — planner/predicates.py)
    # joins that query's share group, ingesting once under the weakest
    # member predicate with a vectorized residual re-filter per
    # stronger member.  False restores exact-signature matching only
    # (the pre-subsumption behavior; the bench's A/B control).
    mq_subsumption: bool = True

    def set(self, key: str, value) -> "EngineConfig":
        """String-keyed setter for parity with SessionConfig::set
        (README.md:105 `denormalized_config.checkpoint`)."""
        k = key.removeprefix("denormalized_config.")
        if not hasattr(self, k):
            raise PlanError(f"unknown config key {key!r}")
        setattr(self, k, value)
        return self


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for this process and
    return the directory in use (None on the CPU backend).

    The one cache policy, shared by the engine (first device touch, in the
    window-state factory), the benchmark and ``chip_smoke.py``: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    directory is set in code; otherwise ``<checkout>/.jax_cache`` — a fixed
    path, never a temporary name, because a cache that moves between
    processes never hits.  Every program is cached whatever it cost to
    compile: the prewarm ladders are dozens of individually cheap programs,
    and a run that re-reads them all adds no file.  A directory that cannot
    be created is an error, not a silent recompile.

    CPU compiles are not cached: they are fast, and a cached CPU executable
    may target machine features another host lacks.  Initializes the
    backend; idempotent."""
    import jax

    if jax.devices()[0].platform == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = Path(__file__).resolve().parents[2] / ".jax_cache"
        path.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    return jax.config.jax_compilation_cache_dir


class Context:
    """Session factory: registers sources, builds streams."""

    def __init__(self, config: EngineConfig | None = None) -> None:
        self.config = config or EngineConfig()
        self._tables: dict[str, Source] = {}
        self._orchestrator = None
        # metrics_enabled is resolved by the EXECUTOR per execution
        # (runtime/executor.py _resolve_registry): each query binds its
        # operators against its own resolved registry — live handles or
        # shared nulls — so concurrently EXECUTING queries with
        # different settings no longer fight over a process-global flag
        # (the PR-6 documented limitation, since fixed).

    def __repr__(self) -> str:
        """String representation (reference context.py:16-30)."""
        return (
            f"Context(tables=[{', '.join(sorted(self._tables))}], "
            f"checkpoint={self.config.checkpoint})"
        )

    def __str__(self) -> str:
        return self.__repr__()

    # -- registration (Context::from_topic, context.rs:65-72) -----------
    def register_source(self, name: str, source: Source) -> None:
        self._tables[name] = source

    def from_source(self, source: Source, name: str | None = None):
        from denormalized_tpu.api.data_stream import DataStream

        name = name or source.name
        self.register_source(name, source)
        scan = lp.Scan(name, source, source.schema)
        return DataStream(scan, self)

    def from_topic(
        self,
        topic: str,
        sample_json: str | None = None,
        bootstrap_servers: str = "localhost:9092",
        timestamp_column: str | None = None,
        group_id: str = "denormalized-tpu",
        encoding: str = "json",
        schema: Schema | None = None,
        avro_schema=None,
        timestamp_unit: str | None = None,
    ):
        """Kafka source entry point (PyContext::from_topic,
        py-denormalized/src/context.rs:50-117): schema comes from an explicit
        Schema, is inferred from ``sample_json``, or — for
        ``encoding="avro"`` — derives from ``avro_schema`` (an Avro record
        declaration as JSON string or dict).

        Parameter ORDER matches the reference wrapper exactly
        (py-denormalized/python/denormalized/context.py:32-39:
        topic, sample_json, bootstrap_servers, timestamp_column,
        group_id) — a migrating user's positional call
        ``from_topic("t", sample, server, "occurred_at_ms")`` must bind
        the timestamp column, not the consumer group id; getting this
        wrong silently demotes event-time to broker arrival time."""
        from denormalized_tpu.sources.kafka import KafkaTopicBuilder

        builder = (
            KafkaTopicBuilder(bootstrap_servers)
            .with_topic(topic)
            .with_encoding(encoding)
            .with_group_id(group_id)
        )
        if timestamp_column:
            builder = builder.with_timestamp_column(timestamp_column)
        if timestamp_unit:
            builder = builder.with_timestamp_unit(timestamp_unit)
        if avro_schema is not None:
            # conflicting arguments are errors, not silent overrides
            if schema is not None:
                raise PlanError(
                    "pass either schema= or avro_schema=, not both (the "
                    "Avro declaration defines the schema)"
                )
            if encoding.lower() != "avro":
                raise PlanError(
                    f"avro_schema= conflicts with encoding={encoding!r}"
                )
            builder = builder.with_avro_schema(avro_schema)
        elif schema is not None:
            builder = builder.with_schema(schema)
        elif sample_json is not None:
            builder = builder.infer_schema_from_json(sample_json)
        return self.from_source(builder.build_reader(), name=topic)

    def table(self, name: str) -> Source:
        if name not in self._tables:
            raise PlanError(f"unknown table {name!r}")
        return self._tables[name]

    # -- state backend (Context::with_slatedb_backend, context.rs:77-86) -
    def with_state_backend(self, path: str) -> "Context":
        self.config.state_backend_path = path
        self.config.checkpoint = True
        return self
