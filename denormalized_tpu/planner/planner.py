"""Physical planner: logical plan → executable operator tree.

Counterpart of the reference's ``StreamingQueryPlanner`` +
``StreamingWindowPlanner`` extension (query_planner.rs:11-30,
planner/streaming_window.rs:71-172).  Where the reference decides
Partial+Final vs Single aggregation by input partitioning and injects a hash
``RepartitionExec`` via a physical optimizer rule
(coalesce_before_streaming_window_aggregate.rs:32-95), the TPU build has no
cross-thread exchange to plan: partition-parallelism maps to device sharding
inside the window operator (see :mod:`denormalized_tpu.parallel`), so the
planner decides *which window operator variant* to instantiate (dense device
kernel / UDAF host loop / session) and threads sharding config through.
"""

from __future__ import annotations

from denormalized_tpu.common.errors import PlanError
from denormalized_tpu.logical import plan as lp
from denormalized_tpu.logical.expr import (
    AliasExpr,
    BinaryExpr,
    CaseExpr,
    CastExpr,
    Column,
    Expr,
    Literal,
    NotExpr,
    ScalarFunctionExpr,
)
from denormalized_tpu.logical.optimizer import _expr_nodes
from denormalized_tpu.physical.base import ExecOperator
from denormalized_tpu.physical.simple_execs import (
    FilterExec,
    ProjectExec,
    SinkExec,
    SourceExec,
)
from denormalized_tpu.physical.window_exec import StreamingWindowExec

#: expression nodes whose value at a row is a function of that row's columns
#: alone (a scalar function: one that takes arguments).  Not among them: UDFs
#: (may keep state or see the batch), window functions (the batch is their
#: frame), ``is_null`` (reads a validity mask, the optimizer's caution in
#: ``FilterPushdown``), field access, and whatever is added later
_ROW_WISE = (
    Column, Literal, BinaryExpr, NotExpr, AliasExpr, CastExpr, CaseExpr,
    ScalarFunctionExpr,
)


def _applies_at_emission(predicate: Expr, window: StreamingWindowExec) -> bool:
    """Can ``window`` apply the filter above it where it emits, before a
    row's key string and columns are built?  Only a predicate that is
    provably row-wise and names aggregate outputs and window bounds alone —
    a group key would need the strings first.  All or nothing: a
    conjunction is not split."""
    names = predicate.columns_referenced()
    keys = {f.name for f in window.schema.fields[:len(window.group_exprs)]}
    return (
        bool(names)
        and not names & keys
        and all(window.schema.has(n) for n in names)
        and all(
            isinstance(e, _ROW_WISE)
            and (not isinstance(e, ScalarFunctionExpr) or e.args)
            for e in _expr_nodes(predicate)
        )
    )


class Planner:
    def __init__(self, config=None) -> None:
        # config: api.context.EngineConfig
        self.config = config

    def _route_approx(self, node) -> list:
        """Route approximate aggregates: on the slice path they stay
        first-class sketch kinds (constant-state mergeable planes,
        ops/sketches.py); everywhere else — sessions, the device ring,
        default config, plans mixing true UDAFs, or
        ``approx_native=False`` — each lowers to the exact accumulator
        UDAF it historically was, preserving every prior behavior."""
        from denormalized_tpu.logical.expr import (
            SKETCH_AGG_KINDS,
            AggregateExpr,
        )

        aggs = node.aggr_exprs
        if not any(a.kind in SKETCH_AGG_KINDS for a in aggs):
            return aggs
        native = (
            node.window_type is not lp.WindowType.SESSION
            and self.config is not None
            and getattr(self.config, "slice_windows", False)
            and getattr(self.config, "approx_native", True)
            and not getattr(self.config, "mesh_devices", None)
            and not any(a.kind == "udaf" for a in aggs)
        )
        if native:
            return aggs
        lowered = []
        for a in aggs:
            if a.kind in SKETCH_AGG_KINDS:
                if a.udaf is None:
                    raise PlanError(
                        f"approximate aggregate {a.name!r} has no "
                        "accumulator fallback and the plan cannot take "
                        "the slice path (sketch aggregates need "
                        "EngineConfig(slice_windows=True) here)"
                    )
                lowered.append(
                    AggregateExpr("udaf", a.arg, a._alias, a.udaf)
                )
            else:
                lowered.append(a)
        return lowered

    def create_physical_plan(self, node: lp.LogicalPlan) -> ExecOperator:
        # extension point: a logical node that knows how to build its own
        # exec (the cluster runtime's ExchangeScan leaf) builds it here —
        # the planner stays ignorant of subsystem-specific operators
        hook = getattr(node, "create_exec", None)
        if hook is not None:
            return hook(self)
        if isinstance(node, lp.Scan):
            return SourceExec(
                node.source,
                idle_timeout_ms=getattr(
                    self.config, "source_idle_timeout_ms", None
                )
                if self.config is not None
                else None,
                partition_watermarks=getattr(
                    self.config, "partition_watermarks", "auto"
                )
                if self.config is not None
                else "auto",
            )
        if isinstance(node, lp.Project):
            child = self.create_physical_plan(node.input)
            return ProjectExec(child, node.exprs, node.schema)
        if isinstance(node, lp.Filter):
            child = self.create_physical_plan(node.input)
            # a filter straight over the ring's window operator goes down
            # into it where it can (what the plan shows decides, no option);
            # the node stays, as a pass-through: taking it out would
            # renumber the window and the source beneath it, and a snapshot
            # written under the old ids would not be found
            # (state/checkpoint.py assign_node_ids)
            absorbed = isinstance(
                child, StreamingWindowExec
            ) and _applies_at_emission(node.predicate, child)
            if absorbed:
                child.set_emission_predicate(node.predicate)
            return FilterExec(child, node.predicate, absorbed=absorbed)
        if isinstance(node, lp.StreamingWindow):
            child = self.create_physical_plan(node.input)
            aggr_exprs = self._route_approx(node)
            kwargs = {}
            if self.config is not None:
                mesh = None
                if getattr(self.config, "mesh_slices", None) and not (
                    self.config.mesh_devices
                ):
                    raise ValueError(
                        "mesh_slices requires mesh_devices (the 2-D "
                        "layout needs the total device count) — the job "
                        "would otherwise silently run single-device"
                    )
                if self.config.mesh_devices:
                    from denormalized_tpu.parallel.mesh import (
                        make_mesh,
                        make_mesh_2d,
                    )

                    if getattr(self.config, "mesh_slices", None):
                        import jax as _jax

                        n_dev = self.config.mesh_devices
                        n_sl = self.config.mesh_slices
                        if n_sl > n_dev or n_dev % n_sl:
                            raise ValueError(
                                f"mesh_devices={n_dev} must be a multiple "
                                f"of mesh_slices={n_sl} (each slice gets "
                                f"mesh_devices/mesh_slices key shards)"
                            )
                        if n_sl & (n_sl - 1):
                            # batches bucket to powers of two and rows
                            # shard P(slices): a non-pow2 slice count
                            # would die on the first batch mid-stream
                            # with a cryptic divisibility error
                            raise ValueError(
                                f"mesh_slices={n_sl} must be a power of "
                                f"two (batches are pow2-bucketed and rows "
                                f"split across slices)"
                            )
                        mesh = make_mesh_2d(
                            n_sl,
                            n_dev // n_sl,
                            devices=_jax.devices()[:n_dev],
                        )
                    else:
                        mesh = make_mesh(self.config.mesh_devices)
                kwargs.update(
                    accum_dtype=self.config.accum_dtype,
                    compensated_sums=self.config.compensated_sums,
                    min_group_capacity=self.config.min_group_capacity,
                    min_window_slots=self.config.min_window_slots,
                    min_batch_bucket=self.config.min_batch_bucket,
                    emit_on_close=self.config.emit_on_close,
                    device_finalize=self.config.device_finalize,
                    mesh=mesh,
                    shard_strategy=self.config.shard_strategy,
                    device_strategy=self.config.device_strategy,
                    partial_merge_rows=self.config.partial_merge_rows,
                    emit_lag_ms=self.config.emit_lag_ms,
                    host_pipeline=self.config.host_pipeline,
                )
            if node.window_type is lp.WindowType.SESSION:
                # sessions handle builtin AND accumulator (UDAF/collection)
                # aggregates in one operator
                import os

                if os.environ.get("DENORMALIZED_SESSION_REFERENCE") == "1":
                    # escape hatch + differential-oracle path: the
                    # pre-vectorization operator, kept verbatim
                    from denormalized_tpu.physical.session_reference import (
                        ReferenceSessionWindowExec as SessionWindowExec,
                    )
                else:
                    from denormalized_tpu.physical.session_exec import (
                        SessionWindowExec,
                    )

                return SessionWindowExec(
                    child,
                    node.group_exprs,
                    aggr_exprs,
                    gap_ms=node.length_ms,
                    emit_on_close=kwargs.get("emit_on_close", True),
                )
            if any(a.kind == "udaf" for a in aggr_exprs):
                from denormalized_tpu.physical.udaf_exec import UdafWindowExec

                return UdafWindowExec(
                    child,
                    node.group_exprs,
                    aggr_exprs,
                    node.window_type,
                    node.length_ms,
                    node.slide_ms,
                    emit_on_close=kwargs.get("emit_on_close", True),
                )
            if (
                self.config is not None
                and getattr(self.config, "slice_windows", False)
                and not self.config.mesh_devices
            ):
                # slice-fold fast path (docs/multi_query.md): every
                # builtin aggregate folds from slice partials, so a
                # sliding window pays O(1) per row + O(L/slide) per
                # emitted window instead of the k-way scatter fan-out.
                # Host kernel — a device mesh keeps the ring operator.
                from denormalized_tpu.physical.slice_exec import (
                    SliceSubscriber,
                    SliceWindowExec,
                )

                return SliceWindowExec(
                    child,
                    node.group_exprs,
                    [
                        SliceSubscriber(
                            aggr_exprs,
                            node.length_ms,
                            node.slide_ms or node.length_ms,
                        )
                    ],
                    emit_on_close=kwargs.get("emit_on_close", True),
                    unit_ms=getattr(self.config, "slice_unit_ms", None),
                    sort_lane=getattr(
                        self.config, "slice_sort_lane", False
                    ),
                )
            return StreamingWindowExec(
                child,
                node.group_exprs,
                aggr_exprs,
                node.window_type,
                node.length_ms,
                node.slide_ms,
                **kwargs,
            )
        if isinstance(node, lp.Join):
            from denormalized_tpu.physical.join_exec import StreamingJoinExec

            left = self.create_physical_plan(node.left)
            right = self.create_physical_plan(node.right)
            jkw = {}
            if self.config is not None:
                jkw["retention_ms"] = self.config.join_retention_ms
                jkw["adaptive"] = bool(self.config.join_adaptive)
                jkw["adapt_interval_s"] = (
                    self.config.join_adapt_interval_s
                )
                jkw["band_slack_ms"] = self.config.join_band_slack_ms
            return StreamingJoinExec(
                left,
                right,
                node.kind,
                node.left_keys,
                node.right_keys,
                node.filter,
                node.schema,
                band=node.band,
                **jkw,
            )
        if isinstance(node, lp.Sink):
            child = self.create_physical_plan(node.input)
            return SinkExec(child, node.sink)
        raise PlanError(f"no physical rule for {type(node).__name__}")
