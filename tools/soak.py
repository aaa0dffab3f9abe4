"""Long-running stability soak: one checkpointed streaming job, paced for
minutes, SIGKILLed and restored repeatedly, leak- and loss-checked.

The unit/property tests prove single kill/restore cycles; this proves the
ENGINE PROCESS is stable over wall-clock time: no unbounded RSS growth in
a long-lived child (state rings, LSM checkpoints, emission buffers), no
window lost or corrupted across many restores, recovery time bounded.
The reference has no analog (its de-facto soak is "run the docker example
and watch", SURVEY §4); a framework claiming checkpoint/restore parity
should demonstrate it surviving repetition.

    python tools/soak.py [--pipeline simple|sliding|join|session|udaf|approx]
                         [--minutes 12] [--pace 200000] [--kill-every 90]
                         [--out SOAK.json]

Design:
- The child process runs the chosen pipeline — ``simple`` (1s tumbling
  count/min/max/avg by key), ``sliding`` (1s/250ms, 4-way emission
  fan-out), ``join`` (a raw fact stream — skewed + late mid-run —
  band-joined to a per-second dimension stream then windowed: the
  closed-loop skew policy adapts the celebrity key live and SIGKILLs
  land mid-adaptation, docs/joins.md), ``session`` (300ms-gap session windows over a bursty
  feed: exact session bounds verified — the operator the reference
  left ``todo!()``), ``udaf`` (stateful Python accumulator on the
  host-frame path: state()/merge() snapshots), or ``approx``
  (sketch-native approx_distinct on the slice store: the parent's
  golden replays the HLL kernels from ops/sketches.py and demands
  EXACT integer equality on every committed estimate,
  docs/approx_aggregates.md) — over a DETERMINISTIC
  paced source whose
  batches are a pure function of the batch index (seeded RNG per batch),
  with checkpointing every 2s to a shared LSM dir.  The source implements
  ``offset_snapshot``/``offset_restore`` (fast-forward to batch i), so a
  restored child resumes exactly where the checkpoint cut — the same
  contract KafkaPartitionReader honors, exercised here through the public
  Source extension API.
- The parent samples child RSS from /proc, kills it with SIGKILL every
  --kill-every seconds (the LAST segment runs to EOS), respawns it, and
  finally compares the union of all segments' emitted windows against an
  incrementally-computed numpy golden.  Output is exactly-once under a
  transactional file-sink protocol: every emitted line carries its
  in-flight epoch, each restored child announces its recovery epoch,
  and the parent discards a killed segment's uncommitted suffix (the
  lines its successor's replay regenerates) — truncate-on-restore,
  applied where the union is read.  Duplicate emissions that survive
  the clip are therefore REAL duplicates and count against the run.

The parent never imports jax; the child pins jax to CPU before first use.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Event-time origin.  Everything in the soak — batch generation, golden
# folds, window keys — is T0-relative, so its absolute value is free to
# move; the parent anchors it near wall-now (main()) so the engine's
# event-time lag metrics (wall − event time) land inside their histogram
# buckets and the telemetry percentiles are real, then hands the value
# to every child via SOAK_T0 (parent and children MUST agree — window
# keys are absolute).  Standalone/child invocations inherit or fall
# back to the legacy fixed origin.
T0 = int(os.environ.get("SOAK_T0", "0")) or 1_700_000_000_000
N_KEYS = 10
WINDOW_MS = 1000


# -- deterministic feed: batch i is a pure function of (seed, i) ---------


def batch_arrays(i: int, batch_rows: int, pace: float, seed: int = 11):
    """(ts, key_ids, vals) for batch i.  Event time advances at exactly
    ``pace`` rows per event-second, so event time == wall time when the
    feed keeps up."""
    rng = np.random.default_rng(seed * 1_000_003 + i)
    span_ms = batch_rows * 1000.0 / pace
    base = T0 + int(i * span_ms)
    ts = base + np.sort(rng.integers(0, max(1, int(span_ms)), batch_rows))
    keys = rng.integers(0, N_KEYS, batch_rows)
    vals = np.round(rng.normal(50.0, 10.0, batch_rows), 6)
    return ts.astype(np.int64), keys, vals


SEED_LEFT = 11
SEED_RIGHT = 23


# -- query-dense live-registration soak (ISSUE 16) -----------------------
# 50 concurrent windowed queries over one feed, all subsumption-shared
# into ONE slice pipeline: a handful present from the start, the rest
# joining LIVE at staggered event times (incl. mid-epoch), some leaving
# mid-run.  Every when_ts below is event time, so re-issuing the whole
# schedule verbatim after a SIGKILL/restore lands each join/leave at
# the same stream position — the registration control plane is
# replayable by construction.

QD_QUERIES = 50
QD_INITIAL = 6
QD_UNIT_MS = 1000
#: (length, slide) cycle — every spec tiles the 1000ms gcd unit
QD_SPECS = [(3000, 1000), (2000, 1000), (4000, 2000), (2000, 2000),
            (5000, 1000), (3000, 3000), (6000, 2000), (4000, 1000)]
#: reading > thr filter cycle; index 0 (weakest) is the group's base
#: predicate — every other threshold is implied by it (subsumption)
QD_THRESHOLDS = [30.0, 38.0, 42.0, 46.0, 50.0, 52.0, 55.0, 35.0]


def _dense_schedule(total_batches: int, batch_rows: int, pace: float, *,
                    n_queries: int, n_initial: int, specs: list,
                    thresholds: list, tail_ms: int) -> list:
    """Shared core of the dense control planes (query_dense and
    join_dense): one dict per query — {"qid", "L", "S", "thr"} plus
    "join" (event-time when_ts) for the live joiners and "leave" for
    the mid-run departures.  Pure function of the feed shape; parent,
    child, and the oracle child all derive the identical schedule from
    SOAK_* env."""
    span_ms = batch_rows * 1000.0 / pace
    horizon = int(total_batches * span_ms)
    queries = []
    for q in range(n_queries):
        length, slide = specs[q % len(specs)]
        queries.append({
            "qid": q, "L": length, "S": slide,
            "thr": thresholds[q % len(thresholds)],
        })
    # joiners: staggered across the middle of the event-time horizon at
    # off-second offsets (joins land mid-epoch relative to the wall-
    # clock checkpoint cadence); the tail stays join-free so every
    # joiner still closes full windows before EOS
    njoin = n_queries - n_initial
    join_lo = 4000
    join_hi = max(join_lo + 1000, horizon - tail_ms)
    for j, q in enumerate(range(n_initial, n_queries)):
        queries[q]["join"] = (
            T0 + join_lo + (join_hi - join_lo) * j // max(njoin - 1, 1)
        )
    # leavers: every fifth joiner departs a third of the horizon after
    # it joined (never in the EOS drain tail — departure must be a live
    # detach, not the pipeline close)
    for q in range(n_initial, n_queries):
        if q % 5 == 2:
            leave = min(
                queries[q]["join"] + horizon // 3, T0 + horizon - 6000
            )
            if leave > queries[q]["join"] + queries[q]["L"] + 2000:
                queries[q]["leave"] = leave
    return queries


def qd_schedule(total_batches: int, batch_rows: int, pace: float) -> list:
    """The deterministic 50-query control plane (6 initial, 44 live
    joiners, every fifth joiner departing mid-run)."""
    return _dense_schedule(
        total_batches, batch_rows, pace, n_queries=QD_QUERIES,
        n_initial=QD_INITIAL, specs=QD_SPECS, thresholds=QD_THRESHOLDS,
        tail_ms=12000,
    )


def qd_class_continuous(specs: dict, qid: int) -> bool:
    """True when ``qid``'s threshold class had some member alive from
    before its join clear through the join instant — its filter class's
    slice partials were retained, so the attach OWES a warm backfill
    (first emitted window strictly before the join time).  First-of-
    class joiners clamp forward instead (fresh-class rule) and owe
    nothing."""
    spec = specs[qid]
    join = spec["join"]
    for other in specs.values():
        if other["qid"] == qid or other["thr"] != spec["thr"]:
            continue
        born = other.get("join")
        if born is not None and born >= join:
            continue
        gone = other.get("leave")
        if gone is not None and gone <= join:
            continue
        return True
    return False


# -- join-dense shared-join soak (ISSUE 17) ------------------------------
# The query-dense scenario one operator deeper: every query windows over
# the SAME fact×dim interval join, so the whole group runs ONE
# StreamingJoinExec whose output fans into the shared slice pipeline.
# Staggered live joins/leaves and SIGKILL/restore ride the identical
# event-time-replayable control plane; verification is byte-identity
# against per-query independent join+window oracles (jd_verify reuses
# qd_verify's comparison).  Readings are rounded to INTEGERS: the join's
# output batch boundaries depend on pump interleaving (live pacing vs
# the oracle's dense replay), so float sums would drift in the last ulp
# across fold groupings — integer-valued float64 keeps every aggregate
# (sum/avg included) exact and order-free (docs/multi_query.md).

JD_QUERIES = 10
JD_INITIAL = 3
JD_UNIT_MS = 1000
JD_SPECS = [(3000, 1000), (2000, 1000), (4000, 2000), (2000, 2000),
            (3000, 3000), (4000, 1000)]
JD_THRESHOLDS = [30.0, 40.0, 46.0, 52.0, 35.0, 55.0]
#: join retention — small enough that the retention-clamped downstream
#: watermark still closes windows promptly, large enough to absorb the
#: pump-interleaving skew between the paced live run and the oracle's
#: dense replay (both sides' batches stay co-retained)
JD_RETENTION_MS = 3000


def jd_batch_arrays(i: int, batch_rows: int, pace: float):
    """Fact-side batch i for the join_dense feed: ``batch_arrays`` with
    readings rounded to integers (see the block comment above)."""
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    return ts, keys, np.round(vals)


def jd_schedule(total_batches: int, batch_rows: int, pace: float) -> list:
    """The join-dense control plane: 10 queries over one shared join (3
    initial, 7 live joiners, one mid-run departure).  The join-free
    tail is longer than query_dense's by the join retention — the
    retention-clamped watermark lags the feed by JD_RETENTION_MS, and a
    joiner attaching inside that lag would backfill against a floor the
    EOS flush then overruns."""
    return _dense_schedule(
        total_batches, batch_rows, pace, n_queries=JD_QUERIES,
        n_initial=JD_INITIAL, specs=JD_SPECS, thresholds=JD_THRESHOLDS,
        tail_ms=12000 + JD_RETENTION_MS,
    )


def _group_reduce(comp, arrays):
    """Composite-key group reduction shared by the golden folds — ONE
    argsort/unique reused across every value array: ``arrays`` is a list
    of (vals, [ufuncs]); returns (uniq_keys, counts, [[reduceat results
    per ufunc] per entry])."""
    order = np.argsort(comp, kind="stable")
    uniq, starts = np.unique(comp[order], return_index=True)
    cnts = np.diff(np.append(starts, len(comp)))
    outs = []
    for vals, ops in arrays:
        v = vals[order]
        outs.append([op.reduceat(v, starts) for op in ops])
    return uniq, cnts, outs


def _merge_tumbling(agg, uniq, cnts, mins, maxs, sums):
    """Accumulate one batch's per-(window,key) partials into the golden —
    shared by the tumbling and sliding folds."""
    for u, c, mn, mx, sm in zip(
        uniq.tolist(), cnts.tolist(), mins.tolist(), maxs.tolist(),
        sums.tolist(),
    ):
        w, k = divmod(u, N_KEYS)
        a = agg.setdefault(
            (w, f"sensor_{k}"), [0, float("inf"), float("-inf"), 0.0]
        )
        a[0] += c
        if mn < a[1]:
            a[1] = mn
        if mx > a[2]:
            a[2] = mx
        a[3] += sm


def golden_update(agg: dict, i: int, batch_rows: int, pace: float):
    """Fold batch i into the golden {(ws, key): [cnt, min, max, sum]},
    vectorized: the Python loop runs per GROUP (~2 windows x N_KEYS per
    batch), not per row — the parent must not steal the single core from
    the engine child it is measuring."""
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    ws = (ts // WINDOW_MS) * WINDOW_MS
    uniq, cnts, [[mins, maxs, sums]] = _group_reduce(
        ws * N_KEYS + keys, [(vals, [np.minimum, np.maximum, np.add])]
    )
    _merge_tumbling(agg, uniq, cnts, mins, maxs, sums)


_SK_MOD = None


def _sk():
    """ops/sketches.py loaded by FILE PATH, not package import — the
    sketch kernels are pure numpy by contract, and the parent must stay
    jax-free (module docstring).  Importing denormalized_tpu here would
    drag the whole engine (and jax) into the measuring process."""
    global _SK_MOD
    if _SK_MOD is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "denormalized_tpu", "ops", "sketches.py",
        )
        spec = importlib.util.spec_from_file_location(
            "_soak_sketches", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _SK_MOD = mod
    return _SK_MOD


def golden_update_approx(agg: dict, i: int, batch_rows: int, pace: float):
    """Fold batch i into {(ws, key): [cnt, hll_plane]} with the SAME
    kernels the engine runs (stable_hash64 → hll_accumulate on a
    single-row int8 plane).  The HLL scatter-max is associative and
    commutative, so the parent's one-shot fold equals the child's
    slice-split, kill-interrupted, restored fold register for register
    — which is why the verify gate can demand EXACT integer equality
    on the estimates instead of an epsilon band."""
    sk = _sk()
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    ws = (ts // WINDOW_MS) * WINDOW_MS
    hashes = sk.stable_hash64(vals)
    comp = ws * N_KEYS + keys
    order = np.argsort(comp, kind="stable")
    uniq, starts = np.unique(comp[order], return_index=True)
    ends = np.append(starts[1:], len(comp))
    ho = hashes[order]
    for u, s, e in zip(uniq.tolist(), starts.tolist(), ends.tolist()):
        w, k = divmod(u, N_KEYS)
        a = agg.setdefault(
            (w, f"sensor_{k}"),
            [0, np.zeros((1, 1 << sk.HLL_P), dtype=np.int8)],
        )
        a[0] += e - s
        sk.hll_accumulate(
            a[1], np.zeros(e - s, dtype=np.int64), ho[s:e]
        )


# -- skew-adaptive interval-join soak feed (ISSUE 15) --------------------
# The join pipeline is a raw fact stream band-joined to a sparse
# per-second dimension stream, then windowed: every fact row matches
# EXACTLY the dim row of its key and event-second (band
# fact.ts − dim.ts ∈ [0, WINDOW_MS−1]), so the golden is a pure
# per-(window, key) fold of the fact feed plus the deterministic dim
# value.  A mid-run slice of the feed is SKEWED (one celebrity key takes
# JOIN_HOT_SHARE of the rows — long build chains, the closed-loop
# policy's trigger) and periodically LATE (rows shifted back
# JOIN_LATE_MS, still inside the join retention, and safe downstream
# because the join forwards its watermark clamped by retention).
JOIN_SKEW_START_FRAC = 0.30
JOIN_SKEW_END_FRAC = 0.70
JOIN_HOT_SHARE = 0.6
JOIN_LATE_EVERY = 7
JOIN_LATE_FRAC = 0.1
JOIN_LATE_MS = 2500
JOIN_BAND_MS = WINDOW_MS
JOIN_RETENTION_MS = 6000


def join_skew_slice(total_batches: int) -> tuple[int, int]:
    return (
        int(total_batches * JOIN_SKEW_START_FRAC),
        int(total_batches * JOIN_SKEW_END_FRAC),
    )


def join_batch_arrays(
    i: int, batch_rows: int, pace: float, total_batches: int
):
    """Fact-side batch i: ``batch_arrays`` plus the skewed + late
    mid-run slice.  Deterministic in (i, total_batches) — parent golden
    and child source share it."""
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    lo, hi = join_skew_slice(total_batches)
    if lo <= i < hi:
        rng = np.random.default_rng(77_000_003 + i)
        hot = rng.random(batch_rows) < JOIN_HOT_SHARE
        keys = np.where(hot, 0, keys)
        if (i - lo) % JOIN_LATE_EVERY == 0 and i > lo:
            late = rng.random(batch_rows) < JOIN_LATE_FRAC
            ts = np.where(late, ts - JOIN_LATE_MS, ts)
    return ts, keys, vals


def dim_value(k: int, second: int) -> float:
    """The dimension stream's deterministic enrichment value for key k
    during event-second ``second`` (T0-relative)."""
    return round((second % 97) * 1.5 + k * 0.25, 4)


def golden_update_join(
    agg: dict, i: int, batch_rows: int, pace: float, total_batches: int
):
    """Fold fact batch i into {(ws, key): [cnt, sum]} — with the
    exactly-one dim match per fact row, the joined window aggregate is
    count(fact rows), avg(fact readings), and the (constant within the
    window) dim value.  Vectorized per group like golden_update."""
    ts, keys, vals = join_batch_arrays(i, batch_rows, pace, total_batches)
    ws = (ts // WINDOW_MS) * WINDOW_MS
    uniq, cnts, [[sums]] = _group_reduce(
        ws * N_KEYS + keys, [(vals, [np.add])]
    )
    for u, c, sm in zip(uniq.tolist(), cnts.tolist(), sums.tolist()):
        w, k = divmod(u, N_KEYS)
        a = agg.setdefault((w, f"sensor_{k}"), [0, 0.0])
        a[0] += c
        a[1] += sm


SLIDE_MS = 250  # 1000ms window / 250ms slide = 4-way emission fan-out


def golden_update_sliding(agg: dict, i: int, batch_rows: int, pace: float):
    """Fold batch i into sliding-window golden {(ws, key): [cnt, min,
    max, sum]}: every row belongs to WINDOW_MS/SLIDE_MS consecutive
    windows (epoch-aligned slide indices, like the engine's on-device
    fan-out)."""
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    for j in range(WINDOW_MS // SLIDE_MS):
        ws = (ts // SLIDE_MS - j) * SLIDE_MS
        uniq, cnts, [[mins, maxs, sums]] = _group_reduce(
            ws * N_KEYS + keys, [(vals, [np.minimum, np.maximum, np.add])]
        )
        _merge_tumbling(agg, uniq, cnts, mins, maxs, sums)


KAFKA_PARTS = int(os.environ.get("SOAK_KAFKA_PARTS", 2))


def encode_json_rows(ts, keys, vals):
    """Vectorized emit_measurements-shaped JSON encode (np.char at C
    speed) for the kafka pipeline's staged feed."""
    s = np.char.add(b'{"occurred_at_ms":', ts.astype("S20"))
    s = np.char.add(s, b',"sensor_name":"sensor_')
    s = np.char.add(s, keys.astype("S4"))
    s = np.char.add(s, b'","reading":')
    s = np.char.add(s, vals.astype("S32"))
    s = np.char.add(s, b"}")
    return s.tolist()


def kafka_prep_and_feed(args, total_batches, log):
    """Start the parent-owned broker (the durable log that SURVIVES child
    kills — the restored child seeks back to its checkpointed offsets),
    pre-encode every chunk (the paced feed loop must only append staged
    slices), and return (broker, feed_thread, last_close_ws,
    feed_anchor).  Rows interleave across KAFKA_PARTS partitions per
    batch so both partitions' event-time ranges stay aligned
    (per-partition watermarks advance together).

    The feed is scheduled against the ABSOLUTE event-time origin: one
    calibration batch estimates the full staging wall, T0 is re-anchored
    just past the estimated staging end (rounded to a window boundary),
    and each batch is appended when the wall clock reaches its event
    time — so event time ≈ wall time with near-zero offset, which is
    what lets the engine's event-time lag histograms (bucketed
    exponentially) resolve real latency percentiles instead of one huge
    constant.  ``feed_anchor["epoch"]`` carries the wall second T0 maps
    to; the telemetry report subtracts ``feed_epoch_ms − T0`` (≈0 here)
    to convert raw event-time lag into end-to-end latency."""
    global T0
    import threading

    from denormalized_tpu.testing.mock_kafka import MockKafkaBroker

    broker = MockKafkaBroker().start()
    broker.create_topic("soak", partitions=KAFKA_PARTS)
    # calibration: stage one throwaway batch, scale to the run, pad 30%
    # + 2s (an UNDERestimate only means the first batches burst as
    # catch-up; the offset still collapses once the feed reaches its
    # schedule)
    t_cal = time.monotonic()
    cal_ts, cal_keys, cal_vals = batch_arrays(
        0, args.batch_rows, args.pace, seed=SEED_LEFT
    )
    cal_rows = encode_json_rows(cal_ts, cal_keys, cal_vals)
    for p in range(KAFKA_PARTS):
        rp = cal_rows[p::KAFKA_PARTS]
        MockKafkaBroker.stage_batched(
            rp, ts_ms=int(cal_ts[0]), records_per_batch=len(rp),
            base_offset=0,
        )
    est_s = (time.monotonic() - t_cal) * total_batches * 1.3 + 2.0
    if "SOAK_T0" not in os.environ:  # an explicit pin wins (determinism)
        T0 = (
            int((time.time() + est_s) * 1000) // WINDOW_MS
        ) * WINDOW_MS
    log(f"kafka soak: staging est {est_s:.0f}s — event origin T0={T0}")

    span_ms = int(total_batches * args.batch_rows * 1000.0 / args.pace)
    # two full windows of slack before the stream end: the child exits on
    # seeing this window, closed by the NATURAL watermark (events beyond
    # its end), no idle-hint dependence at the boundary
    last_close_ws = ((T0 + span_ms) // WINDOW_MS - 2) * WINDOW_MS
    staged = [[] for _ in range(KAFKA_PARTS)]
    base = [0] * KAFKA_PARTS
    t_prep = time.monotonic()
    for i in range(total_batches):
        ts, keys, vals = batch_arrays(i, args.batch_rows, args.pace,
                                      seed=SEED_LEFT)
        rows = encode_json_rows(ts, keys, vals)
        for p in range(KAFKA_PARTS):
            rp = rows[p::KAFKA_PARTS]
            staged[p].append(MockKafkaBroker.stage_batched(
                rp, ts_ms=int(ts[0]), records_per_batch=len(rp),
                base_offset=base[p],
            ))
            base[p] += len(rp)
        if i and i % max(1, total_batches // 10) == 0:
            log(f"kafka soak: staged {i}/{total_batches} chunks "
                f"({time.monotonic() - t_prep:.0f}s)")
    log(f"kafka soak: staged all {total_batches} chunks in "
        f"{time.monotonic() - t_prep:.0f}s; feed starts now")

    feed_anchor: dict = {"epoch": T0 / 1000.0}

    def feed():
        # absolute event-time schedule (see docstring): batch i's rows
        # end at event T0 + (i+1)*batch_span, so they are appended at
        # that WALL instant — event time tracks wall time directly
        t0_wall = T0 / 1000.0
        for i in range(total_batches):
            due = t0_wall + (i + 1) * args.batch_rows / args.pace
            delay = due - time.time()
            if delay > 0:
                time.sleep(delay)
            for p in range(KAFKA_PARTS):
                broker.append_staged("soak", p, staged[p][i])

    th = threading.Thread(target=feed, daemon=True)
    th.start()
    return broker, th, last_close_ws, feed_anchor


SESSION_GAP_MS = 300


# -- chaos mode: the kafka exactly-once soak under an armed FaultPlan ----


def chaos_plan(seed: int) -> dict:
    """The chaos schedule layered over the kafka soak.  Counters reset
    with each respawned child (the plan arms at import from the env), so
    ``times`` caps are PER SEGMENT.  Rates are tuned to the soak's fetch
    (~20/s across 2 partitions, much higher during post-kill catch-up)
    and commit (~1 per SOAK_CKPT_S) cadences so every rule fires within
    a kill interval."""
    return {
        "seed": seed,
        "rules": [
            # broker flap: transport-marker errors ride the reader's
            # log-and-reconnect path, then heal
            {"name": "fetch_flap", "site": "kafka.fetch", "kind": "error",
             "message": "recv: injected broker flap", "prob": 0.01,
             "times": 6},
            # worker crash: a non-transport error escapes the reader and
            # exercises the prefetch supervisor's restart-from-snapshot
            {"name": "worker_crash", "site": "kafka.fetch", "kind": "error",
             "message": "injected worker crash", "after": 250, "times": 1},
            # torn state write: only epoch-suffixed snapshot blobs (the
            # "@" restriction), caught by header verification at restore
            # → epoch fallback.  ONE per segment: fallback depth is
            # RETAINED_EPOCHS=2, so two tears landing in two consecutive
            # retained epochs would (by design) be unrecoverable — the
            # plan must stay inside the failure envelope it proves out
            {"name": "torn_snapshot", "site": "lsm.put", "kind": "torn",
             "key_substr": "@", "prob": 0.08, "times": 1},
            # commit-time transient error: absorbed by the coordinator's
            # bounded retry
            {"name": "commit_hiccup", "site": "checkpoint.commit",
             "kind": "error", "message": "injected commit hiccup",
             "prob": 0.15, "times": 2},
            # background jitter on state flushes
            {"name": "flush_latency", "site": "lsm.flush",
             "kind": "latency", "ms": 5, "prob": 0.05, "times": 20},
            # cold-tier spill write tear: only fires when the run is
            # budgeted enough to spill (the kafka soak's window state is
            # small, so this usually stays dormant here — the bigstate
            # soak's own plan exercises the tier deterministically).
            # Caught by copy_block_to_epoch's integrity check: the epoch
            # refuses the torn block, the previous intact epoch stays
            # the recovery point
            {"name": "spill_put_torn", "site": "lsm.spill_put",
             "kind": "torn", "prob": 0.05, "times": 1},
        ],
    }


def bigstate_fault_plan(seed: int) -> dict:
    """Spill-site chaos for the bigstate soak: transient reload flaps
    (healed by get_block's bounded retry), one eviction-write failure
    (degrades to keep-resident + backpressure, never kills the query),
    and a torn manifest write (best-effort metadata, logged only)."""
    return {
        "seed": seed,
        "rules": [
            {"name": "spill_get_flap", "site": "lsm.spill_get",
             "kind": "error", "message": "injected spill reload flap",
             "after": 20, "times": 2},
            {"name": "spill_put_fail", "site": "lsm.spill_put",
             "kind": "error", "message": "injected spill write failure",
             "after": 40, "times": 1},
            {"name": "spill_manifest_torn", "site": "spill.manifest",
             "kind": "torn", "after": 5, "times": 1},
        ],
    }


#: spill-site rules the bigstate acceptance gate requires to fire
BIGSTATE_REQUIRED_RULES = (
    "spill_get_flap", "spill_put_fail", "spill_manifest_torn",
)


#: the four failure modes the chaos acceptance gate requires to fire
CHAOS_REQUIRED_RULES = (
    "fetch_flap", "worker_crash", "torn_snapshot", "commit_hiccup",
)


def chaos_sim_sequence(spec: dict) -> list[dict]:
    """Drive a fresh plan through a fixed synthetic call sequence and
    return its event log — run twice, identical logs prove the seed fully
    determines the injection sequence."""
    from denormalized_tpu.runtime.faults import FaultPlan

    p = FaultPlan(dict(spec))
    for i in range(1200):
        try:
            p.on("kafka.fetch", key="soak:0")
        except Exception:
            pass
        if i % 20 == 0:
            try:
                p.on("lsm.put", key=f"window_1@{1000 + i}",
                     payload=b"x" * 64)
            except Exception:
                pass
        if i % 40 == 0:
            try:
                p.on("checkpoint.commit")
            except Exception:
                pass
            try:
                p.on("lsm.flush")
            except Exception:
                pass
    return p.event_log()


def read_chaos_events(paths) -> list[dict]:
    """One 'chaos' event dict per segment file that wrote one."""
    out = []
    for path in paths:
        last = None
        try:
            f = open(path)
        except FileNotFoundError:
            continue
        with f:
            for line in f:
                try:
                    o = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if o.get("event") == "chaos":
                    last = {k: v for k, v in o.items() if k != "event"}
        if last is not None:
            out.append(last)
    return out


def burst_ts(ts: "np.ndarray") -> "np.ndarray":
    """Squeeze each second's events into its first 600ms: the 400ms
    event-time silence every second (> SESSION_GAP_MS) closes one session
    per key per second; the mapping is monotonic, so batch-min watermarks
    are preserved."""
    sec = (ts // 1000) * 1000
    frac = ts - sec
    return sec + (frac * 3) // 5


def golden_update_session(agg: dict, i: int, batch_rows: int, pace: float):
    """Fold batch i into {(key, sec): [cnt, min_v, max_v, sum_v,
    min_ts, max_ts]} — one session per key per second under burst_ts;
    emitted start = min_ts, end = max_ts + SESSION_GAP_MS."""
    ts, keys, vals = batch_arrays(i, batch_rows, pace, seed=SEED_LEFT)
    bts = burst_ts(ts)
    sec = (bts // 1000) * 1000
    comp = sec * N_KEYS + keys
    uniq, cnts, [[vmins, vmaxs, vsums], [tmins, tmaxs]] = _group_reduce(
        comp, [
            (vals, [np.minimum, np.maximum, np.add]),
            (bts, [np.minimum, np.maximum]),
        ]
    )
    for u, c, mn, mx, sm, t0, t1 in zip(
        uniq.tolist(), cnts.tolist(), vmins.tolist(), vmaxs.tolist(),
        vsums.tolist(), tmins.tolist(), tmaxs.tolist(),
    ):
        w, k = divmod(u, N_KEYS)
        a = agg.setdefault(
            (w, f"sensor_{k}"),
            [0, float("inf"), float("-inf"), 0.0, float("inf"), 0],
        )
        a[0] += c
        if mn < a[1]:
            a[1] = mn
        if mx > a[2]:
            a[2] = mx
        a[3] += sm
        if t0 < a[4]:
            a[4] = t0
        if t1 > a[5]:
            a[5] = t1


# -- child ---------------------------------------------------------------


def child_main() -> None:
    sys.path.insert(0, str(REPO))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from denormalized_tpu import Context, col
    from denormalized_tpu.api import functions as F
    from denormalized_tpu.api.context import EngineConfig
    from denormalized_tpu.common.constants import (
        WINDOW_END_COLUMN,
        WINDOW_START_COLUMN,
    )
    from denormalized_tpu.common.record_batch import RecordBatch
    from denormalized_tpu.common.schema import DataType, Field, Schema
    from denormalized_tpu.sources.base import (
        PartitionReader,
        Source,
        attach_canonical_timestamp,
        canonicalize_schema,
    )

    pipeline = os.environ.get("SOAK_PIPELINE", "simple")
    batch_rows = int(os.environ["SOAK_BATCH_ROWS"])
    pace = float(os.environ["SOAK_PACE"])
    total_batches = int(os.environ["SOAK_TOTAL_BATCHES"])
    ckpt_dir = os.environ["SOAK_CKPT_DIR"]
    out_path = os.environ["SOAK_OUT"]

    schema = Schema([
        Field("occurred_at_ms", DataType.INT64, nullable=False),
        Field("sensor_name", DataType.STRING, nullable=False),
        Field("reading", DataType.FLOAT64),
    ])
    key_names = np.array(
        [f"sensor_{k}" for k in range(N_KEYS)], dtype=object
    )

    class SoakPartition(PartitionReader):
        """Deterministic paced feed with Kafka-grade restore semantics:
        batch i regenerates from the index, so offset_restore is a pure
        fast-forward.  Pacing re-anchors at the restored index — the
        source IS the producer here, so a restored child continues at the
        paced rate from the checkpoint cut (event time simply lags wall
        clock by the downtime; window contents are index-deterministic
        either way)."""

        def __init__(self, seed):
            self._seed = seed
            self._i = 0
            self._anchor_wall = None
            self._anchor_i = 0

        def read(self, timeout_s=None):
            if self._i >= total_batches:
                return None
            now = time.monotonic()
            if self._anchor_wall is None:
                self._anchor_wall = now
                self._anchor_i = self._i
            due = self._anchor_wall + (
                (self._i - self._anchor_i) * batch_rows / pace
            )
            if now < due:
                time.sleep(min(due - now, timeout_s or (due - now)))
                if time.monotonic() < due:
                    # not due yet: an empty heartbeat batch (canonical ts
                    # column attached — downstream requires it on every
                    # batch, rowful or not)
                    return attach_canonical_timestamp(
                        RecordBatch.empty(schema), "occurred_at_ms",
                        fallback_ms=int(time.time() * 1000),
                    )
            if pipeline == "join":
                ts, keys, vals = join_batch_arrays(
                    self._i, batch_rows, pace, total_batches
                )
            elif pipeline == "join_dense":
                ts, keys, vals = jd_batch_arrays(self._i, batch_rows, pace)
            else:
                ts, keys, vals = batch_arrays(
                    self._i, batch_rows, pace, seed=self._seed
                )
            if pipeline == "session":
                ts = burst_ts(ts)
            self._i += 1
            b = RecordBatch(schema, [ts, key_names[keys], vals])
            return attach_canonical_timestamp(
                b, "occurred_at_ms", fallback_ms=int(time.time() * 1000)
            )

        def offset_snapshot(self):
            return {"i": self._i}

        def offset_restore(self, snap):
            self._i = int(snap["i"])
            self._anchor_wall = None  # re-anchor pacing at the restored i

    canon = canonicalize_schema(schema)

    class SoakSource(Source):
        def __init__(self, seed, name):
            self._seed = seed
            self.name = name

        @property
        def schema(self):
            return canon

        def partitions(self):
            return [SoakPartition(self._seed)]

        @property
        def unbounded(self):
            return False

    cfg = EngineConfig(
        min_batch_bucket=batch_rows,
        min_window_slots=32,
        checkpoint=True,
        checkpoint_interval_s=float(os.environ.get("SOAK_CKPT_S", 2.0)),
        state_backend_path=ckpt_dir,
        emit_on_close=True,
        source_idle_timeout_ms=int(
            os.environ.get("SOAK_IDLE_MS", 1000)
        ) or None,
        # per-segment JSONL telemetry stream (obs registry snapshots):
        # the parent merges segments' histograms into the report's
        # p50/p95/p99 e2e latency + max watermark lag + fault timeline.
        # Line-buffered writer — a SIGKILL still leaves the last
        # completed snapshot behind.
        metrics_jsonl_path=os.environ.get("SOAK_OBS_OUT"),
        metrics_jsonl_interval_s=1.0,
    )
    ctx = Context(cfg)

    def qd_aggs():
        # the foldable set MINUS variance: the shared store's variance
        # pivot differs from an independent oracle's, so stddev is not
        # byte-comparable across the two runs (docs/multi_query.md)
        return [
            F.count(col("reading")).alias("count"),
            F.sum(col("reading")).alias("sum"),
            F.min(col("reading")).alias("min"),
            F.max(col("reading")).alias("max"),
            F.avg(col("reading")).alias("average"),
        ]

    dim_user = Schema([
        Field("dim_at_ms", DataType.INT64, nullable=False),
        Field("dim_sensor", DataType.STRING, nullable=False),
        Field("w", DataType.FLOAT64),
    ])
    dim_schema = canonicalize_schema(dim_user)
    dim_seconds = -(-total_batches * batch_rows // int(pace)) + 1
    t0_sec = T0 // 1000

    class DimPartition(PartitionReader):
        """One batch per event-second: N_KEYS enrichment rows at the
        second's absolute boundary, value = dim_value(k, s).  Paced at
        one batch per wall second (``paced=False`` replays densely for
        the oracle children); restore fast-forwards by batch index like
        SoakPartition."""

        def __init__(self, paced=True):
            self._paced = paced
            self._i = 0
            self._anchor_wall = None
            self._anchor_i = 0

        def read(self, timeout_s=None):
            if self._i >= dim_seconds:
                return None
            if self._paced:
                now = time.monotonic()
                if self._anchor_wall is None:
                    self._anchor_wall = now
                    self._anchor_i = self._i
                due = self._anchor_wall + (self._i - self._anchor_i)
                if now < due:
                    time.sleep(min(due - now, timeout_s or (due - now)))
                    if time.monotonic() < due:
                        return attach_canonical_timestamp(
                            RecordBatch.empty(dim_user), "dim_at_ms",
                            fallback_ms=int(time.time() * 1000),
                        )
            s = self._i
            self._i += 1
            ts = np.full(
                N_KEYS, (t0_sec + s) * 1000, dtype=np.int64
            )
            vals = np.array(
                [dim_value(k, s) for k in range(N_KEYS)]
            )
            b = RecordBatch(dim_user, [ts, key_names.copy(), vals])
            return attach_canonical_timestamp(
                b, "dim_at_ms", fallback_ms=int(time.time() * 1000)
            )

        def offset_snapshot(self):
            return {"i": self._i}

        def offset_restore(self, snap):
            self._i = int(snap["i"])
            self._anchor_wall = None

    class DimSource(Source):
        name = "soak_dim"

        def __init__(self, paced=True):
            self._paced = paced

        @property
        def schema(self):
            return dim_schema

        def partitions(self):
            return [DimPartition(self._paced)]

        @property
        def unbounded(self):
            return False

    if pipeline in ("query_dense", "join_dense"):
        # ISSUE 16 acceptance: 50 queries register/deregister LIVE on
        # one shared slice pipeline (staggered event-time arrivals,
        # incl. mid-epoch joins), SIGKILLed mid-run; every query's
        # committed emissions must be byte-identical to an independent
        # uninterrupted oracle from its first exact window.  The
        # schedule is event-time keyed, so this child re-issues it
        # VERBATIM every incarnation: subscribers the restored
        # checkpoint carried adopt their snapshotted cursor (orphan
        # adoption by tag), departed tags stay departed, future ops
        # fire when stream time reaches them.
        # join_dense (ISSUE 17) is the same contract one operator
        # deeper: every query windows over the SAME fact×dim interval
        # join, so the group runs ONE StreamingJoinExec — its sides
        # snapshot in the SAME epoch cut as the slice partials and the
        # per-tag cursors.
        from denormalized_tpu.runtime.multi_query import SharedPipeline

        if pipeline == "join_dense":
            cfg.join_retention_ms = JD_RETENTION_MS
            # both sides' band values are in-order (sorted fact batches,
            # strictly increasing dim seconds), so zero slack is exact
            cfg.join_band_slack_ms = 0
            sched = jd_schedule(total_batches, batch_rows, pace)
            unit_ms = JD_UNIT_MS
            fact = ctx.from_source(
                SoakSource(SEED_LEFT, "soak_fact"), name="soak_fact"
            )
            dim = ctx.from_source(DimSource(), name="soak_dim")
            base = fact.join(
                dim, "inner", ["sensor_name"], ["dim_sensor"],
                band=("occurred_at_ms", "dim_at_ms", 0, JOIN_BAND_MS - 1),
            )
        else:
            sched = qd_schedule(total_batches, batch_rows, pace)
            unit_ms = QD_UNIT_MS
            base = ctx.from_source(
                SoakSource(SEED_LEFT, "soak_qd"), name="soak_qd"
            )
        aggs = qd_aggs()

        def q_stream(spec):
            return base.filter(col("reading") > spec["thr"]).window(
                ["sensor_name"], aggs, spec["L"], spec["S"]
            )

        with open(out_path, "a", buffering=1) as out:
            out.write(json.dumps({"event": "ready", "t": time.time()}) + "\n")
            announced: list = []

            def mk_sink(qid):
                def sink(b):
                    coord = getattr(ctx, "_last_coord", None)
                    if not announced:
                        # exactly-once output protocol: announce the
                        # recovery point before any window line (the
                        # parent clips the predecessor's uncommitted
                        # suffix at this epoch)
                        announced.append(True)
                        out.write(json.dumps({
                            "event": "restored",
                            "epoch": (
                                (coord.restored_epoch or 0)
                                if coord is not None else None
                            ),
                        }) + "\n")
                    ep = (
                        (coord.committed_epoch or 0) + 1
                        if coord is not None else None
                    )
                    ws = b.column(WINDOW_START_COLUMN)
                    names = b.column("sensor_name")
                    cols = [
                        b.column(c)
                        for c in ("count", "sum", "min", "max", "average")
                    ]
                    for i in range(b.num_rows):
                        # full float repr — the parent compares these
                        # for byte-identity, not tolerance
                        rec = {
                            "q": qid, "ws": int(ws[i]),
                            "key": str(names[i]),
                            "count": int(cols[0][i]),
                            "sum": float(cols[1][i]),
                            "min": float(cols[2][i]),
                            "max": float(cols[3][i]),
                            "avg": float(cols[4][i]),
                        }
                        if ep is not None:
                            rec["ep"] = ep
                        out.write(json.dumps(rec) + "\n")
                return sink

            initial = [s for s in sched if "join" not in s]
            sp = SharedPipeline(
                ctx,
                [(q_stream(s), mk_sink(s["qid"])) for s in initial],
                labels=[f"q{s['qid']}" for s in initial],
            )
            assert sp.root.unit_ms == unit_ms, sp.root.unit_ms
            # one build per process incarnation: live joins/leaves must
            # NEVER rebuild the shared pipeline (the parent gates on
            # at most one of these per segment)
            out.write(json.dumps({"event": "build", "t": time.time()}) + "\n")
            for s in sched:
                if "join" not in s:
                    continue
                tag = sp.register(
                    q_stream(s), mk_sink(s["qid"]),
                    label=f"q{s['qid']}", when_ts=s["join"],
                )
                assert tag == s["qid"], (tag, s["qid"])
            for s in sched:
                if "leave" in s:
                    sp.deregister(s["qid"], when_ts=s["leave"])
            sp.run()
            m = sp.root.metrics()
            out.write(json.dumps({"event": "metrics", **{
                k: v for k, v in m.items() if isinstance(v, (int, float))
            }}) + "\n")
            out.write(json.dumps({"event": "done", "t": time.time()}) + "\n")
        return

    if pipeline in ("query_dense_oracle", "join_dense_oracle"):
        # per-query independent UNINTERRUPTED oracles over the same
        # index-deterministic feed, replayed densely (no pacing): the
        # byte-identity referent for the live shared run.  Slice mode
        # pins to the shared group's gcd unit so fold order matches
        # (the aggregates carry extrema, so both runs take the lexsort
        # fold lane).  The join_dense oracle runs each query's OWN
        # fact×dim join under the same retention/band-slack config —
        # the joined row multiset is interleaving-free, so the shared
        # run must reproduce it byte for byte.
        from denormalized_tpu.sources.memory import MemorySource

        joined_oracle = pipeline == "join_dense_oracle"
        sched = (
            jd_schedule(total_batches, batch_rows, pace) if joined_oracle
            else qd_schedule(total_batches, batch_rows, pace)
        )
        feed = []
        for i in range(total_batches):
            if joined_oracle:
                ts, keys, vals = jd_batch_arrays(i, batch_rows, pace)
            else:
                ts, keys, vals = batch_arrays(
                    i, batch_rows, pace, seed=SEED_LEFT
                )
            feed.append(RecordBatch(schema, [ts, key_names[keys], vals]))
        with open(out_path, "a", buffering=1) as out:
            for spec in sched:
                ocfg = EngineConfig(
                    min_batch_bucket=batch_rows,
                    min_window_slots=32,
                    slice_windows=True,
                    slice_unit_ms=JD_UNIT_MS if joined_oracle
                    else QD_UNIT_MS,
                    emit_on_close=True,
                )
                if joined_oracle:
                    ocfg.join_retention_ms = JD_RETENTION_MS
                    ocfg.join_band_slack_ms = 0
                octx = Context(ocfg)
                src = octx.from_source(
                    MemorySource.from_batches(
                        feed, timestamp_column="occurred_at_ms"
                    ),
                    name="soak_fact" if joined_oracle else "soak_qd",
                )
                if joined_oracle:
                    src = src.join(
                        octx.from_source(
                            DimSource(paced=False), name="soak_dim"
                        ),
                        "inner", ["sensor_name"], ["dim_sensor"],
                        band=(
                            "occurred_at_ms", "dim_at_ms", 0,
                            JOIN_BAND_MS - 1,
                        ),
                    )
                ds = src.filter(col("reading") > spec["thr"]).window(
                    ["sensor_name"], qd_aggs(), spec["L"], spec["S"]
                )
                for b in ds.stream():
                    if not b.schema.has(WINDOW_START_COLUMN):
                        continue
                    ws = b.column(WINDOW_START_COLUMN)
                    names = b.column("sensor_name")
                    cols = [
                        b.column(c)
                        for c in ("count", "sum", "min", "max", "average")
                    ]
                    for i in range(b.num_rows):
                        out.write(json.dumps({
                            "q": spec["qid"], "ws": int(ws[i]),
                            "key": str(names[i]),
                            "count": int(cols[0][i]),
                            "sum": float(cols[1][i]),
                            "min": float(cols[2][i]),
                            "max": float(cols[3][i]),
                            "avg": float(cols[4][i]),
                        }) + "\n")
            out.write(json.dumps({"event": "done", "t": time.time()}) + "\n")
        return

    last_close_ws = (
        int(os.environ["SOAK_LAST_CLOSE_WS"])
        if pipeline == "kafka" else None
    )
    if pipeline == "kafka":
        # the reference-shaped path end to end: broker -> native wire
        # client -> native JSON decode -> window, checkpointed offsets
        # restored by seek.  The feed keeps running across kills (the
        # broker is the durable log), so recovery includes backlog
        # catch-up — exactly a real deployment's restart
        ds = ctx.from_topic(
            "soak",
            schema=schema,
            bootstrap_servers=os.environ["SOAK_BOOTSTRAP"],
            timestamp_column="occurred_at_ms",
        ).window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("count"),
                F.min(col("reading")).alias("min"),
                F.max(col("reading")).alias("max"),
                F.avg(col("reading")).alias("average"),
            ],
            WINDOW_MS,
        )
    elif pipeline == "udaf":
        # stateful Python accumulator (host-frame path, udaf_exec):
        # Accumulator.state()/merge() snapshots ride the checkpoint —
        # the SerializableAccumulator contract through repeated kills
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
        ds = ctx.from_source(
            SoakSource(SEED_LEFT, "soak_u"), name="soak_u"
        ).window(
            ["sensor_name"],
            [
                spread(col("reading")).alias("spread"),
                F.count(col("reading")).alias("count"),
            ],
            WINDOW_MS,
        )
    elif pipeline == "approx":
        # sketch-native approximate aggregates on the slice store
        # (docs/approx_aggregates.md): approx_distinct rides an HLL
        # register plane whose scatter-max fold is associative and
        # commutative, so the plane — and its integer estimate — is
        # independent of how the feed was split across checkpoint
        # segments.  The parent's golden replays the SAME kernels
        # (ops/sketches.py loaded by file path; pure numpy, keeps the
        # parent jax-free) and holds every committed estimate to EXACT
        # integer equality through repeated SIGKILLs — the sketch
        # restore path is bit-faithful or this gate goes red.
        cfg.slice_windows = True
        cfg.slice_unit_ms = SLIDE_MS  # kills land mid-window, mid-slice
        ds = ctx.from_source(
            SoakSource(SEED_LEFT, "soak_ax"), name="soak_ax"
        ).window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("count"),
                F.approx_distinct(col("reading")).alias("distinct"),
            ],
            WINDOW_MS,
        )
    elif pipeline == "bigstate":
        # larger-than-memory session state: phase A opens SOAK_BS_KEYS
        # singleton sessions (gap = the whole phase-A event span, so all
        # of them stay open simultaneously); phase B advances the
        # watermark in waves of SOAK_BS_WAVE keys so sessions close
        # progressively instead of one giant reload-everything sweep.
        # Budgeted children (SOAK_BS_BUDGET > 0) run the cold tier +
        # checkpointing and get SIGKILLed; the reference child runs the
        # identical feed unbudgeted — emissions must match byte-for-byte.
        bs_keys = int(os.environ["SOAK_BS_KEYS"])
        bs_wave = int(os.environ["SOAK_BS_WAVE"])
        bs_budget = int(os.environ.get("SOAK_BS_BUDGET", "0") or 0)
        if bs_budget:
            cfg.state_budget_bytes = bs_budget
        else:
            # reference (unbudgeted) child: same feed, no cold tier, no
            # snapshots — the byte-identical oracle the budgeted run is
            # compared against
            cfg.checkpoint = False
        bs_gap = bs_keys  # DT = 1ms per key
        wave_rows = 64
        a_batches = -(-bs_keys // batch_rows)
        waves = -(-bs_keys // bs_wave)

        bs_user = Schema([
            Field("occurred_at_ms", DataType.INT64, nullable=False),
            Field("sensor_id", DataType.INT64, nullable=False),
            Field("reading", DataType.FLOAT64),
        ])
        bs_schema = canonicalize_schema(bs_user)

        class BigstatePartition(PartitionReader):
            """Index-deterministic feed (restore = fast-forward)."""

            def __init__(self):
                self._i = 0

            def read(self, timeout_s=None):
                i = self._i
                if i >= a_batches + waves:
                    return None
                self._i += 1
                if i < a_batches:
                    lo = i * batch_rows
                    kids = np.arange(
                        lo, min(lo + batch_rows, bs_keys), dtype=np.int64
                    )
                    ts = T0 + kids  # DT = 1ms
                else:
                    j = i - a_batches + 1
                    base = bs_keys + (j - 1) * wave_rows
                    kids = np.arange(
                        base, base + wave_rows, dtype=np.int64
                    )
                    ts = np.full(
                        wave_rows, T0 + bs_gap + j * bs_wave,
                        dtype=np.int64,
                    )
                vals = (kids % 997) * 0.5 + 1.0
                b = RecordBatch(bs_user, [ts, kids, vals])
                return attach_canonical_timestamp(
                    b, "occurred_at_ms",
                    fallback_ms=int(time.time() * 1000),
                )

            def offset_snapshot(self):
                return {"i": self._i}

            def offset_restore(self, snap):
                self._i = int(snap["i"])

        class BigstateSource(Source):
            name = "bigstate"

            @property
            def schema(self):
                return bs_schema

            def partitions(self):
                return [BigstatePartition()]

            @property
            def unbounded(self):
                return False

        ds = ctx.from_source(
            BigstateSource(), name="bigstate"
        ).session_window(
            ["sensor_id"],
            [
                F.count(col("reading")).alias("count"),
                F.min(col("reading")).alias("min"),
                F.max(col("reading")).alias("max"),
                F.avg(col("reading")).alias("average"),
            ],
            bs_gap,
        )
    elif pipeline == "session":
        ds = ctx.from_source(
            SoakSource(SEED_LEFT, "soak_s"), name="soak_s"
        ).session_window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("count"),
                F.min(col("reading")).alias("min"),
                F.max(col("reading")).alias("max"),
                F.avg(col("reading")).alias("average"),
            ],
            SESSION_GAP_MS,
        )
    elif pipeline == "join":
        # skew-adaptive interval join (ISSUE 15, docs/joins.md): a raw
        # fact stream — skewed + late mid-run (join_batch_arrays) —
        # band-joined to a sparse per-second dimension stream on the
        # sensor key (fact.ts − dim.ts ∈ [0, WINDOW_MS−1]: exactly the
        # dim row of the fact row's event-second), then windowed.  The
        # skew slice builds celebrity chains on the fact side, the
        # closed-loop policy sub-partitions the hot key live (visible in
        # the telemetry as dnz_join_adaptations_total), kills land while
        # hot blocks are live, and the restored child rebuilds them from
        # the snapshot's representative rows.
        cfg.join_retention_ms = JOIN_RETENTION_MS
        # band-aware eviction (ISSUE 17, docs/joins.md): the band is far
        # tighter than retention, so band-dead batches release early.
        # Slack = the feed's bounded lateness — late rows sit at most
        # JOIN_LATE_MS below an on-time batch's band minimum, which is
        # exactly the horizon the slack re-opens
        cfg.join_band_slack_ms = JOIN_LATE_MS
        left = ctx.from_source(
            SoakSource(SEED_LEFT, "soak_fact"), name="soak_fact"
        )
        right = ctx.from_source(DimSource(), name="soak_dim")
        ds = left.join(
            right, "inner", ["sensor_name"], ["dim_sensor"],
            band=("occurred_at_ms", "dim_at_ms", 0, JOIN_BAND_MS - 1),
        ).window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("count"),
                F.avg(col("reading")).alias("avg_t"),
                F.avg(col("w")).alias("avg_h"),
            ],
            WINDOW_MS,
        )
    else:
        ds = ctx.from_source(SoakSource(SEED_LEFT, "soak"), name="soak").window(
            ["sensor_name"],
            [
                F.count(col("reading")).alias("count"),
                F.min(col("reading")).alias("min"),
                F.max(col("reading")).alias("max"),
                F.avg(col("reading")).alias("average"),
            ],
            WINDOW_MS,
            SLIDE_MS if pipeline == "sliding" else None,
        )
    it = ds.stream()
    if pipeline == "bigstate":
        # the drive loop only wakes on EMITTED batches, and phase A
        # emits nothing for minutes — a side sampler thread records the
        # state accounting (working set, spill counters) on a wall
        # cadence into its own file (no interleaving with the emission
        # stream; state_info reads are single-writer-defensive by
        # contract)
        import threading as _threading

        def _state_sampler():
            with open(out_path + ".state", "a", buffering=1) as sf:
                while True:
                    time.sleep(1.0)
                    try:
                        root = getattr(ctx, "_last_physical", None)
                        if root is None:
                            continue
                        info = None
                        stack = [root]
                        while stack:
                            cur = stack.pop()
                            if type(cur).__name__ == "SessionWindowExec":
                                info = cur.state_info()
                                break
                            stack.extend(cur.children)
                        if info:
                            sf.write(json.dumps({
                                "event": "state",
                                "bytes": info.get("state_bytes"),
                                "evictable": info.get("evictable_bytes"),
                                "live_keys": info.get("live_keys"),
                                "spilled_bytes": info.get(
                                    "spilled_bytes", 0
                                ),
                                "spilled_keys": info.get(
                                    "spilled_keys", 0
                                ),
                                "spill": info.get("spill"),
                            }) + "\n")
                    except Exception:
                        pass

        _threading.Thread(
            target=_state_sampler, daemon=True, name="bs-state"
        ).start()
    stop = False
    coord = None
    announced = False
    last_chaos_write = 0.0
    chaos_log_seen = 0

    def write_chaos_event(out) -> None:
        """Snapshot of self-healing/fault state, rewritten every few
        seconds so a SIGKILLed segment still leaves its (nearly) final
        fault log behind — the parent keeps the LAST one per segment."""
        try:
            from denormalized_tpu.runtime import faults as fault_mod
            from denormalized_tpu.state.lsm import get_global_state_backend

            chaos: dict = {}
            if coord is not None:
                chaos["commit_retries"] = coord.commit_retries
                chaos["restored_from_fallback"] = bool(
                    coord.restored_from_fallback
                )
            try:
                chaos["replay_truncated"] = int(
                    get_global_state_backend().replay_truncated
                )
            except Exception:
                pass
            try:
                # restart counts must ride THIS snapshot (which survives
                # SIGKILL) — the 'metrics' event only exists for segments
                # that reach EOS, i.e. never the killed ones
                from denormalized_tpu.runtime.tracing import collect_metrics

                chaos["prefetch_restarts"] = sum(
                    m.get("prefetch_restarts", 0)
                    for m in collect_metrics(ctx._last_physical).values()
                )
            except Exception:
                pass
            p = fault_mod.plan()
            if p is not None:
                chaos["fault_log"] = p.event_log()
            if chaos:
                out.write(json.dumps({"event": "chaos", **chaos}) + "\n")
            if pipeline == "bigstate":
                # state accounting snapshot (survives SIGKILL like the
                # chaos event): the parent derives the unbudgeted
                # working set and the budgeted run's resident bound
                # from these
                op = ctx._last_physical
                info = None
                stack = [op]
                while stack:
                    cur = stack.pop()
                    if type(cur).__name__ == "SessionWindowExec":
                        info = cur.state_info()
                        break
                    stack.extend(cur.children)
                if info is not None:
                    out.write(json.dumps({
                        "event": "state",
                        "bytes": info.get("state_bytes"),
                        "evictable": info.get("evictable_bytes"),
                        "live_keys": info.get("live_keys"),
                        "spilled_bytes": info.get("spilled_bytes", 0),
                        "spilled_keys": info.get("spilled_keys", 0),
                        "spill": info.get("spill"),
                    }) + "\n")
        except Exception:
            pass

    with open(out_path, "a", buffering=1) as out:
        out.write(json.dumps({"event": "ready", "t": time.time()}) + "\n")
        for batch in it:
            # snapshot chaos state on a 5s cadence AND immediately when
            # the fault log grew — an injection in the last pre-SIGKILL
            # seconds must not vanish from the segment's record (the
            # acceptance gate counts required rules from these events)
            mono = time.monotonic()
            try:
                from denormalized_tpu.runtime import faults as _fm

                _p = _fm.plan()
                log_len = len(_p.events) if _p is not None else 0
            except Exception:
                log_len = 0
            if mono - last_chaos_write > 5.0 or log_len > chaos_log_seen:
                last_chaos_write = mono
                chaos_log_seen = log_len
                write_chaos_event(out)
            if not announced:
                # exactly-once output protocol: announce the recovery
                # point (frozen at coordinator construction) BEFORE any
                # window line.  The parent clips the PREVIOUS segment's
                # lines tagged beyond this epoch — they are the
                # uncommitted suffix this incarnation's replay
                # regenerates (a transactional sink's
                # truncate-on-restore, done reader-side).
                coord = getattr(ctx, "_last_coord", None)
                out.write(json.dumps({
                    "event": "restored",
                    "epoch": (
                        (coord.restored_epoch or 0)
                        if coord is not None else None
                    ),
                }) + "\n")
                announced = True
            if not batch.schema.has(WINDOW_START_COLUMN):
                continue
            now = time.time()
            ws = batch.column(WINDOW_START_COLUMN)
            names = batch.column(
                "sensor_id" if pipeline == "bigstate" else "sensor_name"
            )
            for i in range(batch.num_rows):
                if pipeline == "udaf":
                    rec = {
                        "t": round(now, 3),
                        "ws": int(ws[i]),
                        "key": str(names[i]),
                        "count": int(batch.column("count")[i]),
                        "spread": round(float(batch.column("spread")[i]), 4),
                    }
                elif pipeline in ("session", "bigstate"):
                    rec = {
                        "t": round(now, 3),
                        "ws": int(ws[i]),
                        "key": (
                            int(names[i]) if pipeline == "bigstate"
                            else str(names[i])
                        ),
                        "we": int(batch.column(WINDOW_END_COLUMN)[i]),
                        "count": int(batch.column("count")[i]),
                        "min": round(float(batch.column("min")[i]), 4),
                        "max": round(float(batch.column("max")[i]), 4),
                        "avg": round(float(batch.column("average")[i]), 4),
                    }
                elif pipeline == "join":
                    rec = {
                        "t": round(now, 3),
                        "ws": int(ws[i]),
                        "key": str(names[i]),
                        "count": int(batch.column("count")[i]),
                        "avg_t": round(float(batch.column("avg_t")[i]), 4),
                        "avg_h": round(float(batch.column("avg_h")[i]), 4),
                    }
                elif pipeline == "approx":
                    # the estimate is an INT — no rounding tolerance;
                    # the golden recomputes it with the same kernels
                    rec = {
                        "t": round(now, 3),
                        "ws": int(ws[i]),
                        "key": str(names[i]),
                        "count": int(batch.column("count")[i]),
                        "distinct": int(batch.column("distinct")[i]),
                    }
                else:
                    rec = {
                        "t": round(now, 3),
                        "ws": int(ws[i]),
                        "key": str(names[i]),
                        "count": int(batch.column("count")[i]),
                        "min": round(float(batch.column("min")[i]), 4),
                        "max": round(float(batch.column("max")[i]), 4),
                        "avg": round(float(batch.column("average")[i]), 4),
                    }
                if coord is not None:
                    # in-flight epoch tag: this line is committed once
                    # epoch `ep` commits — emissions between barrier N
                    # and N+1 belong to (uncommitted) epoch N+1, and
                    # stream order guarantees the commit never precedes
                    # the write
                    rec["ep"] = (coord.committed_epoch or 0) + 1
                out.write(json.dumps(rec) + "\n")
                if last_close_ws is not None and rec["ws"] >= last_close_ws:
                    stop = True  # unbounded source: close at the target
            if stop:
                it.close()
                break
        try:
            from denormalized_tpu.runtime.tracing import collect_metrics

            sums: dict = {}
            for m in collect_metrics(ctx._last_physical).values():
                for k, v in m.items():
                    if isinstance(v, (int, float)):
                        sums[k] = sums.get(k, 0) + v
            out.write(json.dumps({
                "event": "metrics",
                **{k: sums[k] for k in (
                    "late_rows", "rows_out", "rows_in", "batches_out",
                    "prefetch_restarts", "prefetch_restarted_partitions",
                    "salvaged_rows", "hot_keys", "adaptations",
                ) if k in sums},
            }) + "\n")
        except Exception:
            pass
        write_chaos_event(out)
        out.write(json.dumps({"event": "done", "t": time.time()}) + "\n")


# -- parent --------------------------------------------------------------


def read_emissions(paths):
    """ALL COMMITTED emitted window rows across segment files →
    ({(ws,key): [tuple, ...]}, duplicate_emissions, done_seen,
    child_metrics, uncommitted_clipped) — every committed occurrence is
    kept, so a wrong first emission can't hide behind a correct
    re-emission after restore.  ``child_metrics`` is one dict per
    'metrics' event found (only children that reached EOS write one —
    SIGKILLed segments leave none).  A torn tail line (SIGKILL
    mid-write) is skipped.

    Exactly-once output: each line carries ``ep``, the in-flight epoch
    at write time, and each restored child announces the epoch it
    recovered from.  A killed segment's lines tagged BEYOND the epoch
    the successor restored from are the uncommitted suffix that
    successor's replay regenerates — the recovery reader discards them
    (the transactional sink's truncate-on-restore, applied where the
    union is read).  The clip boundary for segment i is the restore
    epoch of the next segment that emitted windows: an intermediate
    windowless segment may have advanced commits without re-emitting
    anything, and clipping by ITS restore point would drop lines nobody
    regenerates.  Lines without ``ep`` (no checkpointing) are always
    kept — at-least-once counting, as before."""
    done = False
    metrics: list = []
    segments: list = []  # (seg_idx, restored_epoch|None, [line dicts])
    for seg_idx, path in enumerate(paths, 1):
        restored = None
        lines: list = []
        try:
            f = open(path)
        except FileNotFoundError:
            segments.append((seg_idx, restored, lines))
            continue
        with f:
            for line in f:
                try:
                    o = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if o.get("event") == "done":
                    done = True
                elif o.get("event") == "restored":
                    restored = o.get("epoch")
                elif o.get("event") == "metrics":
                    metrics.append({k: v for k, v in o.items()
                                    if k != "event"})
                elif "ws" in o:
                    lines.append(o)
        segments.append((seg_idx, restored, lines))

    clipped = 0
    kept: list = []  # (seg_idx, line)
    for i, (seg_idx, _restored, lines) in enumerate(segments):
        boundary = None  # None = final (or no emitting successor): keep all
        for j in range(i + 1, len(segments)):
            if segments[j][2]:  # next segment that emitted windows
                boundary = segments[j][1]
                break
        for o in lines:
            ep = o.get("ep")
            if (
                boundary is not None
                and ep is not None
                and ep > (boundary or 0)
            ):
                clipped += 1
                continue
            kept.append((seg_idx, o))

    wins: dict = {}
    dupes = 0
    for seg_idx, o in kept:
        if "q" in o:  # query-dense record: per-query key, full precision
            k = (o["ws"], o["key"], o["q"])
            occ = wins.setdefault(k, [])
            if occ:
                dupes += 1
            occ.append((
                (o["count"], o["sum"], o["min"], o["max"], o["avg"]),
                seg_idx,
            ))
            continue
        k = (o["ws"], o["key"])
        occ = wins.setdefault(k, [])
        if occ:
            dupes += 1
        if "avg_t" in o:  # join pipeline record
            vals = (o["count"], o["avg_t"], o["avg_h"])
        elif "we" in o:  # session record: bounds + aggregates
            vals = (o["count"], o["min"], o["max"],
                    o["avg"], o["ws"], o["we"])
        elif "spread" in o:  # udaf record
            vals = (o["count"], o["spread"])
        elif "distinct" in o:  # approx record: exact integer estimate
            vals = (o["count"], o["distinct"])
        else:
            vals = (o["count"], o["min"], o["max"], o["avg"])
        # segment attribution rides along for diagnosis but stays OUT
        # of the compared tuple
        occ.append((vals, seg_idx))
    return wins, dupes, done, metrics, clipped


def qd_verify(args, env, work, wins, seg_paths, total_batches, *,
              sched_fn=qd_schedule,
              oracle_pipeline="query_dense_oracle") -> dict:
    """Dense-pipeline acceptance (query_dense and join_dense): spawn
    the oracle child (independent uninterrupted runs over the same
    feed), then hold every live query's committed emissions to
    BYTE-identity with its oracle from its first exact window — late
    joiners' backfilled windows included, departed queries' prefixes
    included, duplicate committed occurrences each checked.  Also
    counts pipeline builds per segment (live joins/leaves must never
    rebuild the shared pipeline)."""
    oracle_path = os.path.join(work, "qd_oracle.jsonl")
    oenv = dict(env)
    oenv["SOAK_PIPELINE"] = oracle_pipeline
    oenv["SOAK_OUT"] = oracle_path
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=oenv, stdout=sys.stderr, stderr=sys.stderr,
    )
    oracle: dict = {}  # qid -> {(key, ws): vals}
    if rc == 0:
        with open(oracle_path) as f:
            for line in f:
                try:
                    o = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "ws" not in o:
                    continue
                oracle.setdefault(o["q"], {})[(o["key"], o["ws"])] = (
                    o["count"], o["sum"], o["min"], o["max"], o["avg"]
                )

    builds_per_seg = []
    for p in seg_paths:
        n = 0
        try:
            with open(p) as f:
                for line in f:
                    if '"event": "build"' in line:
                        n += 1
        except FileNotFoundError:
            pass
        builds_per_seg.append(n)

    per_q: dict = {}  # qid -> {(key, ws): [vals, ...]}
    for (ws, key, q), occs in wins.items():
        per_q.setdefault(q, {}).setdefault((key, ws), []).extend(
            v for v, _seg in occs
        )

    sched = sched_fn(total_batches, args.batch_rows, args.pace)
    specs = {s["qid"]: s for s in sched}
    failures: list = []
    silent: list = []
    backfilled = 0
    backfill_missing: list = []
    for q, spec in specs.items():
        got = per_q.get(q)
        if not got:
            silent.append(q)
            continue
        want_all = oracle.get(q, {})
        min_ws = min(ws for (_k, ws) in got)
        max_ws = max(ws for (_k, ws) in got)
        leave = spec.get("leave")
        if leave is None:
            # survivor: exact through the EOS flush — every oracle
            # window from the first emitted one onward, byte-identical
            want = {kw: v for kw, v in want_all.items() if kw[1] >= min_ws}
        else:
            want = {
                kw: v for kw, v in want_all.items()
                if min_ws <= kw[1] <= max_ws
            }
            if max_ws > leave + spec["L"]:
                failures.append(
                    (q, "emitted past its leave", max_ws, leave)
                )
        incoherent = [
            kw for kw, vs in got.items() if any(v != vs[0] for v in vs[1:])
        ]
        if incoherent:
            failures.append(
                (q, "inconsistent duplicate emissions", incoherent[:2], None)
            )
        flat = {kw: vs[0] for kw, vs in got.items()}
        if flat != want:
            failures.append((q, "diverged from oracle", {
                "missing": sorted(set(want) - set(flat))[:2],
                "extra": sorted(set(flat) - set(want))[:2],
                "value_diff": [
                    kw for kw in set(flat) & set(want)
                    if flat[kw] != want[kw]
                ][:2],
            }, None))
        join = spec.get("join")
        if join is not None:
            if min_ws < join:
                backfilled += 1
            elif qd_class_continuous(specs, q):
                backfill_missing.append(q)
    return {
        "oracle_rc": rc,
        "oracle_windows": sum(len(v) for v in oracle.values()),
        "queries": len(specs),
        "joined_live": sum(1 for s in sched if "join" in s),
        "departed": sum(1 for s in sched if "leave" in s),
        "pipeline_builds_per_segment": builds_per_seg,
        "max_builds_per_segment": max(builds_per_seg, default=0),
        "queries_silent": silent,
        "backfilled_joiners": backfilled,
        "backfill_missing": backfill_missing,
        "failures": len(failures),
        "failure_sample": failures[:3],
    }


def _obs_readers():
    """Load the obs read-side helpers WITHOUT importing the engine
    package (the soak parent never imports jax; the module is stdlib-only
    by contract — see denormalized_tpu/obs/readers.py)."""
    import importlib.util

    path = REPO / "denormalized_tpu" / "obs" / "readers.py"
    spec = importlib.util.spec_from_file_location("_soak_obs_readers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def derive_telemetry(obs_paths, anchor_epoch_ms=None) -> dict:
    """The report's time-series section, derived entirely from the
    segments' JSONL telemetry streams: p50/p95/p99 end-to-end latency
    and max watermark lag (histograms merged across killed segments),
    plus the fault-event timeline (per-site injection deltas).

    The engine's lag metrics are event-time-relative (wall − event
    time), so a paced feed replaying from T0 carries a constant offset
    ``anchor_epoch_ms − T0``; when the feed anchor is known (kafka
    pipeline) the report also emits anchored values = true end-to-end
    latency."""
    R = _obs_readers()

    def final_hists(snaps, prefix):
        # matched by PREFIX, not one hardcoded op label: the session and
        # udaf pipelines emit their lag series under op="session"/"udaf"
        last: dict = {}
        for snap in reversed(snaps):
            m = snap.get("metrics", {})
            if any(k.startswith(prefix) for k in m):
                last = m
                break
        return [
            v for k, v in last.items()
            if k.startswith(prefix) and isinstance(v, dict)
        ]

    finals_emit, finals_wm = [], []
    timeline: list = []
    adapt_timeline: list = []
    adapt_by_seg: list = []
    n_snaps = 0
    segs_reporting = 0
    peak_state = 0.0
    peak_spilled = 0.0
    salvaged = 0.0
    state_hot: list = []
    for seg_i, path in enumerate(obs_paths):
        snaps = R.read_stream(path)
        if not snaps:
            continue
        segs_reporting += 1
        n_snaps += len(snaps)
        finals_emit += final_hists(snaps, "dnz_emit_event_lag_ms")
        finals_wm += final_hists(snaps, "dnz_watermark_lag_hist_ms")
        # timeline per SEGMENT: each killed child restarts its counters
        # from zero, so the delta baseline must reset with it
        timeline += R.counter_timeline(snaps, "dnz_fault_injections_total")
        # closed-loop adaptation events (dnz_join_adaptations_total,
        # labeled action=adapt|fold + side): same per-segment delta
        # derivation — a kill while the counter is ahead of its folds
        # landed MID-ADAPTATION (hot sub-partitions live at the cut)
        seg_adapt = R.counter_timeline(
            snaps, "dnz_join_adaptations_total"
        )
        adapt_timeline += seg_adapt
        final_counts: dict = {}
        for snap in snaps:
            vals = {
                k: v for k, v in snap.get("metrics", {}).items()
                if k.startswith("dnz_join_adaptations_total")
                and isinstance(v, (int, float))
            }
            if vals:
                final_counts = vals
        if final_counts:
            adapts = sum(
                v for k, v in final_counts.items()
                if 'action="adapt"' in k
            )
            folds = sum(
                v for k, v in final_counts.items()
                if 'action="fold"' in k
            )
            adapt_by_seg.append({
                "segment": seg_i + 1,
                "adapt": round(adapts),
                "fold": round(folds),
                "hot_blocks_live_at_end": round(adapts - folds) > 0,
            })
        # state observatory: peak total state bytes across the segment's
        # snapshots, and the segment's final top-K hot keys (the
        # dnz_state_hot_key_share gauges a stateful operator refreshes)
        seg_peak = 0.0
        for snap in snaps:
            tot = sum(
                v for k, v in snap.get("metrics", {}).items()
                if k.startswith("dnz_state_bytes")
                and isinstance(v, (int, float))
            )
            if tot > seg_peak:
                seg_peak = tot
        if seg_peak > peak_state:
            peak_state = seg_peak
        # cold-tier + salvage gauges: the segment's FINAL values (both
        # are monotone within a segment's life for salvage; spilled
        # bytes peak tracked like state bytes)
        seg_salvaged = 0.0
        for snap in snaps:
            m = snap.get("metrics", {})
            sp = sum(
                v for k, v in m.items()
                if k.startswith("dnz_state_spilled_bytes")
                and isinstance(v, (int, float))
            )
            if sp > peak_spilled:
                peak_spilled = sp
            sv = sum(
                v for k, v in m.items()
                if k.startswith("dnz_source_salvaged_rows")
                and isinstance(v, (int, float))
            )
            if sv > seg_salvaged:
                seg_salvaged = sv
        salvaged += seg_salvaged
        final_shares = {}
        for snap in snaps:  # last snapshot carrying hot-key series wins
            shares = {
                k: v for k, v in snap.get("metrics", {}).items()
                if k.startswith("dnz_state_hot_key_share") and v
            }
            if shares:
                final_shares = shares
        if final_shares:
            top = sorted(
                final_shares.items(), key=lambda kv: -kv[1]
            )[:8]
            state_hot.append({
                "segment": seg_i,
                "peak_state_bytes": round(seg_peak),
                "top_keys": [
                    {"series": k, "share": round(v, 4)} for k, v in top
                ],
            })
    timeline.sort(key=lambda e: e["t"] or 0)
    adapt_timeline.sort(key=lambda e: e["t"] or 0)
    emit = R.merge_histogram(finals_emit)
    wm = R.merge_histogram(finals_wm)
    tele: dict = {
        "segments_reporting": segs_reporting,
        "snapshots": n_snaps,
        "fault_timeline": timeline,
    }
    if adapt_timeline or adapt_by_seg:
        tele["adaptations"] = {
            "events": adapt_timeline,
            "by_segment": adapt_by_seg,
            "total": sum(s["adapt"] + s["fold"] for s in adapt_by_seg),
        }
    if peak_state:
        tele["peak_state_bytes"] = round(peak_state)
    if peak_spilled:
        tele["peak_spilled_bytes"] = round(peak_spilled)
    # poison records skipped by salvage decode, summed across segments —
    # silent data loss surfaced into the soak report (0 on clean feeds)
    tele["salvaged_rows"] = round(salvaged)
    if state_hot:
        tele["state_hot_keys"] = state_hot
    if emit:
        tele["e2e_event_lag_ms"] = {
            k: round(emit[k], 2) for k in ("p50", "p95", "p99", "max")
            if emit.get(k) is not None
        }
        tele["e2e_event_lag_ms"]["samples"] = emit["count"]
    if wm:
        tele["max_watermark_lag_ms"] = round(wm["max"], 2)
    if anchor_epoch_ms is not None:
        off = anchor_epoch_ms - T0
        tele["feed_anchor_offset_ms"] = round(off, 1)
        if emit:
            tele["e2e_latency_ms"] = {
                k: round(emit[k] - off, 2)
                for k in ("p50", "p95", "p99", "max")
                if emit.get(k) is not None
            }
        if wm:
            tele["max_watermark_lag_anchored_ms"] = round(wm["max"] - off, 2)
    return tele


def read_state_events(paths) -> list[dict]:
    """Every 'state' accounting event across the given files (bigstate
    soak: emission segments + their .state sampler streams)."""
    out = []
    for path in paths:
        try:
            f = open(path)
        except FileNotFoundError:
            continue
        with f:
            for line in f:
                try:
                    o = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if o.get("event") == "state":
                    o["_path"] = str(path)
                    out.append(o)
    return out


def bigstate_main(args) -> None:
    """Larger-than-memory acceptance drive (ROADMAP item 3): one
    unbudgeted reference run over a deterministic feed of
    ``--keys`` simultaneously-open sessions, then the SAME feed under a
    state budget ~5x smaller with the cold tier + checkpointing active,
    SIGKILLed mid-run and restored.  Gates: byte-identical emissions
    across the two runs (and across the kill), resident state bounded by
    the budget, a materially lower RSS ceiling, the spill machinery
    demonstrably exercised, and the armed spill-site fault rules all
    fired + healed."""
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix="soak_bs_")
    a_batches = -(-args.keys // args.batch_rows)
    waves = -(-args.keys // args.wave_keys)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SOAK_BATCH_ROWS": str(args.batch_rows),
        "SOAK_PACE": str(args.pace),
        "SOAK_TOTAL_BATCHES": str(a_batches + waves),
        "SOAK_PIPELINE": "bigstate",
        "SOAK_BS_KEYS": str(args.keys),
        "SOAK_BS_WAVE": str(args.wave_keys),
        "SOAK_T0": str(T0),
        "SOAK_CKPT_S": str(args.ckpt_s),
    })
    report: dict = {
        "pipeline": "bigstate",
        "keys": args.keys,
        "wave_keys": args.wave_keys,
        "batch_rows": args.batch_rows,
        "kill_every_s": args.kill_every,
        "phaseA_batches": a_batches,
        "close_waves": waves,
    }

    def run_child(out_path, obs_path, ckpt_dir, budget, kill_every,
                  max_kills):
        seg_env = dict(env)
        seg_env["SOAK_BS_BUDGET"] = str(budget)
        seg_env["SOAK_CKPT_DIR"] = ckpt_dir
        if budget and args.chaos_spill:
            seg_env["DENORMALIZED_FAULT_PLAN"] = json.dumps(
                bigstate_fault_plan(args.chaos_seed)
            )
        segs, rss, kills, crashes = [], [], 0, 0
        done = False
        seg = 0
        while not done:
            seg += 1
            seg_out = f"{out_path}.{seg}"
            segs.append(seg_out)
            seg_env["SOAK_OUT"] = seg_out
            seg_env["SOAK_OBS_OUT"] = f"{obs_path}.{seg}"
            t_spawn = time.monotonic()
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child"],
                env=seg_env, stdout=sys.stderr, stderr=sys.stderr,
            )
            kill_at = t_spawn + kill_every
            while True:
                rc = proc.poll()
                if rc is not None:
                    if rc == 0:
                        done = True
                    else:
                        crashes += 1
                        if crashes > 5:
                            raise RuntimeError(
                                f"bigstate child crashed {crashes}x "
                                f"(rc={rc})"
                            )
                    break
                if (r := rss_kb(proc.pid)):
                    rss.append(r)
                if (
                    kills < max_kills
                    and time.monotonic() >= kill_at
                ):
                    os.kill(proc.pid, signal.SIGKILL)
                    kills += 1
                    proc.wait(10)
                    break
                time.sleep(0.5)
        return segs, rss, kills, crashes

    try:
        # -- run 1: the unbudgeted oracle --------------------------------
        ckpt_ref = os.path.join(work, "ckpt_ref")
        os.makedirs(ckpt_ref)
        t0 = time.monotonic()
        ref_segs, ref_rss, _, _ = run_child(
            os.path.join(work, "ref.jsonl"),
            os.path.join(work, "ref_obs.jsonl"),
            ckpt_ref, budget=0, kill_every=float("inf"), max_kills=0,
        )
        ref_wall = time.monotonic() - t0
        wins_ref, ref_dupes, ref_done, _m, _c = read_emissions(ref_segs)
        ref_states = read_state_events(
            ref_segs + [p + ".state" for p in ref_segs]
        )
        working_set = max(
            (s.get("bytes") or 0 for s in ref_states), default=0
        )
        budget = args.state_budget or max(working_set // 5, 1_000_000)
        report.update({
            "reference": {
                "wall_s": round(ref_wall, 1),
                "sessions": len(wins_ref),
                "duplicate_emissions": ref_dupes,
                "rss_kb_max": max(ref_rss) if ref_rss else None,
                "working_set_bytes": working_set,
            },
            "budget_bytes": budget,
            "budget_ratio": (
                round(working_set / budget, 2) if budget else None
            ),
        })
        # -- run 2: budgeted + kills -------------------------------------
        ckpt_b = os.path.join(work, "ckpt_b")
        os.makedirs(ckpt_b)
        t0 = time.monotonic()
        b_segs, b_rss, kills, crashes = run_child(
            os.path.join(work, "bud.jsonl"),
            os.path.join(work, "bud_obs.jsonl"),
            ckpt_b, budget=budget, kill_every=args.kill_every,
            max_kills=args.max_kills,
        )
        b_wall = time.monotonic() - t0
        wins_b, dupes, done_seen, _m2, clipped = read_emissions(b_segs)
        b_states = read_state_events(
            b_segs + [p + ".state" for p in b_segs]
        )
        resident_max = max(
            (s.get("bytes") or 0 for s in b_states), default=0
        )
        evictable_max = max(
            (s.get("evictable") or 0 for s in b_states), default=0
        )
        # counters reset with each respawned incarnation: sum each
        # segment's LAST spill snapshot for the run totals
        last_per_seg: dict = {}
        for s in b_states:
            if s.get("spill"):
                last_per_seg[s["_path"]] = s["spill"]
        spill_final: dict = {}
        for sp in last_per_seg.values():
            for k, v in sp.items():
                if isinstance(v, (int, float)):
                    spill_final[k] = spill_final.get(k, 0) + v
        chaos_events = read_chaos_events(b_segs)
        fired: dict = {}
        for ev in chaos_events:
            for e in ev.get("fault_log", []):
                name = e.get("name", f"rule{e.get('rule')}")
                fired[name] = fired.get(name, 0) + 1
        # -- drift: EVERY budgeted occurrence must equal the oracle's ----
        lost, spurious, mismatched = [], [], 0
        for k, occs in wins_ref.items():
            want = occs[0][0]
            got = wins_b.get(k)
            if not got:
                lost.append(k)
                continue
            for vals, _seg in got:
                if vals != want:
                    mismatched += 1
        for k in wins_b:
            if k not in wins_ref:
                spurious.append(k)
        expected_sessions = args.keys + waves * 64
        spill_blocks = (
            (spill_final or {}).get("spill_blocks_total", 0)
        )
        required_fired = (
            sorted(r for r in BIGSTATE_REQUIRED_RULES if r in fired)
            if args.chaos_spill else []
        )
        rss_ratio = (
            round(max(b_rss) / max(ref_rss), 3)
            if b_rss and ref_rss else None
        )
        # the RSS gate is relative to the WORKING SET, not a bare
        # ratio: both runs keep the interner key index resident (the
        # documented membership-filter floor, ~2.8GB at 10M int keys),
        # so the budgeted run must shed at least 35% of the evictable
        # working set from RAM — a gate that scales with the workload
        # instead of hardcoding the index share
        rss_saved_bytes = (
            (max(ref_rss) - max(b_rss)) * 1024
            if b_rss and ref_rss else None
        )
        rss_flat_ok = (
            rss_saved_bytes is not None
            and rss_saved_bytes >= 0.35 * working_set
            and (rss_ratio is None or rss_ratio <= 0.9)
        )
        report.update({
            "budgeted": {
                "wall_s": round(b_wall, 1),
                "segments": len(b_segs),
                "kills": kills,
                "crash_restarts": crashes,
                "sessions": len(wins_b),
                "duplicate_emissions": dupes,
                "uncommitted_clipped": clipped,
                "rss_kb_max": max(b_rss) if b_rss else None,
                "resident_state_bytes_max": resident_max,
                "evictable_state_bytes_max": evictable_max,
                "spill": spill_final,
            },
            "chaos_spill": {
                "armed": bool(args.chaos_spill),
                "fired_rules": fired,
                "required_rules_fired": required_fired,
            },
            "sessions_expected": expected_sessions,
            "sessions_lost": len(lost),
            "sessions_spurious": len(spurious),
            "sessions_mismatched": mismatched,
            "rss_budgeted_over_reference": rss_ratio,
            "rss_saved_mb": (
                round(rss_saved_bytes / 2**20) if rss_saved_bytes else None
            ),
            "ok": (
                ref_done and done_seen
                and len(wins_ref) == expected_sessions
                and not lost and not spurious and not mismatched
                and kills >= 1
                and spill_blocks > 0
                # EVICTABLE resident state stays bounded by the budget
                # (25% slack covers estimate-vs-exact gap + the
                # protected current batch); the interned-key index is
                # the documented un-evictable resident floor, reported
                # via resident_state_bytes_max (docs/state_spill.md)
                and evictable_max <= budget * 1.25
                and rss_flat_ok
                and (
                    not args.chaos_spill
                    or len(required_fired) == len(BIGSTATE_REQUIRED_RULES)
                )
            ),
        })
        Path(args.out).write_text(json.dumps(report, indent=1))
        print(json.dumps({
            "ok": report["ok"],
            "sessions": len(wins_b),
            "kills": kills,
            "spill_blocks": spill_blocks,
            "rss_ratio": rss_ratio,
            "budget_ratio": report.get("budget_ratio"),
        }))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def rss_kb(pid: int) -> int | None:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


def _cluster_cell(args, partial: bool) -> dict:
    """Run one cluster soak cell and return its report dict.

    Both cells stream the same paced job over N worker processes with a
    SIGKILLed worker mid-stream plus one injected torn exchange frame,
    and hold the surviving output to EXACTLY-ONCE vs the uninterrupted
    single-process oracle (0 lost / 0 spurious / 0 duplicates).  They
    differ in the recovery contract under test:

    - ``full_restart`` (partial=False): fail-stop fallback — any death
      or tear restarts the WHOLE cluster from the last committed epoch
      (gate: restarts >= 2, one per injected failure).
    - ``partial`` (partial=True): single-worker recovery — the tear is
      keyed to the killed worker's outbound edges, so both failures are
      attributed to that one worker and only IT respawns; survivors
      must never restart (``max_restarts=0`` turns any full restart
      into a hard error), only the dead worker's slot may grow partial
      segments, and the coordinator's recovery-duration histogram
      (``dnz_cluster_recovery_ms``) lands in the report."""
    import shutil
    import tempfile
    from collections import Counter

    from denormalized_tpu import obs
    from denormalized_tpu.cluster import ClusterSpec, run_cluster
    from denormalized_tpu.cluster import benchjob
    from denormalized_tpu.cluster.reader import read_cluster

    n_workers = args.cluster_workers
    partitions = args.cluster_partitions
    # stream sized from --minutes at a paced, checkpoint-friendly rate
    batches = max(20, int(args.minutes * 60 / 0.05 / 2))
    job_args = {
        "partitions": partitions,
        "batches": batches,
        "rows": min(args.batch_rows, 1024),
        "keys": 97,
        "batch_span_ms": 250,
        "window_ms": 1000,
        "pace_s": 0.05,
    }
    per_worker_wall = (partitions / n_workers) * batches * 0.05
    t_start = time.time()
    mode = "partial" if partial else "full_restart"
    print(f"cluster soak [{mode}]: {n_workers} workers, {partitions} "
          f"partitions, {batches} batches/partition "
          f"(~{per_worker_wall:.0f}s of stream per worker)",
          file=sys.stderr)
    oracle = benchjob.oracle_rows(job_args)
    work = tempfile.mkdtemp(prefix="soak_cluster_")
    victim = n_workers - 1
    # one torn exchange frame mid-stream, detected by the receiver's
    # CRC/length check.  full_restart tears worker 0's edge (both ends
    # fail, coordinator restarts the cluster); partial tears the
    # VICTIM's outbound edge so the failure is attributed to the same
    # worker the SIGKILL targets — two partial recoveries of one
    # worker, peers never stop
    fault_plan = {
        "seed": args.chaos_seed,
        "rules": [{
            "site": "exchange.send", "kind": "torn",
            "key_substr": f"{victim}->" if partial else "0->",
            # partial recovery pins the respawn to the last CLUSTER
            # commit, so the partial cell's tear waits until the first
            # 1s-interval barrier has provably committed
            "after": 150 if partial else 40, "times": 1,
            "name": "torn-exchange-frame",
        }],
    }
    spec = ClusterSpec(
        workdir=work,
        n_workers=n_workers,
        job="denormalized_tpu.cluster.benchjob:soak_job",
        job_args=job_args,
        checkpoint_interval_s=1.0,
        sink="jsonl",
        # partial: ANY full-cluster restart is a hard failure — the
        # survivors-keep-streaming contract is the point of the cell
        max_restarts=0 if partial else 4,
        liveness_timeout_s=300.0,
        metrics_jsonl=True,
        fault_plan=fault_plan,
        partial_recovery=partial,
    )
    kill_at = min(args.kill_every, per_worker_wall * 0.4)
    result = run_cluster(
        spec,
        kill_worker_after_s=kill_at,
        kill_worker_id=victim,
    )
    got = read_cluster(result["segments"])
    rows = [benchjob.canonical_row(r) for r in got["rows"]]
    counts = Counter(rows)
    dupes = sum(c - 1 for c in counts.values() if c > 1)
    want = Counter(oracle)
    lost = sum((want - counts).values())
    spurious = sum((counts - want).values()) - dupes
    # fault evidence: the torn frame fired in generation 0 (its obs
    # stream carries the dnz_fault_injections_total counter) and cost
    # at least one restart/recovery beyond the SIGKILL's
    merged = _obs_readers().merge_final_snapshots(
        sorted(
            os.path.join(work, "obs", f)
            for f in os.listdir(os.path.join(work, "obs"))
        )
    ) if os.path.isdir(os.path.join(work, "obs")) else {"series": {}}
    fault_fired = sum(
        v for k, v in merged["series"].items()
        if k.startswith("dnz_fault_injections_total")
        and "exchange" in k and isinstance(v, (int, float))
    )
    # a tear can kill the worker before the next JSONL export cycle:
    # the coordinator's crash log is the durable secondary evidence
    torn_crashes = sum(
        1 for why in result.get("crashes", [])
        if "torn" in (why or "")
    )
    fault_fired = max(int(fault_fired), torn_crashes)
    report = {
        "mode": mode,
        "workers": n_workers,
        "partitions": partitions,
        "total_rows": partitions * batches * job_args["rows"],
        "oracle_windows": len(oracle),
        "emitted_windows_kept": len(rows),
        "clipped_uncommitted": got["clipped"],
        "lost": lost,
        "spurious": spurious,
        "duplicate_emissions": dupes,
        "sigkills": result.get("killed_workers", 0),
        "exchange_faults_fired": int(fault_fired),
        "restarts": result["restarts"],
        "commits": result["commits"],
        "status": result["status"],
        "wall_s": round(time.time() - t_start, 1),
        "host_cores": os.cpu_count(),
    }
    if partial:
        partials = [s for s in result["segments"] if s.get("partial")]
        # the coordinator runs in THIS process: its recovery-duration
        # histogram is read straight off the live obs registry
        hist = {
            k: v for k, v in obs.registry().snapshot().items()
            if k.startswith("dnz_cluster_recovery_ms")
        }
        report.update({
            "worker_restarts": result["worker_restarts"],
            "aborted_epochs": result["aborted_epochs"],
            "recoveries": result["recoveries"],
            "recovery_ms_histogram": hist,
            "crashes": result.get("crashes", []),
            "partial_segment_workers": sorted(
                {s["worker"] for s in partials}
            ),
        })
        report["pass"] = bool(
            result["status"] == "done"
            and lost == 0 and spurious == 0 and dupes == 0
            and result.get("killed_workers", 0) >= 1
            and fault_fired >= 1
            # survivors never restarted; only the victim replayed
            and result["restarts"] == 0
            and result["worker_restarts"] >= 1
            and partials
            and all(s["worker"] == victim for s in partials)
            and all(s["restored"] >= 1 for s in partials)
            and any(r["worker"] == victim and r["ms"] > 0
                    for r in result["recoveries"])
        )
    else:
        report["pass"] = bool(
            result["status"] == "done"
            and lost == 0 and spurious == 0 and dupes == 0
            and result.get("killed_workers", 0) >= 1
            and fault_fired >= 1
            and result["restarts"] >= 2
        )
    shutil.rmtree(work, ignore_errors=True)
    return report


def cluster_main(args) -> None:
    """Multi-process cluster soak (see ``_cluster_cell``): the
    full-restart fallback cell and the partial-recovery cell, one
    report with both.  ``--partial`` runs only the partial cell (quick
    iteration on the single-worker recovery path).

    Unlike the single-process soaks this parent imports the engine (the
    oracle runs in-process); the workers are real spawned processes."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    modes = [True] if args.partial else [False, True]
    cells = {}
    for partial in modes:
        cell = _cluster_cell(args, partial)
        cells[cell["mode"]] = cell
    report = {
        "pipeline": "cluster",
        "cells": cells,
        "pass": all(c["pass"] for c in cells.values()),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    if not report["pass"]:
        sys.exit(1)


def main():
    global T0
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--minutes", type=float, default=12.0)
    ap.add_argument("--pace", type=float, default=200_000.0)
    ap.add_argument("--batch-rows", type=int, default=4096)
    ap.add_argument("--kill-every", type=float, default=90.0)
    ap.add_argument("--pipeline",
                    choices=("simple", "sliding", "join", "session",
                             "udaf", "approx", "kafka", "bigstate",
                             "cluster", "query_dense", "join_dense"),
                    default="simple")
    ap.add_argument("--cluster-workers", type=int, default=3,
                    help="cluster: engine worker processes")
    ap.add_argument("--cluster-partitions", type=int, default=6,
                    help="cluster: source partitions (static assignment)")
    ap.add_argument("--partial", action="store_true",
                    help="cluster: run ONLY the partial-recovery cell "
                    "(single-worker replay while peers keep streaming); "
                    "default runs the full-restart fallback cell AND "
                    "the partial cell")
    ap.add_argument("--keys", type=int, default=10_000_000,
                    help="bigstate: simultaneously-open sessions")
    ap.add_argument("--wave-keys", type=int, default=100_000,
                    help="bigstate: sessions closed per watermark wave")
    ap.add_argument("--state-budget", type=int, default=0,
                    help="bigstate: budget bytes (0 = working set / 5)")
    ap.add_argument("--ckpt-s", type=float, default=20.0,
                    help="bigstate: checkpoint interval")
    ap.add_argument("--max-kills", type=int, default=2,
                    help="bigstate: SIGKILLs issued mid-run")
    ap.add_argument("--chaos-spill", action="store_true", default=True,
                    help="bigstate: arm the spill-site fault plan "
                    "(transient reload flap, eviction-write failure, "
                    "torn manifest; default on)")
    ap.add_argument("--no-chaos-spill", dest="chaos_spill",
                    action="store_false")
    ap.add_argument("--chaos", action="store_true",
                    help="arm the seeded FaultPlan (broker flaps, worker "
                    "crashes, torn state writes, commit hiccups) on top "
                    "of the kafka exactly-once soak; implies "
                    "--pipeline kafka")
    ap.add_argument("--chaos-seed", type=int, default=1234)
    ap.add_argument("--out", default=None, help="default derives from "
                    "--pipeline: SOAK.json / SOAK_SLIDING.json / "
                    "SOAK_JOIN.json / SOAK_SESSION.json / SOAK_UDAF.json "
                    "/ SOAK_APPROX.json / SOAK_CHAOS.json (never "
                    "cross-clobbers artifacts)")
    args = ap.parse_args()
    if args.chaos:
        if args.pipeline not in ("simple", "kafka"):
            ap.error("--chaos runs on the kafka pipeline only")
        args.pipeline = "kafka"
    if args.out is None:
        args.out = str(REPO / (
            "SOAK_CHAOS.json" if args.chaos else {
                "simple": "SOAK.json",
                "join": "SOAK_JOIN.json",
                "session": "SOAK_SESSION.json",
                "udaf": "SOAK_UDAF.json",
                "approx": "SOAK_APPROX.json",
                "sliding": "SOAK_SLIDING.json",
                "kafka": "SOAK_KAFKA.json",
                "bigstate": "SOAK_BIGSTATE.json",
                "cluster": "SOAK_CLUSTER.json",
                "query_dense": "SOAK_QUERY_DENSE.json",
                "join_dense": "SOAK_JOIN_DENSE.json",
            }[args.pipeline]
        ))
    if args.child:
        child_main()
        return
    if args.pipeline == "bigstate":
        bigstate_main(args)
        return
    if args.pipeline == "cluster":
        cluster_main(args)
        return

    import shutil
    import tempfile

    total_batches = int(args.minutes * 60 * args.pace / args.batch_rows)
    work = tempfile.mkdtemp(prefix="soak_")
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(ckpt_dir)
    if "SOAK_T0" not in os.environ:
        # anchor event time near wall-now, rounded to a window boundary
        # (see the T0 comment above; the kafka feed re-anchors once its
        # staging estimate is known)
        T0 = int(time.time()) * 1000 // WINDOW_MS * WINDOW_MS
    kafka_broker = None
    kafka_last_close_ws = None
    kafka_feed_anchor: dict = {}
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SOAK_BATCH_ROWS": str(args.batch_rows),
        "SOAK_PACE": str(args.pace),
        "SOAK_TOTAL_BATCHES": str(total_batches),
        "SOAK_CKPT_DIR": ckpt_dir,
        "SOAK_PIPELINE": args.pipeline,
    })
    chaos_spec = None
    chaos_deterministic = None
    if args.chaos:
        chaos_spec = chaos_plan(args.chaos_seed)
        # determinism proof: the same seed must reproduce the same
        # injection sequence — two fresh plans driven through the same
        # synthetic call sequence must log identical decisions
        seq_a = chaos_sim_sequence(chaos_spec)
        seq_b = chaos_sim_sequence(chaos_spec)
        chaos_deterministic = bool(seq_a and seq_a == seq_b)
        chaos_sim_count = len(seq_a)
        env["DENORMALIZED_FAULT_PLAN"] = json.dumps(chaos_spec)
        # pure-Python LSM engine: its replay accounting (replay_truncated)
        # is part of what the chaos run asserts on
        env["DENORMALIZED_LSM_PY"] = "1"
    if args.pipeline == "kafka":
        kafka_broker, _feed_th, kafka_last_close_ws, kafka_feed_anchor = (
            kafka_prep_and_feed(
                args, total_batches, lambda m: print(m, file=sys.stderr)
            )
        )
        env["SOAK_BOOTSTRAP"] = kafka_broker.bootstrap
        env["SOAK_LAST_CLOSE_WS"] = str(kafka_last_close_ws)
    # AFTER the kafka branch: the feed's staging calibration re-anchors
    # T0, and every child must see the final value (window keys are
    # absolute — parent golden and child emissions must agree)
    env["SOAK_T0"] = str(T0)

    report = {
        "pipeline": args.pipeline,
        "minutes": args.minutes,
        "pace_rows_per_s": args.pace,
        "total_rows": total_batches * args.batch_rows,
        "kill_every_s": args.kill_every,
        "segments": [],
    }
    if args.chaos:
        report["chaos"] = {
            "seed": args.chaos_seed,
            "plan": chaos_spec,
            "fault_plan_deterministic": chaos_deterministic,
            "sim_injections": chaos_sim_count,
        }

    def write(extra=None):
        report.update(extra or {})
        Path(args.out).write_text(json.dumps(report, indent=1))

    golden: dict = {}
    _fold = {
        "join": lambda agg, i, br, pc: golden_update_join(
            agg, i, br, pc, total_batches
        ),
        "session": golden_update_session,
        "sliding": golden_update_sliding,
        "approx": golden_update_approx,
        # query_dense/join_dense verify against per-query ORACLE RUNS
        # (qd_verify) after the drive loop, not an incremental golden
        # fold — the loop still advances golden_i to track feed
        # exhaustion
        "query_dense": lambda agg, i, br, pc: None,
        "join_dense": lambda agg, i, br, pc: None,
    }.get(args.pipeline, golden_update)  # udaf golden == tumbling fold
    golden_i = 0
    seg_paths = []
    obs_paths = []
    seg = 0
    kills_issued = 0
    t_start = time.monotonic()
    aborted = None
    recovery_times = []
    done = False
    proc = None
    try:
        while not done:
            seg += 1
            out_path = os.path.join(work, f"emit_{seg}.jsonl")
            seg_paths.append(out_path)
            obs_path = os.path.join(work, f"obs_{seg}.jsonl")
            obs_paths.append(obs_path)
            seg_env = dict(env)
            seg_env["SOAK_OUT"] = out_path
            seg_env["SOAK_OBS_OUT"] = obs_path
            t_spawn = time.monotonic()
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child"],
                env=seg_env, stdout=sys.stderr, stderr=sys.stderr,
            )
            # first-emission latency after spawn = recovery time (seg > 1)
            first_emit = None
            seg_rss = []  # sampled only AFTER first emission: a pre-exec
            # or mid-import sample (~4KB) says nothing about the engine
            kill_at = t_spawn + args.kill_every
            while True:
                rc = proc.poll()
                if rc is not None:
                    if rc != 0:
                        aborted = f"segment {seg} child rc={rc}"
                    done = True
                    break
                now = time.monotonic()
                if first_emit is not None and (r := rss_kb(proc.pid)):
                    seg_rss.append(r)
                if first_emit is None:
                    wins, _, _, _, _ = read_emissions([out_path])
                    if wins:
                        first_emit = now - t_spawn
                        if seg > 1:
                            recovery_times.append(round(first_emit, 2))
                # fold golden forward while the child streams (parent is
                # otherwise idle); stay ahead of the feed
                target_i = min(
                    total_batches,
                    int((now - t_start) * args.pace / args.batch_rows)
                    + 200,
                )
                while golden_i < target_i:
                    _fold(golden, golden_i, args.batch_rows, args.pace)
                    golden_i += 1
                if now >= kill_at:
                    # never kill the final drain: once the feed's event
                    # time is exhausted, let the segment run to EOS
                    if golden_i >= total_batches:
                        kill_at = float("inf")
                        time.sleep(0.5)
                        continue
                    os.kill(proc.pid, signal.SIGKILL)
                    kills_issued += 1
                    proc.wait(10)
                    break
                time.sleep(0.5)
            report["segments"].append({
                "segment": seg,
                "wall_s": round(time.monotonic() - t_spawn, 1),
                "rss_kb_start": seg_rss[0] if seg_rss else None,
                "rss_kb_max": max(seg_rss) if seg_rss else None,
                "rss_kb_end": seg_rss[-1] if seg_rss else None,
                "first_emit_s": (
                    round(first_emit, 2) if first_emit else None
                ),
            })
            write()
            if aborted:
                break
        # finish golden
        while golden_i < total_batches and not aborted:
            _fold(golden, golden_i, args.batch_rows, args.pace)
            golden_i += 1
        wins, dupes, done_seen, child_metrics, clipped = read_emissions(
            seg_paths
        )
        if args.pipeline in ("query_dense", "join_dense"):
            dense_join = args.pipeline == "join_dense"
            qd = (
                None if aborted
                else qd_verify(
                    args, env, work, wins, seg_paths, total_batches,
                    sched_fn=jd_schedule if dense_join else qd_schedule,
                    oracle_pipeline=(
                        "join_dense_oracle" if dense_join
                        else "query_dense_oracle"
                    ),
                )
            )
            try:
                telemetry = derive_telemetry(obs_paths)
            except Exception as e:  # dnzlint: allow(broad-except) telemetry derivation is reporting, not verification
                telemetry = {"error": str(e)}
            # join_dense runs a 10-query plane (the join oracles replay
            # the full feed per query), so its warm-backfill floor scales
            # down with it
            min_backfilled = 3 if dense_join else 10
            ok = bool(
                not aborted and done_seen and kills_issued >= 2
                and qd is not None
                and qd["oracle_rc"] == 0 and qd["oracle_windows"] > 0
                and qd["failures"] == 0 and not qd["queries_silent"]
                and not qd["backfill_missing"]
                and qd["backfilled_joiners"] >= min_backfilled
                and qd["max_builds_per_segment"] == 1
            )
            write({
                "aborted": aborted,
                "telemetry": telemetry,
                "eos_done_seen": done_seen,
                "kills": kills_issued,
                "recovery_first_emit_s": recovery_times,
                "emitted_rows": sum(len(v) for v in wins.values()),
                "duplicate_emissions": dupes,
                "uncommitted_clipped": clipped,
                "child_metrics": child_metrics,
                args.pipeline: qd,
                "ok": ok,
            })
            print(json.dumps({
                "ok": ok,
                "kills": kills_issued,
                "queries": qd and qd["queries"],
                "joined_live": qd and qd["joined_live"],
                "departed": qd and qd["departed"],
                "backfilled": qd and qd["backfilled_joiners"],
                "failures": qd and qd["failures"],
                "aborted": aborted,
            }))
            return
        if args.pipeline == "kafka" and not aborted:
            # the unbounded source ends at last_close_ws by design: windows
            # past it may or may not close (idle-hint timing) before the
            # child exits — clip BOTH sides to the deterministic range
            golden = {
                k: g for k, g in golden.items()
                if k[0] <= kafka_last_close_ws
            }
            wins = {
                k: v for k, v in wins.items()
                if k[0] <= kafka_last_close_ws
            }
        if args.pipeline == "session" and not aborted:
            # golden keys are (burst second, key); emissions key on the
            # session START (min ts in the burst) — remap for comparison
            golden = {
                (int(g[4]), k[1]): g for k, g in golden.items()
            }
        lost = []
        spurious = []
        mismatched = []
        if not aborted:
            for k, g in golden.items():
                occs = wins.get(k)
                if not occs:
                    lost.append(k)
                    continue
                if args.pipeline == "join":
                    cnt, sm = g
                    # exactly-one dim match per fact row: count and avg
                    # come from the fact fold, avg_h is the window's
                    # (constant) deterministic dim value
                    want = (
                        cnt,
                        round(sm / cnt, 4),
                        dim_value(
                            int(k[1].rsplit("_", 1)[1]),
                            k[0] // 1000 - T0 // 1000,
                        ),
                    )
                elif args.pipeline == "session":
                    cnt, mn, mx, sm, t0, t1 = g
                    want = (cnt, round(mn, 4), round(mx, 4),
                            round(sm / cnt, 4), t0, t1 + SESSION_GAP_MS)
                elif args.pipeline == "udaf":
                    cnt, mn, mx, _sm = g
                    want = (cnt, round(mx - mn, 4))
                elif args.pipeline == "approx":
                    # EXACT integer equality: the golden's plane was
                    # folded with the engine's own kernels, and HLL
                    # max-merge is split-invariant — any deviation is
                    # a real sketch restore/fold bug, not "noise"
                    cnt, plane = g
                    want = (cnt, int(_sk().hll_estimate(plane)[0]))
                else:
                    cnt, mn, mx, sm = g
                    want = (cnt, round(mn, 4), round(mx, 4),
                            round(sm / cnt, 4))
                for got, seg_idx in occs:  # EVERY occurrence, dupes too
                    if len(got) != len(want) or any(
                        abs(a - b) > 1e-3 for a, b in zip(got, want)
                    ):
                        mismatched.append((k, got, want,
                                           {"segment": seg_idx}))
            # spurious: emitted keys the golden never produced (corrupted
            # ws/key after a restore would land here)
            spurious = [k for k in wins if k not in golden]
        chaos_report = {}
        if args.chaos:
            chaos_events = read_chaos_events(seg_paths)
            fired_rules: dict = {}
            fired_sites: dict = {}
            for ev in chaos_events:
                for e in ev.get("fault_log", []):
                    name = e.get("name", f"rule{e.get('rule')}")
                    fired_rules[name] = fired_rules.get(name, 0) + 1
                    fired_sites[e["site"]] = fired_sites.get(e["site"], 0) + 1
            chaos_report = {
                "segments_reporting": len(chaos_events),
                "injections_fired": sum(fired_rules.values()),
                "fired_rules": fired_rules,
                "fired_sites": fired_sites,
                "required_rules_fired": sorted(
                    r for r in CHAOS_REQUIRED_RULES if r in fired_rules
                ),
                "commit_retries": sum(
                    ev.get("commit_retries", 0) for ev in chaos_events
                ),
                "fallback_restores": sum(
                    1 for ev in chaos_events
                    if ev.get("restored_from_fallback")
                ),
                "replay_truncated": sum(
                    ev.get("replay_truncated", 0) for ev in chaos_events
                ),
                "prefetch_restarts": sum(
                    ev.get("prefetch_restarts", 0) for ev in chaos_events
                ),
            }
            report["chaos"].update(chaos_report)
        try:
            telemetry = derive_telemetry(
                obs_paths,
                anchor_epoch_ms=(
                    kafka_feed_anchor["epoch"] * 1000.0
                    if kafka_feed_anchor.get("epoch") else None
                ),
            )
        except Exception as e:  # dnzlint: allow(broad-except) telemetry derivation is reporting, not verification — a malformed snapshot stream must not turn a green soak red
            telemetry = {"error": str(e)}
        write({
            "aborted": aborted,
            "telemetry": telemetry,
            "eos_done_seen": done_seen,
            "kills": kills_issued,
            "recovery_first_emit_s": recovery_times,
            "golden_windows": len(golden),
            "emitted_windows": len(wins),
            "duplicate_emissions": dupes,
            "uncommitted_clipped": clipped,
            "child_metrics": child_metrics,
            "windows_lost": len(lost),
            "windows_spurious": len(spurious),
            "windows_mismatched": len(mismatched),
            "mismatch_sample": mismatched[:3],
            "spurious_sample": spurious[:3],
            "ok": (
                not aborted and done_seen and not lost and not spurious
                and not mismatched and len(wins) == len(golden) > 0
                and (
                    not args.chaos
                    or (
                        chaos_deterministic
                        and len(chaos_report.get(
                            "required_rules_fired", []
                        )) == len(CHAOS_REQUIRED_RULES)
                    )
                )
            ),
        })
        print(json.dumps({
            "ok": report.get("ok"),
            "kills": report.get("kills"),
            "windows": len(wins),
            "lost": len(lost),
            "dupes": dupes,
            "aborted": aborted,
            **({"chaos_rules": chaos_report.get("fired_rules"),
                "fallbacks": chaos_report.get("fallback_restores")}
               if args.chaos else {}),
        }))
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        if kafka_broker is not None:
            kafka_broker.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
