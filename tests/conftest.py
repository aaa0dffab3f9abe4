"""Test configuration: force JAX onto a virtual 8-device CPU platform BEFORE
jax initializes, so sharding tests run without TPU hardware and unit tests
are hermetic/fast."""

import os

# FORCE cpu, in the environment (for child processes) and in the config:
# tests are hermetic and must behave the same on a machine that has a
# chip as on one that does not.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import faulthandler
import signal
import sys

import numpy as np
import pytest

# -- lock-order witness (on for the whole tier-1 run) ---------------------
# Install BEFORE any engine module imports: module-level engine locks
# (native/build.py _LOCK, state/lsm.py _BUILD_LOCK, ...) are created at
# import time and must be wrapped too.  The witness records the runtime
# lock-acquisition order of every engine lock and the session FAILS if
# two code paths ever disagreed about it (a deadlock waiting for the
# right interleaving).  Opt out with DENORMALIZED_LOCK_WITNESS=0; see
# denormalized_tpu/common/lockwitness.py and docs/static_analysis.md.
_LOCK_WITNESS = os.environ.get("DENORMALIZED_LOCK_WITNESS", "1") != "0"
if _LOCK_WITNESS:
    from denormalized_tpu.common import lockwitness

    lockwitness.install()

from denormalized_tpu.common.record_batch import RecordBatch
from denormalized_tpu.common.schema import DataType, Field, Schema


if _LOCK_WITNESS:

    def pytest_terminal_summary(terminalreporter, exitstatus, config):
        viol = lockwitness.witness().violations()
        if viol:
            terminalreporter.section("lock-order witness")
            for v in viol:
                terminalreporter.write_line(v.render())
        else:
            terminalreporter.write_line(
                f"lock-order witness: "
                f"{len(lockwitness.witness().edges())} edge(s), "
                f"0 violations"
            )

    def pytest_sessionfinish(session, exitstatus):
        # a recorded inversion fails the run even if every test passed —
        # that is the witness's whole contract
        if exitstatus == 0 and lockwitness.witness().violations():
            session.exitstatus = 1

# -- env-gated per-test watchdog ------------------------------------------
# DENORMALIZED_TEST_TIMEOUT_S=<seconds> arms a SIGALRM per test that dumps
# EVERY thread's stack via faulthandler before failing the test.  The
# tier-1 runner once wedged inside test_idle_watermark and produced
# nothing but an 870s timeout kill (CHANGES.md PR 1) — a wedge must
# produce stacks, not silence.  Off by default: SIGALRM only exists on
# the main thread and some environments (debuggers) own it.
#
# SIGALRM's Python-level handler only runs between bytecodes on the main
# thread, so a main thread wedged INSIDE a blocking native call (stuck
# ctypes lsm_*/kc_fetch) would defer it forever — exactly the wedge class
# this exists for.  faulthandler.dump_traceback_later runs on a dedicated
# C watchdog thread and needs no bytecode, so it backstops that case:
# stacks dump and the process exits (a native wedge cannot be failed
# test-by-test anyway).
_TEST_TIMEOUT_S = float(os.environ.get("DENORMALIZED_TEST_TIMEOUT_S", 0) or 0)

if _TEST_TIMEOUT_S > 0:

    @pytest.fixture(autouse=True)
    def _test_watchdog(request):
        def _on_alarm(signum, frame):
            sys.stderr.write(
                f"\n=== watchdog: {request.node.nodeid} exceeded "
                f"{_TEST_TIMEOUT_S}s — all thread stacks follow ===\n"
            )
            faulthandler.dump_traceback(all_threads=True, file=sys.stderr)
            raise TimeoutError(
                f"test exceeded DENORMALIZED_TEST_TIMEOUT_S="
                f"{_TEST_TIMEOUT_S}s (thread stacks dumped to stderr)"
            )

        prev = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, _TEST_TIMEOUT_S)
        faulthandler.dump_traceback_later(
            _TEST_TIMEOUT_S + 10, exit=True, file=sys.stderr
        )
        try:
            yield
        finally:
            faulthandler.cancel_dump_traceback_later()
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, prev)


@pytest.fixture
def sensor_schema() -> Schema:
    """The emit_measurements shape: {occurred_at_ms, sensor_name, reading}
    (reference examples/examples/emit_measurements.rs:26-47)."""
    return Schema(
        [
            Field("occurred_at_ms", DataType.INT64, nullable=False),
            Field("sensor_name", DataType.STRING, nullable=False),
            Field("reading", DataType.FLOAT64),
        ]
    )


def make_sensor_batch(schema, ts, names, readings) -> RecordBatch:
    return RecordBatch(
        schema,
        [
            np.asarray(ts, dtype=np.int64),
            np.asarray(names, dtype=object),
            np.asarray(readings, dtype=np.float64),
        ],
    )


@pytest.fixture
def make_batch(sensor_schema):
    def _make(ts, names, readings):
        return make_sensor_batch(sensor_schema, ts, names, readings)

    return _make
