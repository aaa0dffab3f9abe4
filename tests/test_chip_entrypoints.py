"""The entry points that run on the chip, checked where there is none:
``chip_smoke.py`` refuses a CPU it was not asked to use, its rehearsal
drives every leg through the same code the chip run takes, and the engine
and the smoke share one compile-cache rule.  Each case is its own
process: the platform and the cache directory are process-wide JAX
state."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(args, timeout, **env):
    base = {
        k: v for k, v in os.environ.items()
        # XLA_FLAGS: conftest's eight virtual devices are for sharding
        # tests; these entry points are one-device programs
        if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")
    }
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=timeout, env={**base, "JAX_PLATFORMS": "cpu", **env},
    )


def test_chip_smoke_refuses_a_cpu():
    proc = _run(["chip_smoke.py"], 120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr, proc.stderr[-500:]
    assert proc.stdout.strip() == ""  # no result line to mistake for one


def test_chip_smoke_rehearsal_passes_every_leg():
    proc = _run(["chip_smoke.py", "--rehearsal", "--seed", "7"], 300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    assert "rehearsal" in lines[0]["chip_smoke"]
    assert all(lines[0]["native"].values()), lines[0]
    legs = {ln["leg"]: ln for ln in lines if "leg" in ln}
    assert set(legs) == {
        "served", "keyed/auto", "keyed/scatter", "sliding/auto",
        "restore/run", "restore/restored",
    }
    assert all(ln["ok"] for ln in legs.values()), legs
    assert legs["served"]["decode_fallback_rows"] == 0
    assert legs["keyed/scatter"]["strategy_resolved"] == "row_shipping:scatter"
    assert legs["restore/run"]["committed_epochs"] >= 2
    assert legs["restore/restored"]["rows_in"] < legs["restore/run"]["rows"]
    assert lines[-1] == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


# Reports what enable_compilation_cache() did, on a process made to believe
# it holds a chip (CPU compiles are deliberately not cached).
_CACHE_PROBE = """
import json, types
import jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
jax.devices = lambda *a: [types.SimpleNamespace(platform="tpu")]
from denormalized_tpu.api.context import enable_compilation_cache
print(json.dumps({"returned": enable_compilation_cache(),
                  "config": jax.config.jax_compilation_cache_dir,
                  "updates": updates}))
"""


def _cache_probe(**env):
    proc = _run(["-c", _CACHE_PROBE], 120, **env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    want = str(tmp_path / "xla")
    got = _cache_probe(JAX_COMPILATION_CACHE_DIR=want)
    assert got["returned"] == got["config"] == want
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_default_cache_dir_is_the_checkout_in_every_process():
    first, second = _cache_probe(), _cache_probe()
    assert first["returned"] == first["config"] == str(REPO / ".jax_cache")
    assert second == first
