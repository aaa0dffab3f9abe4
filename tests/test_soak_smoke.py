"""Fast end-to-end runs of the soak harness (tools/soak.py).

The real soaks are minutes long (committed artifacts SOAK.json /
SOAK_JOIN.json / SOAK_SESSION.json); this keeps the harness itself
CI-validated: a ~20s run with one mid-stream SIGKILL must lose zero
windows, match the golden, and see EOS — for the simple windowed
pipeline, the stream-join pipeline (join state is the hardest
checkpoint-restore path), session windows (exact bounds checked), and
the sketch-native approx pipeline (HLL estimates held to exact integer
equality against a golden folded with the engine's own kernels).
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize(
    "pipeline", ["simple", "sliding", "join", "session", "udaf", "kafka",
                 "approx"]
)
def test_soak_smoke(tmp_path, pipeline):
    out = tmp_path / "soak.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "tools" / "soak.py"),
            "--pipeline", pipeline,
            "--minutes", "0.35", "--kill-every", "8",
            "--pace", "150000", "--out", str(out),
        ],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    r = json.loads(out.read_text())
    assert r["aborted"] is None, r
    assert r["eos_done_seen"], r
    assert r["kills"] >= 1, r
    assert r["windows_lost"] == 0, r
    assert r["windows_spurious"] == 0, r
    assert r["windows_mismatched"] == 0, r
    assert r["emitted_windows"] == r["golden_windows"] > 0, r
    # recovery after SIGKILL banks its first emission promptly
    for t in r["recovery_first_emit_s"]:
        assert t < 30, r


def test_soak_smoke_join_dense(tmp_path):
    """Shared-join multi-query registry under SIGKILL: 10 staggered
    queries windowing over ONE fact×dim interval join, every emission
    checked byte-identical to its independent join+window oracle,
    warm backfills exact, one pipeline build per segment."""
    out = tmp_path / "soak.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "tools" / "soak.py"),
            "--pipeline", "join_dense",
            "--minutes", "0.5", "--kill-every", "8",
            "--pace", "40000", "--batch-rows", "2048",
            "--out", str(out),
        ],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    r = json.loads(out.read_text())
    assert r["aborted"] is None, r
    assert r["eos_done_seen"], r
    assert r["kills"] >= 1, r
    jd = r["join_dense"]
    assert jd["oracle_rc"] == 0, jd
    assert jd["oracle_windows"] > 0, jd
    assert jd["failures"] == 0, jd
    assert jd["queries_silent"] == [], jd
    assert jd["backfill_missing"] == [], jd
    assert jd["backfilled_joiners"] >= 3, jd
    assert jd["max_builds_per_segment"] == 1, jd


def test_soak_smoke_query_dense(tmp_path):
    """Live multi-query registry under one SIGKILL: 50 staggered
    queries, every emission checked byte-identical to its independent
    oracle, backfills exact, one pipeline build per segment."""
    out = tmp_path / "soak.json"
    proc = subprocess.run(
        [
            sys.executable, str(REPO / "tools" / "soak.py"),
            "--pipeline", "query_dense",
            "--minutes", "0.5", "--kill-every", "8",
            "--pace", "40000", "--batch-rows", "2048",
            "--out", str(out),
        ],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    r = json.loads(out.read_text())
    assert r["aborted"] is None, r
    assert r["eos_done_seen"], r
    assert r["kills"] >= 1, r
    qd = r["query_dense"]
    assert qd["oracle_rc"] == 0, qd
    assert qd["oracle_windows"] > 0, qd
    assert qd["failures"] == 0, qd
    assert qd["queries_silent"] == [], qd
    assert qd["backfill_missing"] == [], qd
    assert qd["backfilled_joiners"] >= 10, qd
    assert qd["max_builds_per_segment"] == 1, qd
