#!/usr/bin/env bash
# Single lint entry point, CI-shaped: exit 0 iff the tree is clean.
#
#   tools/lint.sh            dnzlint + native warning build (-Werror)
#   tools/lint.sh --tsan     ... + the TSan-built native hammer smoke
#
# Everything here is also enforced as tier-1 tests (tests/test_lint.py,
# tests/test_native_build_gate.py, tests/test_native_sanitizers.py) —
# this script exists for fast local/CI runs without the pytest harness.
set -euo pipefail
cd "$(dirname "$0")/.."

fail=0

echo "== dnzlint (rules: docs/static_analysis.md)"
python -m tools.dnzlint denormalized_tpu --report LINT_REPORT.json || fail=1

# budget gate: the whole point of a tier-1 lint is that nobody skips it
# for being slow — the JSON report carries wall_clock_s so CI sees drift
if ! python - <<'EOF'
import json, sys
wall = json.load(open("LINT_REPORT.json"))["wall_clock_s"]
print(f"dnzlint wall clock: {wall}s (budget 60s)")
sys.exit(0 if wall < 60 else 1)
EOF
then
    echo "dnzlint blew its 60s wall-clock budget — profile the passes"
    fail=1
fi

echo "== fault-site docs drift"
table="$(python -m tools.dnzlint --fault-site-table)"
if ! python - "$table" <<'EOF'
import sys
table = sys.argv[1]
docs = open("docs/fault_tolerance.md").read()
sys.exit(0 if table in docs else 1)
EOF
then
    echo "docs/fault_tolerance.md fault-site table is stale — paste the"
    echo "output of: python -m tools.dnzlint --fault-site-table"
    fail=1
fi

echo "== replay-path docs drift"
table="$(python -m tools.dnzlint --replay-path-table)"
if ! python - "$table" <<'EOF'
import sys
table = sys.argv[1]
docs = open("docs/static_analysis.md").read()
sys.exit(0 if table in docs else 1)
EOF
then
    echo "docs/static_analysis.md replay-path table is stale — paste the"
    echo "output of: python -m tools.dnzlint --replay-path-table"
    fail=1
fi

if command -v g++ >/dev/null; then
    echo "== native warning build (-Wall -Wextra -Wshadow -Wconversion -Werror)"
    NATIVE=denormalized_tpu/native
    PY_INC="$(python -c 'import sysconfig; print(sysconfig.get_paths()["include"])')"
    WARN="-Wall -Wextra -Wshadow -Wconversion -Werror"
    tmp="$(mktemp -d)"
    trap 'rm -rf "$tmp"' EXIT
    # enumerate from disk (native_test is the standalone binary, built
    # below) so a new .cpp can never silently skip the warning build —
    # same completeness contract as test_native_build_gate.py
    for src in "$NATIVE"/*.cpp; do
        mod="$(basename "$src" .cpp)"
        [ "$mod" = native_test ] && continue
        extra=""
        [ "$mod" = kafka_client ] && extra="-lz"
        [ "$mod" = pyassemble ] && extra="-I$PY_INC"
        # shellcheck disable=SC2086
        g++ -O2 -shared -fPIC -std=c++17 $WARN \
            "$src" -o "$tmp/$mod.so" $extra \
            || { echo "WARN-BUILD FAILED: $mod"; fail=1; }
    done
    g++ -std=c++17 -g -O1 $WARN \
        "$NATIVE/native_test.cpp" -o "$tmp/native_test" -lz -ldl -lpthread \
        || { echo "WARN-BUILD FAILED: native_test"; fail=1; }

    if [ "${1:-}" = "--tsan" ]; then
        echo "== TSan hammer smoke"
        # -lpthread matters on glibc<2.34 (same reason as the pytest
        # driver) — without it a working TSan toolchain would be
        # misreported as absent
        if g++ -std=c++17 -g -fsanitize=thread \
               "$NATIVE/native_test.cpp" -o "$tmp/native_test_tsan" \
               -lz -ldl -lpthread 2>"$tmp/tsan_build.err"; then
            "$tmp/native_test_tsan" "$tmp/lsm" >/dev/null \
                || { echo "TSAN HAMMERS FAILED"; fail=1; }
        else
            echo "toolchain lacks TSan — skipping (reason follows)"
            tail -3 "$tmp/tsan_build.err" || true
        fi
    fi
else
    echo "== no g++ — native checks skipped (pure-Python fallbacks cover this box)"
fi

if [ "$fail" -eq 0 ]; then
    echo "lint: clean"
else
    echo "lint: FAILURES above"
fi
exit "$fail"
