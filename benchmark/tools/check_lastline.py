"""Check a run's last line the way the driver reads it.

    python -m benchmark.run ... | python -m benchmark.tools.check_lastline --workload <name> --trace <0|1>

Reads standard input, takes its last line, and holds it to
``benchmark/harness/lastline.py`` for the metrics the manifest lists for
that cell and kind of run.  Exit 0 and ``lastline ok`` when it would be
read; exit 1 with the problems otherwise.
"""

from __future__ import annotations

import argparse
import sys

from benchmark.harness import lastline, manifest


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    lines = [ln for ln in sys.stdin.read().splitlines() if ln.strip()]
    if not lines:
        print("lastline: nothing was printed")
        return 1
    found = lastline.check_text(
        lines[-1], cell.per_layer if args.trace else cell.end_to_end,
        bool(args.trace),
    )
    print(lines[-1])
    if found:
        print("lastline MALFORMED: " + "; ".join(found))
        return 1
    print("lastline ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
