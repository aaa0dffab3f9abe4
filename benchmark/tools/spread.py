"""Spreads of the end-to-end metrics over sets of runs, as the driver takes
them: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python -m benchmark.tools.spread chiprun_out/runs/<cell>.set1.jsonl chiprun_out/runs/<cell>.set2.jsonl

Each file holds one result line a run.  Prints, per metric, each set's
median and spread, the wider spread, and five times it.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv: list[str]) -> int:
    sets = []
    for path in argv[1:]:
        with open(path) as f:
            sets.append([json.loads(ln) for ln in f if ln.strip()])
    names = sorted({m for s in sets for line in s for m in line["metrics"]})
    for name in names:
        row = []
        for s in sets:
            vals = [ln["metrics"][name]["value"] for ln in s if name in ln["metrics"]]
            if name == "setup_s":
                vals = vals[1:]  # the first run of a set compiles
            if len(vals) < 2:
                continue
            row.append((statistics.median(vals), spread(vals), len(vals)))
        if not row:
            continue
        widest = max(r[1] for r in row)
        print(name, " ".join(
            f"median={m:.6g} spread={s:.4%} n={n}" for m, s, n in row
        ), f"widest={widest:.4%} five_times={5 * widest:.4%}")
    bad = [ln for s in sets for ln in s if not ln["correct"]]
    print("runs", sum(len(s) for s in sets), "not correct", len(bad))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
