"""The knee of a paced cell, found once by a sweep on the chip.

    python -m benchmark.tools.knee_sweep --workload emit_sliding.paced \\
        --rates 3400000,4080000,... --seconds 20 --seed 7

Runs the cell at each offered rate (events per second; a multiple of 400 so
that 10 ms chunks split over 4 partitions), one after the other in this
process, and prints a line a rate: the latency percentiles, how far the
generator ran late, and the backlog (produced - fetched) when the window
opened and closed.  The knee is the highest rate at which the backlog does
not grow and the generator's lateness stays small against the latency.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from benchmark.harness import manifest, runner


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    for i, rate in enumerate(int(r) for r in args.rates.split(",")):
        at = dataclasses.replace(
            cell, traffic={**cell.traffic, "events_per_second": rate}
        )
        notes: list[str] = []
        try:
            line = json.loads(runner.run_cell(
                at, args.seed + i, args.seconds, False, log=notes.append
            ))
        except runner.RunFailed as e:
            print(json.dumps({"rate": rate, "failed": str(e)[-400:]}), flush=True)
            continue
        note = next(json.loads(n) for n in notes if n.startswith('{"workload"'))
        print(json.dumps({
            "rate": rate, "correct": line["correct"],
            "attempted": line["attempted"], "failed": line["failed"],
            **{k: v["value"] for k, v in line["metrics"].items()},
            "late_ms_p95": note["late_ms_p95"],
            "backlog_open": note["backlog_open"],
            "backlog_close": note["backlog_close"],
            "fetched_per_s": note["counters"].get("rows_in", 0) / note["window_s"],
            "compiles_in_window": note["compiles_in_window"],
            "latency_ms": note["latency_ms"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
