"""The benchmark's one command.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once on the machine it is started on and prints, as the last
line of standard output, the result object ``benchmark/harness/lastline.py``
describes.  Exits non-zero and prints no result when JAX finds no TPU (or
fewer chips than the cell asks for), when a traced run's trace holds no
device operation, or when the line would be malformed.  ``--control 1``
also reads the control (the reference in bfloat16, put in the engine's
place) on the same sample and prints it on standard error; the benchmark's
own runs leave it at 0.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up counts from here, before any heavy import

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import lastline, manifest, runner

    try:
        cell = manifest.load_cell(args.workload)
        line = runner.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
            control=bool(args.control),
        )
    except (runner.RunFailed, lastline.Malformed, KeyError, OSError) as e:
        print(f"benchmark: no result: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
