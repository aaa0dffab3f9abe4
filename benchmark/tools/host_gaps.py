"""One traced run of a cell, and where the host's time went in it.

    chiprun -- python3 -m benchmark.tools.host_gaps --workload <cell> --seed <n> --seconds <s>

Runs the cell exactly as ``benchmark.run --trace 1`` does and prints, besides
the result line: the per-phase shares of the window from the engine's
always-on counters (``host_spans.phase_shares``) with the two identities
they have to satisfy; the self time of every engine span in the trace; and,
for the ten longest gaps between device programs, the host spans that
filled each.  The runner deletes the trace once it has reduced it, so this
process reads the host plane from inside its own wrapper around
``trace_reduce.reduce_trace``.  Everything printed is also written to
``chiprun_out/host_gaps/<cell>.<seed>.json``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import (
        host_spans,
        lastline,
        manifest,
        runner,
        trace_reduce,
    )

    cell = manifest.load_cell(args.workload)
    kept: dict = {}
    reduce_trace = trace_reduce.reduce_trace

    def reduce_and_keep(path: str, chips: int) -> dict:
        planes = trace_reduce.device_lines(path)
        kept["modules"] = planes[min(planes)].get(trace_reduce.MODULES_LINE, [])
        kept["events"] = host_spans.host_events(path)
        return reduce_trace(path, chips)

    logged: list[str] = []

    def log(msg: str) -> None:
        logged.append(msg)
        print(msg, file=sys.stderr, flush=True)

    trace_reduce.reduce_trace = reduce_and_keep
    try:
        line = runner.run_cell(
            cell, args.seed, args.seconds, True, t_start=T_START, log=log
        )
    except (runner.RunFailed, lastline.Malformed) as e:
        print(f"host_gaps: no result: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        trace_reduce.reduce_trace = reduce_trace

    run = next(json.loads(m) for m in logged if m.startswith('{"workload"'))
    obs = {"counters": run["counters"], "window_s": run["window_s"]}
    shares = host_spans.phase_shares(obs)
    window = [v for k, v in shares.items() if k.startswith("window_")]
    outside = {
        "window_op_busy_share": host_spans.share(obs, "dnz_op_batch_ms.window"),
        "hint_path_share": host_spans.share(obs, "hint_path_ms"),
        "window_input_wait_share": host_spans.share(
            obs, "dnz_op_input_wait_ms.window"),
        "kafka_fetch_load": host_spans.share(obs, "kafka_fetch_ms"),
        "kafka_decode_load": host_spans.share(obs, "kafka_decode_ms"),
    }
    report = {
        "workload": cell.name, "seed": args.seed,
        "events_per_s": run["metrics"]["events_per_s"],
        "phase_shares": shares, "outside": outside,
        "identities": {
            "window_phases_sum": None if None in window else sum(window),
            "busy_plus_hint_path": (
                None if None in (outside["window_op_busy_share"],
                                 outside["hint_path_share"])
                else outside["window_op_busy_share"] + outside["hint_path_share"]
            ),
            "prefetch_read_plus_blocked": (
                None if shares["prefetch_read_load.drain"] is None
                else shares["prefetch_read_load.drain"]
                + shares["prefetch_blocked_load.drain"]
            ),
            "partitions_x_100": 100 * cell.config["partitions"],
        },
        "span_events": len(kept["events"]),
        "self_times_s": dict(sorted(
            host_spans.self_times(kept["events"]).items(),
            key=lambda kv: -kv[1])),
        "gaps": host_spans.attribute_gaps(kept["modules"], kept["events"]),
    }
    out_dir = os.path.join(manifest.ROOT, "chiprun_out", "host_gaps")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.{args.seed}.json"), "w") as f:
        json.dump(report, f, indent=1)

    def pct(v):
        return "   (none)" if v is None else f"{v:9.3f}"

    print(f"# {cell.name} seed {args.seed}: "
          f"{report['events_per_s']:.0f} events/s (traced)")
    print("## shares of the window, from the counters (% of one thread)")
    for name, v in {**shares, **outside}.items():
        print(f"{pct(v)}  {name}")
    for name, v in report["identities"].items():
        print(f"{pct(v)}  {name}")
    print(f"## self time of the engine's spans in the trace "
          f"({report['span_events']} events)")
    for name, s in report["self_times_s"].items():
        print(f"{s:9.3f} s  {name}")
    print("## the ten longest gaps between device programs")
    for g in report["gaps"]:
        lead = ", ".join(f"{n} {v:.1f}" for n, v in g["lead"] if v >= 0.5)
        others = ", ".join(f"{n} {v:.0f}" for n, v in g["others"] if v >= 0.5)
        print(f"{g['gap_s']:.3f} s at {g['at_s']:.2f} s before {g['before']}: "
              f"{lead} | other threads: {others}")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
