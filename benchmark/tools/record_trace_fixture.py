"""Record a small profiler trace on the chip: the fixture that
``tests/benchmark_tests`` checks ``trace_reduce`` against.

    chiprun -- python -m benchmark.tools.record_trace_fixture

Runs a handful of small device programs with idle gaps between them under
``jax.profiler``, prints the trace's planes, lines and first events, and
copies the ``.xplane.pb`` to ``chiprun_out/trace_fixture/``.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    print("memory_stats", dev.memory_stats())
    out = os.path.join("chiprun_out", "trace_fixture")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    @jax.jit
    def step(state, x):
        return state.at[x % state.shape[0]].add(1.0) * 0.5

    state = jnp.zeros((4096, 128), jnp.float32)
    x = jnp.arange(2048, dtype=jnp.int32)
    step(state, x).block_until_ready()
    opts = None
    if hasattr(jax.profiler, "ProfileOptions"):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        print("ProfileOptions", [a for a in dir(opts) if not a.startswith("_")])
    tdir = os.path.join(out, "raw")
    t0 = time.monotonic()
    if opts is not None:
        jax.profiler.start_trace(tdir, profiler_options=opts)
    else:
        jax.profiler.start_trace(tdir)
    for i in range(6):
        with jax.profiler.TraceAnnotation("bench_step", i=i):
            state = step(state, x + i)
            state.block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    print("traced_s", time.monotonic() - t0)
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    print("files", paths, [os.path.getsize(p) for p in paths])
    pd = jax.profiler.ProfileData.from_file(paths[0])
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:4]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
    shutil.copy(paths[0], os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tdir)
    print("memory_stats_after", dev.memory_stats())
    return 0


if __name__ == "__main__":
    sys.exit(main())
