"""Share of the window the pull thread spent in ``window.finalize``: unpacking
the block, key lookup and building the emitted batches.  100 x the delta of
``phase_ms_finalize`` over the window's milliseconds; nothing where the
program has no such counter.  (The parked ``window_finalize_share.drain``
reads the same counter in the other cells.)"""

from benchmark.harness.host_spans import share


def read(obs):
    return share(obs, "phase_ms_finalize")
