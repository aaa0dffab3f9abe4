"""Share of the window the pull thread spent in ``window.finalize``: unpacking
the block, key lookup and building the emitted batches.  100 x the counters'
delta over the window's milliseconds; nothing where the program has no such
counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_finalize_share.drain"])
