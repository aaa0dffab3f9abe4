"""Time the prefetch workers spent in ``prefetch.blocked`` (waiting for a
buffer slot: the pull thread has not taken their last batch), summed over
workers, in percent of one thread.  100 x the counters' delta over the
window's milliseconds; nothing where the program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["prefetch_blocked_load.drain"])
