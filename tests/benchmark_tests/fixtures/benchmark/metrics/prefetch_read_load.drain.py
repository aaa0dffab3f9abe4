"""Time the prefetch workers spent in ``prefetch.read`` (wire fetch + native
decode), summed over workers, in percent of one thread: 0-400 at four
partitions.  100 x the counters' delta over the window's milliseconds;
nothing where the program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["prefetch_read_load.drain"])
