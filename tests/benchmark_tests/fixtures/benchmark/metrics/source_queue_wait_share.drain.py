"""Share of the window the pull thread spent in ``source.queue_wait``: blocked
in the pump's get with nothing ready, truly starved.  100 x the counters'
delta over the window's milliseconds; nothing where the program has no such
counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["source_queue_wait_share.drain"])
