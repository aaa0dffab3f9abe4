"""Share of the window spent in ``window.reduce``: ``backend.accumulate``, the
k-way host reduce into the stripe.  100 x the counters' delta over the
window's milliseconds; nothing where the program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_reduce_share.drain"])
