"""Share of the window the pull thread spent in ``window.statewatch``: the
state observatory's sketch update over every row's group id.  100 x the
counters' delta over the window's milliseconds; nothing where the program
has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_statewatch_share.drain"])
