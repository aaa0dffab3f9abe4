"""Share of the window the pull thread spent in ``window.project``: timestamps
to slide units, the ``first_open`` rebase, capacity check, late mask, value
expressions and validity.  100 x the counters' delta over the window's
milliseconds; nothing where the program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_project_share.drain"])
