"""Share of the window in the window operator's spans that the seven named
shares do not cover: the self time of ``window.process_batch`` / ``hint`` /
``marker`` / ``eos``, and ``trigger``, ``gather``, ``acc_wait``, ``update``.
100 x the counters' delta over the window's milliseconds; nothing where the
program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_other_share.drain"])
