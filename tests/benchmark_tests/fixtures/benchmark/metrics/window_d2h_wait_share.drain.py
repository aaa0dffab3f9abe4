"""Share of the window the pull thread spent in ``window.d2h_wait``:
``jax.device_get`` of an emission block, the only place it waits for the
device.  100 x the counters' delta over the window's milliseconds; nothing
where the program has no such counter."""

from benchmark.harness.host_spans import PHASE_SHARES, share


def read(obs):
    return share(obs, *PHASE_SHARES["window_d2h_wait_share.drain"])
