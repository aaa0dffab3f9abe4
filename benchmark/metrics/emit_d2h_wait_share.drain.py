"""Share of the window the pull thread spent in ``window.d2h_wait``:
``jax.device_get`` of an emission block, the only place it waits for the
device.  100 x the delta of ``phase_ms_d2h_wait`` over the window's
milliseconds; nothing where the program has no such counter.  (The parked
``window_d2h_wait_share.drain`` reads the same counter in the other cells.)"""

from benchmark.harness.host_spans import share


def read(obs):
    return share(obs, "phase_ms_d2h_wait")
