"""Share of the window the pull thread spent in ``window.flush``: packing the
stripe, ``jnp.asarray(packed)`` and the merge program's dispatch.  100 x the
delta of ``phase_ms_flush`` over the window's milliseconds; nothing where the
program has no such counter.  (The parked ``window_flush_share.drain`` of the
tests' fixtures reads the same counter in the other cells.)"""

from benchmark.harness.host_spans import share


def read(obs):
    return share(obs, "phase_ms_flush")
