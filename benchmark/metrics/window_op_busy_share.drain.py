"""Share of the window in which the window operator was busy (intern, host
reduce, dispatch, emission): sum of ``dnz_op_batch_ms{op=window}`` over the
window's milliseconds, in percent."""


def read(obs):
    busy = obs["counters"].get("dnz_op_batch_ms.window")
    return None if busy is None else 100.0 * busy / (obs["window_s"] * 1000.0)
