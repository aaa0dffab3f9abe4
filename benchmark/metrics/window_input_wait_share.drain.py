"""Share of the window in which the window operator waited for its input
(fetch + decode upstream of it): sum of ``dnz_op_input_wait_ms{op=window}``
over the window's milliseconds, in percent."""


def read(obs):
    wait = obs["counters"].get("dnz_op_input_wait_ms.window")
    return None if wait is None else 100.0 * wait / (obs["window_s"] * 1000.0)
