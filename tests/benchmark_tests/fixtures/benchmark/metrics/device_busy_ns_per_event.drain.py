"""Device busy time per row the window operator took in, in ns: the trace's
busy seconds over the delta of ``rows_in``.  Held beside the wall's ns a row
(1e9 / events_per_s) it says when the chip sets the pace."""


def read(obs):
    rows = obs["counters"].get("rows_in")
    if not rows or not obs["trace"]:
        return None
    return obs["trace"]["busy_s"] * 1e9 / rows
