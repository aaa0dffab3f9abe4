"""Bytes sent to the device per row the window operator took in."""


def read(obs):
    c = obs["counters"]
    if not c.get("rows_in"):
        return None
    return c["bytes_h2d"] / c["rows_in"]
