"""Bytes read back from the device per window the operator emitted in the
measured window: the deltas of ``bytes_d2h`` and ``windows_emitted``."""


def read(obs):
    c = obs["counters"]
    if not c.get("windows_emitted") or "bytes_d2h" not in c:
        return None
    return c["bytes_d2h"] / c["windows_emitted"]
