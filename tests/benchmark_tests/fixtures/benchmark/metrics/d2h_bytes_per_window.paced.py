"""Bytes read back from the device per window delivered in the window."""


def read(obs):
    if not obs["windows_delivered"] or "bytes_d2h" not in obs["counters"]:
        return None
    return obs["counters"]["bytes_d2h"] / obs["windows_delivered"]
