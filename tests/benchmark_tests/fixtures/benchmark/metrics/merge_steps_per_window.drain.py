"""Device steps (stripe flushes, each one merge program per slide unit with
rows) per window the operator emitted in the measured window: the deltas of
``device_steps`` and ``windows_emitted``.  1 is one flush a slide unit; what
the traffic around a unit's boundary adds shows above it.  Nothing in a
window without a close."""


def read(obs):
    c = obs["counters"]
    if not c.get("windows_emitted") or "device_steps" not in c:
        return None
    return c["device_steps"] / c["windows_emitted"]
