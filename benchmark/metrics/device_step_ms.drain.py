"""Device busy time per device step (stripe merge), in ms: the trace's
busy seconds over the window operator's ``device_steps``."""


def read(obs):
    steps = obs["counters"].get("device_steps")
    if not steps or not obs["trace"]:
        return None
    return obs["trace"]["busy_s"] * 1000.0 / steps
