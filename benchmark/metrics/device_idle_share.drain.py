"""1 - busy_s / window_s of the traced window, in percent."""


def read(obs):
    t = obs["trace"]
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
