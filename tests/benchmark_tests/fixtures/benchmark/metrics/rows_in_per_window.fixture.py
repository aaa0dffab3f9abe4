"""Fixture-only per-layer metric: rows the window operator took in per
window delivered.  Exists only under the tests' fixtures."""


def read(obs):
    if not obs["windows_delivered"] or not obs["counters"].get("rows_in"):
        return None
    return obs["counters"]["rows_in"] / obs["windows_delivered"]
