"""Rows the window operator emitted per row it took in: the window's
groups a slide over the rows a slide brings.  Exists only under the tests'
fixtures: the metric a deployment brings of its own."""


def read(obs):
    c = obs["counters"]
    if not c.get("rows_in") or "emit_rows" not in c:
        return None
    return c["emit_rows"] / c["rows_in"]
