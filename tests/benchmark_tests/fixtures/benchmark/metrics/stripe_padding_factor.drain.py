"""Cells the host stripe sent to the device per cell that held rows, over the
window: ``stripe_cells_shipped`` / ``stripe_cells_active`` (padding factor of
the transfer ladder; 1 = no padding).  Nothing where the program has no such
counters (every commit before PR 27).  Parked here with the per-phase shares
until a ``benchmark`` PR lets a reader that finds nothing be left out of a
traced line (PERF.md, section 7)."""


def read(obs):
    c = obs["counters"]
    if not c.get("stripe_cells_active") or "stripe_cells_shipped" not in c:
        return None
    return c["stripe_cells_shipped"] / c["stripe_cells_active"]
