"""Bytes the host sent to the devices per byte of packed stripe it made, over
the measured window: the deltas of ``bytes_h2d`` and ``stripe_bytes_packed``.
1 = every packed byte crossed once (on a mesh: each key block's matrix to
its own device); n = the matrix went to n devices from the host.  Nothing
where the program has no such counter or packed nothing (row shipping).

Parked here like ``shard_cells_max_share.drain``, for the same reason (the
manifest entry waits in ``fixtures/mesh_entries.json``)."""


def read(obs):
    c = obs["counters"]
    if not c.get("stripe_bytes_packed") or "bytes_h2d" not in c:
        return None
    return c["bytes_h2d"] / c["stripe_bytes_packed"]
