"""Device-mesh construction.

The scale-out axis of the reference is CPU-thread partitioning: one tokio
task per Kafka partition plus a hash ``RepartitionExec`` exchange
(SURVEY.md §2.4).  The TPU-native analog is a ``jax.sharding.Mesh``: the
single mesh axis ``"keys"`` plays the role of the hash-partition axis —
group-state shards live one-per-device and rows reach the right shard via
masked scatter (no exchange needed on ICI, the batch rides the broadcast) or
via per-device partial state merged with ``psum`` (the Partial/Final analog).
Multi-host extends the same mesh over DCN (jax.distributed), not a separate
code path.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


KEY_AXIS = "keys"
# second mesh axis for the 2-D layout: data-parallel row slices (each
# slice ingests its own source partitions; ICI-local key blocks within a
# slice, cross-slice merge only at emission — the axis that rides DCN in
# a multi-slice job)
SLICE_AXIS = "slices"

shard_map = jax.shard_map


def make_mesh_2d(
    n_slices: int, n_key_shards: int | None = None, devices=None
) -> Mesh:
    """2-D mesh ``(slices, keys)``: rows are data-parallel across the
    slice axis, group-state is sharded across the key axis.  Lay the key
    axis innermost so its per-batch traffic (state updates, emission
    gathers) stays on the fastest links (ICI within a slice); the slice
    axis carries traffic only at emission/export (psum of window rows) —
    the cross-slice/DCN-tolerant direction."""
    if devices is None:
        devices = jax.devices()
    if n_key_shards is None:
        if len(devices) % n_slices:
            raise ValueError(
                f"{len(devices)} devices not divisible by {n_slices} slices"
            )
        n_key_shards = len(devices) // n_slices
    need = n_slices * n_key_shards
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices ({n_slices}x{n_key_shards}), have "
            f"{len(devices)}"
        )
    arr = np.array(devices[:need]).reshape(n_slices, n_key_shards)
    return Mesh(arr, (SLICE_AXIS, KEY_AXIS))


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh (axis "keys") over ``devices`` (default: ``jax.devices()``,
    the job-global list), truncated to the first ``n_devices``.  In
    multi-process jobs do NOT truncate — use
    :func:`denormalized_tpu.parallel.distributed.global_mesh`."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)} "
                f"({[d.platform for d in devices[:3]]}...)"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (KEY_AXIS,))
