"""Ahead-of-time compiles for a TPU v5e, on a machine that has none.

libtpu can build a device topology without hardware
(``jax.experimental.topologies``), and lowering against one of its devices
runs the real XLA:TPU compiler.  A CPU run never sees what that compiler
refuses (a program that does not fit the device's memory, an unsupported
layout), so this is the only guard a CPU run gives the main path's
programs before a chip call — and it costs no chip time.  Skipped where
the topology cannot be built (no libtpu).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from denormalized_tpu.ops import segment_agg as sa
from denormalized_tpu.ops.host_partial import HostPartialStripe

B = 131_072  # the `simple` deployment's arrival batch


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no libtpu here"
        pytest.skip(f"cannot build a v5e topology: {type(e).__name__}: {e}")


@pytest.fixture(scope="module")
def v5e(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def v5e_mesh(topo):
    """The four chips of one v5e host as the 1-D key mesh."""
    import numpy as np
    from jax.sharding import Mesh

    from denormalized_tpu.parallel.mesh import KEY_AXIS

    return Mesh(np.array(topo.devices), (KEY_AXIS,))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _simple_spec(slide_ms=1000):
    # upstream simple_aggregation: count/min/max/avg of one column
    aggs = [("count", 0), ("min", 0), ("max", 0), ("avg", 0)]
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for(aggs)),
        num_value_cols=1,
        window_slots=16,
        group_capacity=1024,
        length_ms=1000,
        slide_ms=slide_ms,
    ), tuple(aggs)


def _state(spec, sharding):
    return {
        c.label: _sds(
            sharding,
            (spec.window_slots, spec.group_capacity),
            spec.init_value(c).dtype,
        )
        for c in spec.components
    }


@pytest.mark.parametrize("slide_ms", [1000, 200])
def test_scatter_update_compiles(v5e, slide_ms):
    spec, _ = _simple_spec(slide_ms)
    sa.update_state.lower(
        spec,
        _state(spec, v5e),
        _sds(v5e, (B, 1), jnp.float32),
        _sds(v5e, (B, 1), jnp.bool_),
        _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.bool_),
        _sds(v5e, (), jnp.int32),
    ).compile()


@pytest.mark.parametrize("dense", [False, True])
def test_merge_partials_compiles(v5e, dense):
    spec, _ = _simple_spec()
    spec = dataclasses.replace(spec, group_capacity=4096)
    stripe = HostPartialStripe(spec, spec.group_capacity)
    a_pad = stripe.unit_cells if dense else stripe.transfer_buckets()[-1]
    lean = sa.lean_possible(spec)
    rows = stripe.n_planes(lean) + (0 if dense else 1)
    # a flush's dense units go stacked, the stripe's span to a call
    shape = (stripe.U, rows, a_pad + 2) if dense else (rows, a_pad + 2)
    assert stripe.U == 16
    sa.merge_partials.lower(
        spec, stripe.SUB, a_pad, lean, dense, _state(spec, v5e),
        _sds(v5e, shape, jnp.int32),
    ).compile()


def _keyed_10m_spec():
    # benchmark/configs/keyed_10m.json: five aggregates of one column, 10 s
    # tumbling, a ring of 16 x 10,000,000 x 5 planes = 3.2 GB
    aggs = [("count", 0), ("sum", 0), ("min", 0), ("max", 0), ("avg", 0)]
    return sa.WindowKernelSpec(
        components=tuple(sa.components_for(aggs)),
        num_value_cols=1,
        window_slots=16,
        group_capacity=10_000_000,
        length_ms=10_000,
        slide_ms=10_000,
    ), tuple(aggs)


def test_keyed_10m_programs_compile_and_leave_the_ring_alone(v5e):
    """The merge and the finals emission at the keyed_10m ring's real size:
    they compile (in seconds: the finals program once took minutes, for
    ``jnp.packbits`` and a gather over computed rows), fit the chip beside
    the ring, and keep no ring-sized scratch (the merge once re-laid every
    ``(16, G)`` plane out as one dimension around each scatter)."""
    spec, aggs = _keyed_10m_spec()
    stripe = HostPartialStripe(spec, spec.group_capacity)
    assert stripe.U == 1 and stripe.transfer_buckets()[-1] == 1 << 23
    ring = 16 * spec.group_capacity * 4 * len(spec.components)
    row = spec.group_capacity * 4
    slot = _sds(v5e, (), jnp.int32)
    for a_pad, dense in ((1 << 22, False), (stripe.unit_cells, True)):
        rows = stripe.n_planes(True) + (0 if dense else 1)
        shape = (1, rows, a_pad + 2) if dense else (rows, a_pad + 2)
        m = sa.merge_partials.lower(
            spec, 1, a_pad, True, dense, _state(spec, v5e),
            _sds(v5e, shape, jnp.int32),
        ).compile().memory_analysis()
        assert m.alias_size_in_bytes == ring  # folded in place
        assert m.temp_size_in_bytes < 8 * row + 8 * 4 * a_pad
    for n in (1, 8):
        m = sa._finals_and_reset.lower(
            spec, aggs, n, spec.group_capacity, _state(spec, v5e), slot
        ).compile().memory_analysis()
        assert m.alias_size_in_bytes == ring
        assert m.temp_size_in_bytes + m.output_size_in_bytes - ring < 16e9 - ring


def test_emission_programs_compile(v5e):
    spec, aggs = _simple_spec()
    slot = _sds(v5e, (), jnp.int32)
    G = spec.group_capacity
    sa._gather_and_reset.lower(
        spec, 8, G, _state(spec, v5e), slot, sa.lean_possible(spec)
    ).compile()
    sa._finals_and_reset.lower(
        spec, aggs, 8, G, _state(spec, v5e), slot
    ).compile()


def test_keyed_40m_programs_keep_the_ring_split_over_four_chips(v5e_mesh):
    """benchmark/configs/keyed_40m.json on the four chips of one host: the
    merge and the finals emission of the key-sharded partial_merge backend
    at the real size — a 12.8 GB ring, 3.2 GB a chip.  Every chip folds its
    own key block in place, no program holds a collective, the finals and
    the active bits come back split over the key axis, and the fullest
    program (the prewarm's n = 8 block) leaves a chip two thirds empty.
    (Left to GSPMD, the same emission gathered 7.7 GB of scratch a chip at a
    bucket that does not align with the key blocks.)"""
    import dataclasses
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from denormalized_tpu.parallel import sharded_state as ss
    from denormalized_tpu.parallel.mesh import KEY_AXIS

    spec10, aggs = _keyed_10m_spec()  # the device-local spec: 10M groups
    n, G = 4, 40_000_000
    assert spec10.group_capacity * n == G
    split = NamedSharding(v5e_mesh, P(None, KEY_AXIS))
    state = {
        c.label: _sds(split, (16, G), spec10.init_value(c).dtype)
        for c in spec10.components
    }
    slot = _sds(NamedSharding(v5e_mesh, P()), (), jnp.int32)
    ring = 16 * spec10.group_capacity * 4 * len(spec10.components)  # a chip
    stripe = HostPartialStripe.__new__(HostPartialStripe)  # no 1.6 GB here
    stripe.block_cells = spec10.group_capacity
    stripe._buckets = HostPartialStripe.buckets_for(stripe.block_cells)
    assert stripe.transfer_buckets()[-1] == 1 << 23

    def check(compiled, out_bytes):
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes == ring  # the block folded in place
        assert m.output_size_in_bytes - ring <= out_bytes + 4096  # padding
        assert ring + out_bytes + m.temp_size_in_bytes < 6e9
        assert not re.search(
            r"all-gather|all-reduce|all-to-all|collective-permute",
            compiled.as_text(),
        )
        for sh in jax.tree.leaves(compiled.output_shardings):
            assert sh.is_equivalent_to(split, 2)

    a_pad = 1 << 21  # a 4M-row stripe's quarter, padded
    packed = _sds(
        NamedSharding(v5e_mesh, P(KEY_AXIS)), (n, 6, a_pad + 2), jnp.int32
    )
    check(ss._key_sharded_merge_partials.lower(
        spec10, v5e_mesh, 1, a_pad, True, False, state, packed
    ).compile(), 0)
    for blocks in (1, 8):
        check(ss._key_sharded_finals_and_reset.lower(
            spec10, v5e_mesh, aggs, blocks, spec10.group_capacity, state, slot
        ).compile(), blocks * spec10.group_capacity * (5 * 4 + 1))

