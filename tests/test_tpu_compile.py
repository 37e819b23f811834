"""The main path's kernels compile for a TPU v5e chip, at the widths the
Paxos-3 check runs (table 2^22, batch 8192 x 14 candidate lanes).

Nothing runs: the chip is described (`topologies.get_topology_desc`), not
attached, and each test compiles for it from this process. The topology is
described only inside the fixture below — never at import time — and these
tests live in this one file, so one xdist worker loads the TPU library for
all of them.
"""

import os

import pytest

import jax
import jax.numpy as jnp

TABLE = 1 << 22
BATCH = 8192
PAXOS3_ACTIONS = 14
LANES = BATCH * PAXOS3_ACTIONS  # 114,688 candidate lanes per step


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _insert_operands(sharding):
    table = [_u32(sharding, TABLE) for _ in range(4)]
    lanes = [_u32(sharding, LANES) for _ in range(4)]
    active = jax.ShapeDtypeStruct((LANES,), jnp.bool_, sharding=sharding)
    return (*table, *lanes, active)


def test_pallas_insert_compiles_to_the_mosaic_kernel(one_chip):
    from stateright_tpu.tensor.pallas_hashtable import (
        pallas_insert,
        pallas_partitions,
    )

    compiled = pallas_insert.lower(
        *_insert_operands(one_chip),
        n_partitions=pallas_partitions(TABLE),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort_insert_compiles(one_chip):
    from stateright_tpu.tensor.hashtable import _insert_impl

    compiled = jax.jit(_insert_impl).lower(
        *_insert_operands(one_chip)
    ).compile()
    assert compiled.memory_analysis() is not None


def test_expand_fingerprint_insert_step_compiles_at_paxos3_shapes(one_chip):
    from stateright_tpu.tensor.frontier import expand_insert
    from stateright_tpu.tensor.paxos import TensorPaxos

    model = TensorPaxos(client_count=3)
    assert model.max_actions == PAXOS3_ACTIONS
    lin = model.property_by_name("linearizable").condition

    def step(t_lo, t_hi, p_lo, p_hi, states, lo, hi, active):
        return (
            *expand_insert(
                model, t_lo, t_hi, p_lo, p_hi, states, lo, hi, active
            ),
            lin(model, states),
        )

    args = (
        *[_u32(one_chip, TABLE) for _ in range(4)],
        _u32(one_chip, BATCH, model.lanes),
        _u32(one_chip, BATCH),
        _u32(one_chip, BATCH),
        jax.ShapeDtypeStruct((BATCH,), jnp.bool_, sharding=one_chip),
    )
    compiled = jax.jit(step).lower(*args).compile()
    mem = compiled.memory_analysis()
    # The step's operands and temporaries fit one 16 GB chip.
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
