"""Rehearsal without the chip: the resident engine's whole-search program,
as the benchmark's configurations run it, compiles for a described TPU v5e
and fits its memory. Slow (a minute or more per configuration on the CPU).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_compile_v5e.py -s
"""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from harness.spec import BENCH_DIR
from harness.window import model_factory


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("config", ["2pc-8", "paxos-3"])
def test_search_program_compiles_and_fits(one_chip, config):
    from stateright_tpu.core.discovery import HasDiscoveries
    from stateright_tpu.tensor.resident import ResidentSearch, _finish_masks

    with open(os.path.join(BENCH_DIR, "configs", config + ".json")) as f:
        cfg = json.load(f)
    model = model_factory(cfg)()
    K, L = cfg["spawn"]["batch_size"], model.lanes
    assert L == cfg["lanes"]
    # append="dus" is what the engine picks on a TPU (resolve_append).
    rs = ResidentSearch(model, append="dus", **cfg["spawn"])
    req, anym = _finish_masks(HasDiscoveries.ALL, rs.props)

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = rs._kernel.lower(
        u32(K, L), u32(K), u32(K), req, anym, u32(), u32(), 1 << 30, i32,
        u32(), u32(), u32(), {},
    ).compile()
    mem = compiled.memory_analysis()
    print(f"{config}: {mem}")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
