"""Test configuration.

Device-layer tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip). These env vars must be
set before jax is first imported anywhere in the test process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the platform in the config too, so the suite runs on the virtual CPU
# mesh whatever JAX_PLATFORMS the caller's environment carries.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    # NOTE: do NOT point jax_compilation_cache_dir at a persistent cache
    # here. It was tried (round 6) to cut the suite's compile-dominated
    # wall clock, and on the jax build of that round cache-DESERIALIZED
    # executables mishandled donated buffers (donate_argnums): the
    # frontier engine's step kernel read stale visited tables until the
    # table "overflowed", and a partially-warm cache segfaulted the
    # process outright (tests/test_checkpoint.py::test_multiple_suspensions
    # reproduced both). The in-process multi-kernel suite compiles fresh.
except ImportError:  # host-only test environments
    pass


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_program_caches():
    """Each test starts with none of the compiled programs this process
    shares across checks (stateright_tpu/compile_cache.py), and leaves
    none behind: what a test reads of a trace, a lowering or a load does
    not depend on which tests its worker ran before it."""
    from stateright_tpu.compile_cache import clear_program_caches

    clear_program_caches()
    yield
    clear_program_caches()
