"""Incremental real-TPU validation + warm-up of the device stack.

Runs smallest-to-largest with flushed, timestamped progress so a stall is
attributable to a specific phase. Shares bench.py's persistent
compilation-cache dir (stateright_tpu/compile_cache.py) and its exact
workload shapes, so a completed run leaves every bench kernel compiled.

Usage: python -u scripts/tpu_validate.py [phase...]
  phases (default all, in order): probe kernels frontier resident bench2pc
  benchpaxos2 benchpaxos3
"""

from __future__ import annotations

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402 — shares the workload shapes
import jax  # noqa: E402

from stateright_tpu.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T0:8.1f}s] {msg}", flush=True)


def timed(label: str, fn, *args, **kw):
    t = time.monotonic()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    log(f"{label}: {time.monotonic() - t:.3f}s")
    return out


def phase_probe():
    log(f"devices: {jax.devices()}")
    x = timed("trivial jit", jax.jit(lambda a: a * 2 + 1), jnp.arange(8))
    assert x[-1] == 15
    timed("trivial jit (cached)", jax.jit(lambda a: a * 2 + 1), jnp.arange(8))


def phase_kernels():
    from stateright_tpu.tensor.hashtable import HashTable

    rng = np.random.default_rng(7)
    table = HashTable(14)
    lo = jnp.asarray(rng.integers(1, 2**32, 4096, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, 4096, dtype=np.uint32))
    z = jnp.zeros(4096, dtype=jnp.uint32)
    act = jnp.ones(4096, dtype=bool)
    r = timed("hashtable insert 4k (compile+run)", table.insert, lo, hi, z, z, act)
    n_first = int(np.asarray(r.is_new).sum())
    r = timed("hashtable re-insert 4k (cached)", table.insert, lo, hi, z, z, act)
    assert int(np.asarray(r.is_new).sum()) == 0, "re-insert must dedup"
    log(f"hashtable: {n_first} unique of 4096 inserted, re-insert deduped")


def phase_frontier():
    from stateright_tpu.tensor.frontier import FrontierSearch
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    s = FrontierSearch(TensorTwoPhaseSys(3), batch_size=512, table_log2=14)
    r = timed("FrontierSearch 2pc-3 (compile+run)", s.run)
    assert r.unique_state_count == 288, r
    log(f"frontier 2pc-3: {r.state_count} gen / {r.unique_state_count} unique ok")


def phase_resident():
    from stateright_tpu.tensor.models import TensorTwoPhaseSys
    from stateright_tpu.tensor.resident import ResidentSearch

    s = ResidentSearch(TensorTwoPhaseSys(3), batch_size=512, table_log2=14)
    r = timed("ResidentSearch 2pc-3 (compile+run)", s.run)
    assert r.unique_state_count == 288, r
    r = timed("ResidentSearch 2pc-3 (cached)", s.run)
    log(f"resident 2pc-3: {r.state_count} gen / {r.unique_state_count} unique ok")


def _bench_workload(model_name: str, n: int):
    import bench

    r, err = bench.device_search(model_name, n)
    log(
        f"bench workload {model_name}-{n}: {r['states']} gen in {r['sec']}s "
        f"({r['states_per_sec']:.0f}/s, compile {r['compile_sec']}s)"
        + (f" PARITY ERROR: {err}" if err else " parity ok")
    )


def phase_bench2pc():
    _bench_workload("2pc", 4)


def phase_benchpaxos2():
    _bench_workload("paxos", 2)


def phase_benchpaxos3():
    _bench_workload("paxos", 3)


PHASES = {
    "probe": phase_probe,
    "kernels": phase_kernels,
    "frontier": phase_frontier,
    "resident": phase_resident,
    "bench2pc": phase_bench2pc,
    "benchpaxos2": phase_benchpaxos2,
    "benchpaxos3": phase_benchpaxos3,
}


if __name__ == "__main__":
    names = sys.argv[1:] or list(PHASES)
    for name in names:
        log(f"=== phase {name} ===")
        PHASES[name]()
    log("ALL PHASES OK")
