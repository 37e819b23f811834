"""Sharded-vs-single overhead ratio on the virtual CPU mesh: run the SAME workload on the single-device resident engine and on
the N-device sharded engine, print states/s for both and the ratio.

Usage: python scripts/sharded_overhead.py [workload=2pc7] [n_chips=8]
Workloads: 2pc7 | 2pc5 | paxos2-lowered | paxos5s4c-10
"""
import math
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
n_chips = int(sys.argv[2]) if len(sys.argv) > 2 else 8
flags = os.environ.get("XLA_FLAGS", "")
want = f"--xla_force_host_platform_device_count={n_chips}"
if want not in flags:
    # Strip any stale device-count flag (a leftover value would silently
    # size the mesh wrong) and pin the requested one.
    flags = " ".join(
        f for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
import jax
jax.config.update("jax_platforms", "cpu")

from stateright_tpu.parallel import ShardedSearch, make_mesh
from stateright_tpu.tensor.resident import ResidentSearch

wl = sys.argv[1] if len(sys.argv) > 1 else "2pc7"
if wl in ("2pc7", "2pc5"):
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    n = int(wl[3:])
    model = TensorTwoPhaseSys(n)
    batch, table = (4096, 20) if n == 7 else (1024, 16)
    golden = {7: (2_744_706, 296_448), 5: (58_146, 8_832)}[n]
elif wl == "paxos5s4c-10":
    from bench import _paxos5s4c_lowered

    t0 = time.monotonic()
    model = _paxos5s4c_lowered(10)
    print(f"closure: {time.monotonic()-t0:.1f}s", flush=True)
    batch, table = 4096, 19
    st = model.closure_stats
    golden = (st["generated"], st["unique"])
elif wl == "paxos2-lowered":
    from stateright_tpu.actor import Network
    from stateright_tpu.actor.register import GetOk
    from stateright_tpu.examples.paxos import NULL_VALUE, PaxosModelCfg
    from stateright_tpu.tensor import TensorProperty
    from stateright_tpu.tensor.lowering import lower_actor_model

    cfg = PaxosModelCfg(
        client_count=2, server_count=3,
        network=Network.new_unordered_nonduplicating(),
    )

    def properties(view):
        lin = view.history_pred(
            lambda h: h.is_consistent()
        )
        chosen = view.any_env(
            lambda e: isinstance(e.msg, GetOk) and e.msg.value != NULL_VALUE
        )
        return [
            TensorProperty.always("linearizable", lambda m, s: lin(s)),
            TensorProperty.sometimes("value chosen", lambda m, s: chosen(s)),
        ]

    t0 = time.monotonic()
    model = lower_actor_model(
        cfg.into_model(), properties=properties, closure="exact"
    )
    print(f"closure: {time.monotonic()-t0:.1f}s", flush=True)
    batch, table = 1024, 17
    golden = (32_971, 16_668)
else:
    raise SystemExit(f"unknown workload {wl}")


RUN_KW = {"target_max_depth": 10} if wl == "paxos5s4c-10" else {}


def best_of(mk, runs=2):
    s = mk()
    r = s.run(**RUN_KW)  # compile + first
    best = r
    for _ in range(runs):
        r = s.run(**RUN_KW)
        if r.duration < best.duration:
            best = r
    return best


single = best_of(lambda: ResidentSearch(model, batch_size=batch, table_log2=table))
assert (single.state_count, single.unique_state_count) == golden, single
sps_single = single.state_count / single.duration
print(f"single-device: {sps_single:,.0f} states/s ({single.duration:.2f}s)")

mesh = make_mesh(n_chips)
shard = best_of(
    lambda: ShardedSearch(
        model,
        mesh=mesh,
        batch_size=max(batch // n_chips, 64),
        table_log2=table - int(math.log2(n_chips)),
    )
)
assert (shard.state_count, shard.unique_state_count) == golden, shard
sps_shard = shard.state_count / shard.duration
print(f"sharded-{n_chips}:  {sps_shard:,.0f} states/s ({shard.duration:.2f}s)")
print(
    f"RATIO sharded/single = {sps_shard / sps_single:.3f} "
    f"(>0.5 means <2x overhead)"
)
