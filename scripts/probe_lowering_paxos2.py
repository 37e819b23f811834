"""Probe: lower the real 2-client Paxos ActorModel with the GENERIC lowering
and compare against the reference golden (32,971 generated / 16,668 unique,
ref examples/paxos.rs:327,351) and the hand-built TensorPaxos. Reports closure
wall time and table sizes."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")

from stateright_tpu.actor import Network
from stateright_tpu.actor.register import GetOk
from stateright_tpu.examples.paxos import NULL_VALUE, PaxosModelCfg
from stateright_tpu.tensor import FrontierSearch
from stateright_tpu.tensor.lowering import lower_actor_model
from stateright_tpu.tensor.model import TensorProperty

C = int(sys.argv[1]) if len(sys.argv) > 1 else 2

cfg = PaxosModelCfg(
    client_count=C, server_count=3,
    network=Network.new_unordered_nonduplicating(),
)

def _unused_local_boundary(i, s):
    return i >= 3 or s.state.ballot[0] <= C

def properties(view):
    lin = view.history_pred(lambda h: h.is_consistent())
    chosen = view.any_env(
        lambda e: isinstance(e.msg, GetOk) and e.msg.value != NULL_VALUE
    )
    return [
        TensorProperty.always("linearizable", lambda m, s: lin(s)),
        TensorProperty.sometimes("value chosen", lambda m, s: chosen(s)),
    ]

t0 = time.monotonic()
lowered = lower_actor_model(
    cfg.into_model(),

    properties=properties,
    max_histories=1 << 17,
    closure="exact",
    max_local_states=1 << 16,
    max_joint_states=1 << 22,
    max_envelopes=1 << 15,
)
t1 = time.monotonic()
print(f"closure: {t1-t0:.1f}s", flush=True)
print(f"  envelopes: {len(lowered.envs)}")
print(f"  local states/actor: {[len(s) for s in lowered.states]}")
print(f"  histories: {len(lowered.histories)}  hevents: {len(lowered.hevents)}")
print(f"  lanes: {lowered.lanes}  max_actions: {lowered.max_actions}", flush=True)

t2 = time.monotonic()
r = FrontierSearch(lowered, batch_size=2048, table_log2=22).run()
t3 = time.monotonic()
print(f"search: {t3-t2:.1f}s  states={r.state_count} unique={r.unique_state_count} depth={r.max_depth}")
print(f"discoveries: {sorted(r.discoveries)}")
