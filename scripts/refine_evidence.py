"""Timing evidence for warm-round refinement: refine
paxos-C end-to-end on this host and print per-round + total wall time.
Usage: python scripts/refine_evidence.py [clients=2] [batch=2048] [table_log2=21]
"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")

from stateright_tpu.actor import Network
from stateright_tpu.actor.register import GetOk
from stateright_tpu.examples.paxos import NULL_VALUE, PaxosModelCfg
from stateright_tpu.tensor.lowering import refine_check
from stateright_tpu.tensor.model import TensorProperty

C = int(sys.argv[1]) if len(sys.argv) > 1 else 2
B = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
T = int(sys.argv[3]) if len(sys.argv) > 3 else 21

cfg = PaxosModelCfg(
    client_count=C, server_count=3,
    network=Network.new_unordered_nonduplicating(),
)

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

def prog(rnd, gaps, result):
    print(
        f"  round {rnd}: {gaps} gaps, {result.state_count:,} gen, "
        f"+{time.monotonic()-t0:.1f}s",
        flush=True,
    )

r, lowered = refine_check(
    cfg.into_model(),
    batch_size=B,
    table_log2=T,
    seed_states=2048,
    max_rounds=96,
    progress=prog,
    properties=properties,
    max_histories=1 << 17,
    max_local_states=1 << 16,
    max_envelopes=1 << 15,
)
dt = time.monotonic() - t0
print(
    f"paxos-{C} refined: {r.unique_state_count:,} unique / "
    f"{r.state_count:,} gen complete={r.complete} "
    f"{sorted(r.discoveries)}"
)
print(f"TOTAL {dt:.1f}s")
