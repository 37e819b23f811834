"""The register layer that TensorPaxos and TensorAbd share
(`tensor/register.py`): TensorPaxos computes through it exactly what it
computed with its own copy of the clients and the linearizability mask —
the successors, the valid masks and every property mask of seeded random
reachable states hash to the digests that copy gave — and the tables
deduplicate to the rows both models evaluate."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.tensor.abd import TensorAbd
from stateright_tpu.tensor.paxos import TensorPaxos
from stateright_tpu.tensor.register import RegisterClients, build_lin_tables

# sha256 over (states, successors, valid, every property on the states and
# on the successors) of `_digest` below, computed with TensorPaxos's own
# client field and mask, before they moved into tensor/register.py.
PAXOS_DIGESTS = {
    2: (322, "9ef120db1aced46cd9a5dcef8a945f91593348eb1df008c611806386d849c0a3"),
    3: (407, "e8ae52670c63efeabe55ca4cc9eeb12db0334d186d2f12a1f6604032f971be7a"),
}


def _random_states(model, seed, walks=24, length=30):
    rng = np.random.default_rng(seed)
    expand = jax.jit(model.expand)
    init = np.asarray(model.init_states())
    rows = [init[0]]
    for _ in range(walks):
        row = init[0]
        for _ in range(length):
            succ, valid = expand(jnp.asarray(row[None]))
            succ, valid = np.asarray(succ)[0], np.asarray(valid)[0]
            idx = np.flatnonzero(valid)
            if not len(idx):
                break
            row = succ[rng.choice(idx)]
            rows.append(row)
    return np.stack(rows)


def _digest(model, seed):
    states = _random_states(model, seed)
    succ, valid = jax.jit(model.expand)(jnp.asarray(states))
    h = hashlib.sha256()
    for a in (states, succ, valid):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    flat = jnp.asarray(np.asarray(succ).reshape(-1, model.lanes))
    for p in model.properties():
        cond = jax.jit(lambda r, p=p: p.condition(model, r))
        for rows in (jnp.asarray(states), flat):
            h.update(np.asarray(cond(rows)).tobytes())
    return len(states), h.hexdigest()


@pytest.mark.parametrize("clients", [2, 3])
def test_paxos_computes_what_its_own_copy_computed(clients):
    model = TensorPaxos(client_count=clients)
    assert _digest(model, 20261018 + clients) == PAXOS_DIGESTS[clients]


def test_both_models_hold_one_register_layer():
    paxos, abd = TensorPaxos(client_count=2), TensorAbd(2, 3)
    for m in (paxos, abd):
        assert isinstance(m.clients, RegisterClients)
        assert m.trace_args()["lin_rows"] == len(m.clients.lin_phase)
    for a, b in zip((paxos.clients.lin_phase, paxos.clients.lin_ret,
                     paxos.clients.lin_maxf), build_lin_tables(2)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("clients,rows", [(1, 3), (2, 18), (3, 215)])
def test_lin_tables_dedupe_to_distinct_rows(clients, rows):
    phase, ret, maxf = build_lin_tables(clients)
    stacked = np.concatenate(
        [phase, ret.astype(np.int64), maxf.reshape(len(maxf), -1)], axis=1)
    assert len(np.unique(stacked, axis=0)) == len(stacked) == rows


def test_mask_reads_a_completed_get():
    """Two clients; client 0's Get completed after client 1's Put had
    completed. It may read 'B' (A, B, Get) or 'A' (B, A, Get); a read of
    NULL after client 0's own Put is never legal."""
    reg = RegisterClients(2)

    def lane(c0, c1):
        return (c0 | (c1 << 8))

    def field(phase, ret=0, frontier=0):
        return phase | (ret << 2) | (frontier << 4)

    lanes = jnp.asarray([
        lane(field(0), field(0)),  # both puts in flight
        lane(field(2, ret=1, frontier=1), field(1, frontier=1)),
        lane(field(2, ret=0, frontier=1), field(1, frontier=1)),
        lane(field(2, ret=3, frontier=0), field(0)),  # a NULL read
    ], jnp.uint32)
    got = np.asarray(reg.linearizable(lanes)).tolist()
    assert got == [True, True, True, False]


@pytest.mark.parametrize("make", [lambda: TensorPaxos(client_count=2),
                                  lambda: TensorAbd(2, 2)],
                         ids=["paxos-2", "abd-2-2"])
def test_step_scopes_and_span_args(make, tmp_path):
    """The named scopes the chip breakdown reads (`deliver`, `server`,
    `emit` in the expand; `linearizable` around the shared mask) are on
    the lowered programs' ops, and a check's `search.run` span records the
    model's constants."""
    import json

    model = make()
    rows = model.init_states()
    step = jax.jit(model.expand).lower(rows).as_text(debug_info=True)
    assert all(f"/{s}/" in step for s in ("deliver", "server", "emit"))
    lin = model.property_by_name("linearizable")
    mask = jax.jit(lambda r: lin.condition(model, r)).lower(rows)
    assert "/linearizable/" in mask.as_text(debug_info=True)

    out = tmp_path / "trace.json"
    model.checker().trace_out(str(out)).spawn_tpu(
        batch_size=256, table_log2=16).join()
    (run,) = [e for e in json.load(open(out))["traceEvents"]
              if e.get("name") == "search.run"]
    assert {k: run["args"][k] for k in model.trace_args()} == model.trace_args()
    assert set(model.trace_args()) == {"lanes", "max_actions", "vocabulary",
                                       "lin_rows", "pool_size"}
