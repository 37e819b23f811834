"""Path replay (tensor/frontier.py replay_fp_chain): one compiled
expand-and-fingerprint program per transition must rebuild exactly the
path the op-by-op replay did, trace once per model definition (once per
instance of a model that names none), and keep the model collectable."""

import gc
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.core.discovery import HasDiscoveries
from stateright_tpu.core.path import Path
from stateright_tpu.tensor.fingerprint import pack_fp
from stateright_tpu.tensor.frontier import (
    _REPLAYS,
    replay_fp_chain,
    replay_traces,
    state_fingerprint,
)
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.resident import ResidentSearch


def _eager_replay(model, chain) -> Path:
    """The replay as it ran before it was compiled: every jnp op of the
    expand and the fingerprint dispatched on its own. The reference."""
    init = np.asarray(model.init_states(), dtype=np.uint32)
    ilo, ihi = state_fingerprint(model, jnp.asarray(init))
    init_fps = pack_fp(np.asarray(ilo), np.asarray(ihi))
    rows = np.nonzero(init_fps == np.uint64(chain[0]))[0]
    if len(rows) == 0:
        raise RuntimeError("no init state")
    cur_row = init[rows[0]]
    pairs = []
    for next_fp in chain[1:]:
        succs, valid = model.expand(jnp.asarray(cur_row[None]))
        slo, shi = state_fingerprint(model, succs[0])
        sfps = pack_fp(np.asarray(slo), np.asarray(shi))
        succs = np.asarray(succs)[0]
        valid = np.asarray(valid)[0]
        hits = np.nonzero(valid & (sfps == np.uint64(next_fp)))[0]
        if len(hits) == 0:
            raise RuntimeError("no step")
        a = int(hits[0])
        pairs.append((model.decode(cur_row), model.action_label(cur_row, a)))
        cur_row = succs[a]
    pairs.append((model.decode(cur_row), None))
    return Path(pairs)


def _chain(parent_map: dict, fp: int) -> list:
    chain = []
    while fp:
        chain.append(fp)
        fp = parent_map.get(fp, 0)
    return chain[::-1]


def _paxos2():
    from stateright_tpu.tensor.paxos import TensorPaxos

    return TensorPaxos(client_count=2), 2048, 16, HasDiscoveries.ANY


def _2pc4():
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    return TensorTwoPhaseSys(4), 512, 14, HasDiscoveries.ALL


def _increment_sym():
    from stateright_tpu.tensor.models import TensorIncrement

    return TensorIncrement(2, symmetry=True), 64, 10, HasDiscoveries.ALL


def _lowered_ping_pong():
    from stateright_tpu.actor import Network
    from stateright_tpu.actor.model import LossyNetwork
    from stateright_tpu.actor.test_util import PingPongCfg
    from stateright_tpu.tensor import TensorProperty
    from stateright_tpu.tensor.lowering import lower_actor_model

    model = (
        PingPongCfg(max_nat=5, maintains_history=False)
        .into_model()
        .with_init_network(Network.new_unordered_nonduplicating())
        .with_lossy_network(LossyNetwork.NO)
    )

    def properties(view):
        counters = view.actor_feature(lambda i, s: s)
        return [
            TensorProperty.sometimes(
                "can reach max", lambda m, s: (counters(s) == 5).any(1)
            ),
        ]

    lowered = lower_actor_model(
        model, local_boundary=lambda i, s: s <= 5, properties=properties
    )
    assert lowered.dyn_tables()
    return lowered, 64, 10, HasDiscoveries.ALL


@pytest.mark.parametrize(
    "build",
    [_paxos2, _2pc4, _increment_sym, _lowered_ping_pong],
    ids=["paxos-2", "2pc-4", "increment-sym", "lowered-ping-pong"],
)
def test_compiled_replay_matches_eager_replay(build):
    model, batch, table_log2, finish = build()
    rs = ResidentSearch(model, batch_size=batch, table_log2=table_log2)
    r = rs.run(finish_when=finish)
    assert r.discoveries
    parent_map = rs.build_parent_map()
    for name, fp in r.discoveries.items():
        chain = _chain(parent_map, fp)
        assert len(chain) > 1, name
        compiled = rs.reconstruct_path(fp)
        assert compiled.into_pairs() == _eager_replay(
            model, chain).into_pairs(), name
    assert rs.metrics()["replay_traces_total"] == 1


def test_replay_traces_once_per_model_instance():
    """The replay traces at most once per model instance: once per model
    definition, so a second instance of one definition traces nothing."""

    def check(model):
        c = model.checker().spawn_tpu(batch_size=512, table_log2=14).join()
        paths = c.discoveries()
        assert set(paths) == {"abort agreement", "commit agreement"}
        return c._search.metrics(), paths

    m1, paths = check(TensorTwoPhaseSys(4))
    assert m1["replay_traces_total"] == 1
    assert m1["replay_steps_total"] == sum(len(p) - 1 for p in paths.values())
    model2 = TensorTwoPhaseSys(4)
    m2, paths2 = check(model2)
    assert m2["replay_traces_total"] == 0
    assert {k: p.into_pairs() for k, p in paths2.items()} == {
        k: p.into_pairs() for k, p in paths.items()}

    # A fingerprint that is no successor still fails the same way, and a
    # reused program does not retrace.
    init = np.asarray(model2.init_states(), dtype=np.uint32)
    lo, hi = state_fingerprint(model2, jnp.asarray(init))
    start = int(pack_fp(np.asarray(lo), np.asarray(hi))[0])
    traces = replay_traces(model2)
    with pytest.raises(RuntimeError, match="failed to reconstruct a step"):
        replay_fp_chain(model2, [start, 12345])
    with pytest.raises(RuntimeError, match="failed to reconstruct init"):
        replay_fp_chain(model2, [12345])
    assert replay_traces(model2) == traces


class _Unnamed(TensorTwoPhaseSys):
    """A 2PC model that names no definition (a subclass that does not
    override `definition_key()`): its replay programs are its own."""


def test_replay_programs_do_not_keep_the_model_alive():
    def replay_init(model):
        init = np.asarray(model.init_states(), dtype=np.uint32)
        lo, hi = state_fingerprint(model, jnp.asarray(init))
        return replay_fp_chain(model, [int(pack_fp(np.asarray(lo),
                                                   np.asarray(hi))[0])])

    # A named definition's programs outlive the instance that traced them.
    model = TensorTwoPhaseSys(3)
    assert len(replay_init(model)) == 1
    ref = weakref.ref(model)
    del model
    gc.collect()
    assert ref() is None
    assert len(replay_init(TensorTwoPhaseSys(3))) == 1
    # An unnamed model's programs go with it.
    model = _Unnamed(3)
    assert len(replay_init(model)) == 1
    key = id(model)
    assert key in _REPLAYS
    del model
    gc.collect()
    assert key not in _REPLAYS
