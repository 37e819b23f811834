"""`spawn_tpu(chips=N)`: a check on N chips through the multi-chip engine
(parallel/sharded.py), on the virtual CPU mesh (conftest: 8 devices).

Its answers are the host BFS's (checker/bfs.py, the plain reference) and
one-chip `spawn_tpu`'s: counts, depth, the set of discoveries, and paths
that re-execute as discoveries at the host's least depth. The options the
multi-chip engine cannot serve are refused with a plain error."""

import functools

import pytest

from stateright_tpu.examples.paxos import PaxosModelCfg
from stateright_tpu.examples.two_phase_commit import TwoPhaseSys
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.paxos import TensorPaxos

#: name -> (tensor model, host model, batch_size, table_log2 a chip)
MODELS = {
    "2pc-4": (lambda: TensorTwoPhaseSys(4), lambda: TwoPhaseSys(4), 64, 12),
    "2pc-5": (lambda: TensorTwoPhaseSys(5), lambda: TwoPhaseSys(5), 128, 13),
    "paxos-2": (
        lambda: TensorPaxos(client_count=2),
        lambda: PaxosModelCfg(client_count=2, server_count=3).into_model(),
        256,
        14,
    ),
}


def _answer(checker) -> tuple:
    """(generated, unique, depth, {property: path length})."""
    return (
        checker.state_count(),
        checker.unique_state_count(),
        checker.max_depth(),
        {k: len(p.into_pairs()) for k, p in checker.discoveries().items()},
    )


@functools.lru_cache(maxsize=None)
def _host(name: str, depth=None) -> tuple:
    b = MODELS[name][1]().checker()
    if depth is not None:
        b = b.target_max_depth(depth)
    return _answer(b.spawn_bfs().join())


@functools.lru_cache(maxsize=None)
def _one_chip(name: str) -> tuple:
    make, _, batch, log2 = MODELS[name]
    # One chip holds what four chips' tables hold between them.
    return _answer(
        make().checker().spawn_tpu(batch_size=batch, table_log2=log2 + 2)
        .join()
    )


@pytest.mark.parametrize("chips", [2, 4])
@pytest.mark.parametrize("name", list(MODELS))
def test_sharded_check_answers_as_host_bfs_and_one_chip(name, chips):
    make, _, batch, log2 = MODELS[name]
    c = make().checker().spawn_tpu(
        batch_size=batch, table_log2=log2, chips=chips
    ).join()
    assert c._search.n_chips == chips
    got = _answer(c)
    assert got == _host(name) == _one_chip(name)
    # Every path re-executes as a discovery of its property (and the
    # lengths above are the host BFS's least depths).
    for prop, path in c.discoveries().items():
        c.assert_discovery(prop, path.actions())


@pytest.mark.parametrize("name", list(MODELS))
def test_sharded_depth_bounded_check_counts_as_host_bfs(name):
    make, _, batch, log2 = MODELS[name]
    c = make().checker().target_max_depth(10).spawn_tpu(
        batch_size=batch, table_log2=log2, chips=4
    ).join()
    assert _answer(c)[:3] == _host(name, 10)[:3]
    assert set(c.discoveries()) == set(_host(name, 10)[3])


def test_sharded_spawn_refuses_what_the_engine_cannot_serve():
    import jax

    from stateright_tpu.core.visitor import PathRecorder

    model = TensorTwoPhaseSys(3)
    with pytest.raises(NotImplementedError, match="drop chips"):
        model.checker().visitor(PathRecorder()).spawn_tpu(
            batch_size=64, table_log2=12, chips=2
        )
    with pytest.raises(ValueError, match="resident=False"):
        model.checker().spawn_tpu(
            batch_size=64, table_log2=12, chips=2, resident=False
        )
    with pytest.raises(ValueError, match="devices"):
        model.checker().spawn_tpu(
            batch_size=64, table_log2=12, chips=len(jax.devices()) + 1
        )
    with pytest.raises(ValueError, match="table_layout"):
        model.checker().spawn_tpu(
            batch_size=64, table_log2=12, chips=2, table_layout="kv"
        )
    with pytest.raises(ValueError, match="at least 1"):
        model.checker().spawn_tpu(batch_size=64, table_log2=12, chips=0)


def test_one_chip_is_the_resident_engine():
    from stateright_tpu.tensor.resident import ResidentSearch

    c = TensorTwoPhaseSys(3).checker().spawn_tpu(
        batch_size=64, table_log2=12, chips=1
    ).join()
    assert isinstance(c._search, ResidentSearch)
    assert c.unique_state_count() == 288
