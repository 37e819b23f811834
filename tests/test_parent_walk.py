"""The parent walk (tensor/parent_walk.py) against the parent map it
replaces in path reconstruction: for every visited fingerprint of a small
check, the chain the walk finds in the table where it lies equals the chain
through `SearchHost.build_parent_map()`, on every table layout, insert
variant and store, on one chip and on four (the virtual CPU mesh, conftest),
with the tables on the chips and on the host; and paths are rebuilt without
the parent map, by programs traced once a process."""

import numpy as np
import pytest

from stateright_tpu.parallel import ShardedSearch, make_mesh
from stateright_tpu.tensor import parent_walk
from stateright_tpu.tensor.frontier import map_chain, replay_path
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.paxos import TensorPaxos
from stateright_tpu.tensor.resident import ResidentSearch
from stateright_tpu.tensor.search_host import SearchHost


def _resident(model, batch, log2, **kw):
    def build():
        return ResidentSearch(model(), batch_size=batch, table_log2=log2, **kw)
    return build


#: id -> (engine builder, run() arguments)
CASES = {
    "paxos-2": (_resident(lambda: TensorPaxos(2), 256, 13), {
        "target_max_depth": 10}),
    "2pc-4": (_resident(lambda: TensorTwoPhaseSys(4), 64, 12), {}),
    "2pc-4-kv": (_resident(lambda: TensorTwoPhaseSys(4), 64, 12,
                           table_layout="kv"), {}),
    "2pc-4-capped": (_resident(lambda: TensorTwoPhaseSys(4), 64, 12,
                               insert_variant="capped"), {}),
    "2pc-4-pallas": (_resident(lambda: TensorTwoPhaseSys(4), 64, 11,
                               insert_variant="pallas"), {}),
    # 1,568 states in 2,048 slots: buckets overflow into their neighbours.
    "2pc-4-full-table": (_resident(lambda: TensorTwoPhaseSys(4), 64, 11), {}),
    "2pc-4-tiered": (_resident(lambda: TensorTwoPhaseSys(4), 32, 11,
                               store="tiered", high_water=0.6,
                               summary_log2=14), {}),
    "2pc-4-4-chips": (None, {}),
    "2pc-4-4-chips-chunked": (None, {"budget": 3}),
}


def _engine(case):
    build, run = CASES[case]
    if build is None:
        search = ShardedSearch(TensorTwoPhaseSys(4), make_mesh(4), 64, 12)
    else:
        search = build()
    return search, search.run(**run)


@pytest.mark.parametrize("case", list(CASES))
def test_walk_matches_the_parent_map(case):
    search, r = _engine(case)
    tables = search._last_tables
    if case.endswith("chunked"):
        assert all(type(t).__module__ == "numpy" for t in tables)
    if case.endswith("tiered"):
        assert r.detail["spilled_states"] > 0
    parent_map = search.build_parent_map()
    assert len(parent_map) == r.unique_state_count
    kv = search._kv_bucket()
    spill = {}
    for s in search._stores or ():
        spill.update(s.parent_map())
    for fp in parent_map:
        chain, dispatches = parent_walk.walk_parents(tables, fp, kv, spill)
        assert chain == map_chain(parent_map, fp), fp
        assert dispatches >= 1 or fp in spill
    # A fingerprint in no table ends its chain at once, as in the dict.
    absent = 0x1234_5678_9ABC_DEF1
    assert absent not in parent_map
    assert parent_walk.walk_parents(tables, absent, kv, spill)[0] == [absent]
    # The paths are the ones the parent map gives, step for step.
    assert r.discoveries
    for fp in r.discoveries.values():
        want = replay_path(search.model, map_chain(parent_map, fp)[::-1])
        assert search.reconstruct_path(fp).into_pairs() == want.into_pairs()


def test_a_chain_longer_than_the_buffer_takes_more_dispatches(monkeypatch):
    search, r = _engine("2pc-4")
    fp = r.discoveries["commit agreement"]
    tables = search._last_tables
    chain, dispatches = parent_walk.walk_parents(tables, fp, 0, {})
    assert len(chain) == 14 and dispatches == 1
    monkeypatch.setattr(parent_walk, "CHAIN", 4)
    assert parent_walk.walk_parents(tables, fp, 0, {}) == (chain, 4)
    host = tuple(np.asarray(t) for t in tables)
    assert parent_walk.walk_parents(host, fp, 0, {}) == (chain, 4)


@pytest.mark.parametrize("chips", [1, 4])
def test_discoveries_need_no_parent_map_and_trace_the_walk_once(
    chips, monkeypatch
):
    def check():
        c = TensorTwoPhaseSys(4).checker().spawn_tpu(
            batch_size=64, table_log2=12, chips=chips).join()
        paths = c.discoveries()
        walk = parent_walk.program_for(c._search._last_tables, 0)
        return c, {k: p.into_pairs() for k, p in paths.items()}, walk.traces

    want = check()[1]

    def no_map(self):
        raise AssertionError("the parent map was built")

    monkeypatch.setattr(SearchHost, "build_parent_map", no_map)
    c, paths, traces = check()
    assert paths == want
    assert set(paths) == {"abort agreement", "commit agreement"}
    assert traces == 1  # traced by the first check, none by the second
    m = c._search.metrics()
    assert m["parent_map_entries"] == 0
    assert m["parent_walk_steps_total"] == sum(len(p) for p in paths.values())
    assert m["parent_walk_dispatches_total"] == len(paths)
