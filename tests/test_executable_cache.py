"""One compiled search and one compiled path replay per model definition
(stateright_tpu/compile_cache.py): a check of a new model instance whose
definition and engine configuration an earlier check of the process had
takes the programs that check compiled, with the same answers, and any
difference in what the programs are traced from is a miss. A model that
names no definition compiles per engine and per instance. The caches keep
no engine and no model alive."""

import gc
import json
import weakref

import pytest

from stateright_tpu.compile_cache import EXECUTABLES, REPLAYS, ProgramCache
from stateright_tpu.core.discovery import HasDiscoveries
from stateright_tpu.obs import STAGES_COUNTER_KEYS
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.paxos import TensorPaxos

ZERO_STAGES = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}


def _check(model, trace_out=None, finish=None, **kw):
    """One check as a user writes it: (checker, the engine's metrics, the
    run's phases, its answer with the paths' pairs)."""
    b = model.checker()
    if finish is not None:
        b = b.finish_when(finish)
    if trace_out is not None:
        b = b.trace_out(trace_out)
    c = b.spawn_tpu(**kw).join()
    paths = c.discoveries()
    answer = (
        c.state_count(), c.unique_state_count(), c.max_depth(),
        {k: p.into_pairs() for k, p in paths.items()},
    )
    return c, c._search.metrics(), c._result.detail["phases"], answer


def _stage_spans(path) -> dict:
    by: dict = {}
    for e in json.load(open(path))["traceEvents"]:
        if e.get("ph") == "X":
            by.setdefault(e["name"], []).append(e)
    return by


@pytest.mark.parametrize(
    "make,kw",
    [
        (lambda: TensorTwoPhaseSys(4), dict(batch_size=256, table_log2=12)),
        (lambda: TensorPaxos(client_count=2),
         dict(batch_size=1024, table_log2=16)),
    ],
    ids=["2pc-4", "paxos-2"],
)
def test_second_check_of_a_definition_takes_the_compiled_programs(
    make, kw, tmp_path
):
    _, m1, p1, a1 = _check(make(), trace_out=str(tmp_path / "1.json"), **kw)
    assert m1["executable_cache_misses_total"] == 1
    assert m1["executable_cache_hits_total"] == 0
    assert m1["replay_traces_total"] == 1
    assert all(p1[k] > 0 for k in ZERO_STAGES)
    _, m2, p2, a2 = _check(make(), trace_out=str(tmp_path / "2.json"), **kw)
    assert a2 == a1
    assert m2["executable_cache_hits_total"] == 1
    assert m2["executable_cache_misses_total"] == 0
    assert m2["replay_traces_total"] == 0
    assert {k: p2[k] for k in ZERO_STAGES} == ZERO_STAGES
    assert p2["execute_s"] > 0
    assert set(m2) >= set(STAGES_COUNTER_KEYS)
    # The trace says so: the search span carries `cached`, and no stage
    # but execute runs inside it; no replay span traced.
    first, second = (_stage_spans(tmp_path / f"{i}.json") for i in (1, 2))
    assert [e["args"]["cached"] for e in first["resident.search"]] == [0]
    assert [e["args"]["cached"] for e in second["resident.search"]] == [1]
    assert not {"resident.trace", "resident.lower",
                "resident.compile"} & set(second)
    assert len(second["resident.execute"]) == 1
    assert sum(e["args"]["traced"] for e in second["path.replay"]) == 0


#: name -> (first model, first spawn args, second model, second spawn
#: args, second finish policy): the second differs from the first in one
#: component of what the search program is traced from.
MISSES = {
    "rm_count": (lambda: TensorTwoPhaseSys(3), {},
                 lambda: TensorTwoPhaseSys(4), {}, None),
    "symmetry": (lambda: TensorTwoPhaseSys(3), {},
                 lambda: TensorTwoPhaseSys(3, symmetry=True), {}, None),
    "client_count": (lambda: TensorPaxos(client_count=1), {},
                     lambda: TensorPaxos(client_count=2),
                     dict(table_log2=15), None),
    "batch_size": (lambda: TensorTwoPhaseSys(3), {},
                   lambda: TensorTwoPhaseSys(3), dict(batch_size=256), None),
    "table_log2": (lambda: TensorTwoPhaseSys(3), {},
                   lambda: TensorTwoPhaseSys(3), dict(table_log2=13), None),
    "insert_variant": (lambda: TensorTwoPhaseSys(3), {},
                       lambda: TensorTwoPhaseSys(3),
                       dict(insert_variant="capped"), None),
    "telemetry": (lambda: TensorTwoPhaseSys(3), {},
                  lambda: TensorTwoPhaseSys(3), dict(telemetry=False), None),
    "finish_when": (lambda: TensorTwoPhaseSys(3), {},
                    lambda: TensorTwoPhaseSys(3), {}, HasDiscoveries.ANY),
}


@pytest.mark.parametrize("name", list(MISSES))
def test_any_difference_in_what_the_program_reads_is_a_miss(name):
    make1, kw1, make2, kw2, finish = MISSES[name]
    base = dict(batch_size=128, table_log2=12)
    _check(make1(), **{**base, **kw1})
    _, m, p, _ = _check(make2(), finish=finish, **{**base, **kw2})
    assert m["executable_cache_misses_total"] == 1
    assert m["executable_cache_hits_total"] == 0
    assert all(p[k] > 0 for k in ZERO_STAGES)


def _lowered():
    from stateright_tpu.actor import Network
    from stateright_tpu.actor.model import LossyNetwork
    from stateright_tpu.actor.test_util import PingPongCfg
    from stateright_tpu.tensor import TensorProperty
    from stateright_tpu.tensor.lowering import lower_actor_model

    def properties(view):
        counters = view.actor_feature(lambda i, s: s)
        return [TensorProperty.sometimes(
            "can reach max", lambda m, s: (counters(s) == 5).any(1))]

    return lower_actor_model(
        PingPongCfg(max_nat=5, maintains_history=False)
        .into_model()
        .with_init_network(Network.new_unordered_nonduplicating())
        .with_lossy_network(LossyNetwork.NO),
        local_boundary=lambda i, s: s <= 5,
        properties=properties,
    )


class _Sub(TensorTwoPhaseSys):
    """A subclass names no definition unless it overrides the key."""


@pytest.mark.parametrize("make", [_lowered, lambda: _Sub(3)],
                         ids=["lowered", "subclass"])
def test_a_model_without_a_definition_compiles_per_engine(make):
    first, second = make(), make()
    assert first.definition_key() is None
    kw = dict(batch_size=64, table_log2=10)
    c1, m1, _, a1 = _check(first, **kw)
    c2, m2, _, a2 = _check(second, **kw)
    assert a1 == a2
    for m in (m1, m2):
        assert m["executable_cache_misses_total"] == 1
        assert m["executable_cache_hits_total"] == 0
        assert m["replay_traces_total"] == (1 if a1[3] else 0)
    assert len(EXECUTABLES) == len(REPLAYS) == 0
    # An engine's own second run still reuses its executable.
    r = c2._search.run()
    assert r.detail["phases"]["trace_s"] == 0.0
    assert c2._search.metrics()["executable_cache_hits_total"] == 1


def test_the_caches_keep_no_engine_and_no_model_alive():
    model = TensorTwoPhaseSys(3)
    c, _, _, answer = _check(model, batch_size=128, table_log2=12)
    assert answer[3]
    refs = [weakref.ref(model), weakref.ref(c._search), weakref.ref(c)]
    assert len(EXECUTABLES) == len(REPLAYS) == 1
    del model, c
    gc.collect()
    assert [r() for r in refs] == [None, None, None]
    # What they compiled still serves the next instance.
    _, m, _, again = _check(TensorTwoPhaseSys(3), batch_size=128,
                            table_log2=12)
    assert again == answer
    assert m["executable_cache_hits_total"] == 1
    assert m["replay_traces_total"] == 0


@pytest.mark.parametrize(
    "model,kw",
    [
        (lambda: TensorPaxos(client_count=2),
         dict(batch_size=1024, table_log2=16, finish=HasDiscoveries.ANY)),
        (lambda: TensorTwoPhaseSys(3),
         dict(batch_size=64, table_log2=12, chips=2)),
    ],
    ids=["resident", "sharded"],
)
def test_a_dropped_check_frees_its_engine_without_the_cyclic_collector(
    model, kw, tmp_path
):
    """A check's engine holds its tables and queue on the device, so it must
    go when the last reference to its checker goes, not when the cyclic
    collector next runs: nothing of a check refers back to its engine."""
    m = model()
    c, _, _, answer = _check(m, trace_out=str(tmp_path / "t.json"), **kw)
    assert answer[3]
    refs = [weakref.ref(m), weakref.ref(c._search), weakref.ref(c)]
    gc.collect()
    gc.disable()
    try:
        del m, c
        assert [r() for r in refs] == [None, None, None]
    finally:
        gc.enable()


def test_a_changed_jax_setting_is_another_program():
    import jax

    kw = dict(batch_size=128, table_log2=12)
    _, _, _, a1 = _check(TensorTwoPhaseSys(3), **kw)
    # Set for the process: the check may trace on a thread of its own.
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        _, m2, _, a2 = _check(TensorTwoPhaseSys(3), **kw)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    assert a2 == a1
    assert (m2["executable_cache_hits_total"],
            m2["executable_cache_misses_total"]) == (0, 1)
    _, m3, _, _ = _check(TensorTwoPhaseSys(3), **kw)
    assert m3["executable_cache_hits_total"] == 1


def test_sharded_check_of_a_definition_takes_the_compiled_program():
    kw = dict(batch_size=64, table_log2=12, chips=4)
    _, m1, _, a1 = _check(TensorTwoPhaseSys(4), **kw)
    _, m2, p2, a2 = _check(TensorTwoPhaseSys(4), **kw)
    assert a1 == a2 and a1[:2] == (8258, 1568)
    assert m1["executable_cache_misses_total"] == 1
    assert (m2["executable_cache_hits_total"],
            m2["executable_cache_misses_total"]) == (1, 0)
    assert {k: p2[k] for k in ZERO_STAGES} == ZERO_STAGES
    assert m2["replay_traces_total"] == 0
    # Another mesh is another program.
    _, m3, _, a3 = _check(TensorTwoPhaseSys(4), **{**kw, "chips": 2})
    assert a3[:2] == a1[:2]
    assert m3["executable_cache_misses_total"] == 1


def test_definition_key_names_class_code_and_fields():
    a, b = TensorTwoPhaseSys(3), TensorTwoPhaseSys(3)
    assert a.definition_key() == b.definition_key()
    assert hash(a.definition_key()) == hash(b.definition_key())
    assert TensorTwoPhaseSys(4).definition_key() != a.definition_key()
    assert (TensorTwoPhaseSys(3, symmetry="value").definition_key()
            != TensorTwoPhaseSys(3, symmetry=True).definition_key())
    assert TensorPaxos(2).definition_key() != TensorPaxos(3).definition_key()
    assert _Sub(3).definition_key() is None


def test_a_method_replaced_at_run_time_is_another_definition(monkeypatch):
    before = TensorTwoPhaseSys(3).definition_key()
    orig = TensorTwoPhaseSys.expand
    monkeypatch.setattr(TensorTwoPhaseSys, "expand",
                        lambda self, s: orig(self, s))
    assert TensorTwoPhaseSys(3).definition_key() != before
    m = TensorTwoPhaseSys(3)
    key = m.definition_key()
    m.properties = lambda: []
    assert m.definition_key() != key


def test_program_cache_is_a_bounded_lru():
    cache = ProgramCache(size=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == 1  # "b" is now the least recently used
    cache.put("c", 3)
    assert (cache.get("a"), cache.get("b"), cache.get("c")) == (1, None, 3)
    assert len(cache) == 2
    unbounded = ProgramCache(size=None)
    for i in range(100):
        unbounded.put(i, i)
    assert len(unbounded) == 100
    cache.clear()
    assert len(cache) == 0


def test_program_cache_stays_bounded_and_exact_under_threads():
    import sys
    import threading

    cache = ProgramCache(size=4)
    errors = []

    def worker(t):
        for i in range(2000):
            key = (t + i) % 16
            cache.put(key, ("program", key))
            got = cache.get(key)
            if got is not None and got != ("program", key):
                errors.append((key, got))
            if len(cache) > 4:
                errors.append(("size", len(cache)))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    assert errors == [] and len(cache) == 4
