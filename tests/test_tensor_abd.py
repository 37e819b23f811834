"""Count, discovery and successor parity of the device ABD register
(`tensor/abd.py` TensorAbd) against the host actor model
(`examples/abd.py`, ref: examples/linearizable-register.rs): 544 unique
states at 2 clients / 2 servers; the 2 client / 3 server space's depth
prefixes; the successor sets of seeded random reachable states, state by
state; decode and action labels along a discovery path; and one compiled
search for every instance of a definition."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.actor import Id
from stateright_tpu.actor.register import GetOk, Internal
from stateright_tpu.examples.abd import AbdModelCfg
from stateright_tpu.tensor.abd import TensorAbd

SMALL = dict(batch_size=256, table_log2=12)
PREFIX = dict(batch_size=1024, table_log2=16)


# -- the host state in the device decode's terms --------------------------------


def _envelope(env) -> tuple:
    msg = env.msg
    inner = msg.msg if isinstance(msg, Internal) else msg
    name = type(inner).__name__
    seq = getattr(inner, "seq", None)
    seq = None if seq is None else (seq[0], int(seq[1]))
    value = getattr(inner, "value", None)
    return (name, int(env.src), int(env.dst), inner.request_id, seq, value)


def _server(s, state) -> dict:
    a = state.state
    out = dict(seq=(a.seq[0], int(a.seq[1])), val=a.val, phase=0)
    ph = a.phase
    if ph is None:
        return out
    common = dict(request_id=ph.request_id, requester=int(ph.requester_id))
    if hasattr(ph, "responses"):
        out.update(phase=1, write=ph.write, **common, responses=sorted(
            (int(p), (sv[0][0], int(sv[0][1])), sv[1]) for p, sv in ph.responses))
    else:
        out.update(phase=2, read=ph.read, **common,
                   acks=sorted(int(p) for p in ph.acks))
    return out


def _clients(state, S, C) -> list:
    """Phase, ret and packed frontier of each client, as
    `RegisterClients.decode` gives them, from the client actors and the
    linearizability tester."""
    hist = state.history
    out = []
    for k in range(C):
        tid = Id(S + k)
        cs = state.actor_states[S + k]
        phase = {1: 0, 2: 1, 3: 2}[cs.op_count]
        done = hist.history_by_thread.get(tid, ())
        ret, last = 0, None
        if phase == 1:
            last = hist.in_flight_by_thread[tid][0]
        elif phase == 2:
            last, _op, read_ok = done[1]
            ret = ord(read_ok.value) - ord("A")
        frontier = 0
        if last is not None:
            counts = {int(t): i + 1 for t, i in last}
            for slot, peer in enumerate(j for j in range(C) if j != k):
                frontier |= counts.get(S + peer, 0) << (2 * slot)
        out.append(dict(phase=phase, ret=ret, frontier=frontier))
    return out


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


def host_view(state, S, C):
    servers = [_server(s, state.actor_states[s]) for s in range(S)]
    network = sorted(_envelope(e) for e in state.network.iter_all())
    return _freeze(dict(servers=servers, clients=_clients(state, S, C),
                        network=network))


def device_view(model, row):
    d = model.decode(row)
    d["network"] = sorted(d["network"])
    return _freeze(d)


def _host_successors(host, state):
    acts = []
    host.actions(state, acts)
    out = []
    for a in acts:
        n = host.next_state(state, a)
        if n is not None:
            out.append((a, n))
    return out


# -- whole checks ---------------------------------------------------------------


def test_abd_2x2_matches_the_host_checker():
    """The reference's test: 544 unique states at 2 clients / 2 servers;
    the device check agrees on every count and on the discoveries."""
    host = AbdModelCfg(2, 2).into_model().checker().spawn_bfs().join()
    dev = TensorAbd(2, 2).checker().spawn_tpu(**SMALL).join()
    assert dev.unique_state_count() == host.unique_state_count() == 544
    assert dev.state_count() == host.state_count() == 875
    assert dev.max_depth() == host.max_depth() == 25
    assert set(dev.discoveries()) == set(host.discoveries()) == {"value chosen"}


@pytest.mark.parametrize("depth,generated,unique", [
    (9, 2_435, 1_246),
    (12, 22_739, 9_302),
])
def test_abd_2x3_depth_prefix_matches_the_host_checker(depth, generated, unique):
    """`linearizable-register check 2` cut by depth: the counts the host
    checker gives (`AbdModelCfg(2, 3)...target_max_depth(d).spawn_bfs()`;
    depth 9 is rerun here, depth 12 takes the host 8 s)."""
    if depth == 9:
        host = (AbdModelCfg(2, 3).into_model().checker()
                .target_max_depth(depth).spawn_bfs().join())
        assert (host.state_count(), host.unique_state_count()) == (generated, unique)
    dev = (TensorAbd(2, 3).checker().target_max_depth(depth)
           .spawn_tpu(**PREFIX).join())
    assert (dev.state_count(), dev.unique_state_count()) == (generated, unique)
    assert dev.max_depth() == depth
    assert not dev.discoveries()  # "value chosen" first at depth 12's end


# -- state by state ----------------------------------------------------------------


@pytest.mark.parametrize("seed", [29, 2_900_000_003])
def test_random_states_have_the_host_successors(seed):
    """Random walks from the init state, the device row and the host state
    in lockstep: at every state the decoded device successors are the host
    model's `next_state` over its actions, as a multiset, and every
    property agrees with the host's."""
    S, C = 3, 2
    model = TensorAbd(C, S)
    host = AbdModelCfg(C, S).into_model()
    expand = jax.jit(model.expand)
    props = {p.name: jax.jit(lambda r, p=p: p.condition(model, r))
             for p in model.properties()}
    host_props = {p.name: p for p in host.properties()}
    rng = np.random.default_rng(seed)
    (h0,) = host.init_states()
    row0 = np.asarray(model.init_states())[0]
    assert device_view(model, row0) == host_view(h0, S, C)
    visited = 0
    for _walk in range(6):
        row, h = row0, h0
        for _step in range(40):
            succ, valid = expand(jnp.asarray(row[None]))
            succ, valid = np.asarray(succ)[0], np.asarray(valid)[0]
            dev = [device_view(model, r) for r in succ[valid]]
            hs = _host_successors(host, h)
            assert collections.Counter(dev) == collections.Counter(
                host_view(n, S, C) for _a, n in hs)
            for name in ("linearizable", "value chosen"):
                got = bool(np.asarray(props[name](jnp.asarray(row[None])))[0])
                assert got == bool(host_props[name].condition(host, h)), name
            visited += 1
            if not dev:
                break
            i = rng.integers(len(dev))
            row = succ[valid][i]
            (h,) = [n for _a, n in hs if host_view(n, S, C) == dev[i]][:1]
    assert visited > 100


def test_discovery_path_decodes_and_labels_the_host_steps():
    """The "value chosen" path of a device check, read back through
    `decode` and `action_label`: it starts at the host's init state, and
    each label names the host Deliver whose next state is the path's next
    decoded state."""
    S, C = 2, 2
    model = TensorAbd(C, S)
    host = AbdModelCfg(C, S).into_model()
    path = model.checker().spawn_tpu(**SMALL).join().discoveries()["value chosen"]
    pairs = path.into_pairs()
    assert len(pairs) == 12  # the host's witness: 11 deliveries
    (h,) = host.init_states()
    for state, action in pairs:
        assert _freeze(_sorted_network(state)) == host_view(h, S, C)
        if action is None:
            break
        (n,) = [n for a, n in _host_successors(host, h)
                if _label(a) == action]
        h = n
    assert any(v.msg.__class__ is GetOk
               for v in h.network.iter_all())


def _sorted_network(decoded):
    return dict(decoded, network=sorted(decoded["network"]))


def _label(action) -> str:
    """The host Deliver as `TensorAbd.action_label` writes it."""
    name, src, dst, req, seq, val = _envelope(action)
    args = ", ".join(repr(x) for x in (req, seq, val) if x is not None)
    return f"Deliver({src}->{dst}, {name}({args}))"


# -- one program per definition ---------------------------------------------------


def test_two_engines_of_one_definition_share_one_executable():
    first = TensorAbd(2, 2).checker().spawn_tpu(**SMALL).join()
    second = TensorAbd(2, 2).checker().spawn_tpu(**SMALL).join()
    m1, m2 = first._search.metrics(), second._search.metrics()
    assert m1["executable_cache_misses_total"] == 1
    assert m2["executable_cache_hits_total"] == 1
    assert m2["executable_cache_misses_total"] == 0
    assert (second.state_count(), second.unique_state_count()) == (875, 544)
    assert TensorAbd(2, 2).definition_key() == TensorAbd(2, 2).definition_key()
    assert TensorAbd(2, 2).definition_key() != TensorAbd(2, 3).definition_key()


def test_layout_bounds():
    m = TensorAbd()
    assert (m.client_count, m.server_count, m.pool_size) == (2, 3, 10)
    assert m.lanes == 14 and m.max_actions == 10
    assert m.V == 460
    assert m.trace_args() == dict(lanes=14, max_actions=10, vocabulary=460,
                                  lin_rows=len(m.clients.lin_phase),
                                  pool_size=10)
    with pytest.raises(ValueError):
        TensorAbd(2, 4)
    with pytest.raises(ValueError):
        TensorAbd(4, 3)
