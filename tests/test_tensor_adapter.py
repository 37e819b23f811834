"""Device models behind host facilities: the `as_host_model` adapter serves
TensorModels to the Explorer / on-demand checker / host checkers, and the
engines' `dump_states` hook gives the reference's StateRecorder-style exact
state-set assertions (ref: src/checker/visitor.rs:75-111,
src/checker/explorer.rs:224-320) against device searches."""

import json
import urllib.request

from stateright_tpu.core.visitor import StateRecorder
from stateright_tpu.explorer.server import serve, states_view
from stateright_tpu.tensor import FrontierSearch, as_host_model
from stateright_tpu.tensor.models import TensorTwoPhaseSys
from stateright_tpu.tensor.resident import ResidentSearch


def test_adapter_host_bfs_matches_device_counts():
    # The host BFS checker drives the tensor model row-by-row through the
    # adapter — full cross-validation of expand/within_boundary against the
    # batched device search.
    host = as_host_model(TensorTwoPhaseSys(3)).checker().spawn_bfs().join()
    dev = FrontierSearch(TensorTwoPhaseSys(3), 512, 16).run()
    assert host.unique_state_count() == dev.unique_state_count == 288
    assert host.state_count() == dev.state_count
    assert set(host.discoveries()) == set(dev.discoveries)


def test_explorer_views_over_tensor_model():
    m = as_host_model(TensorTwoPhaseSys(3))
    init = states_view(m, [])
    assert len(init) == 1
    assert not init[0]["ignored"]
    # Decoded, human-readable state — not a u32 lane dump.
    assert "working" in init[0]["state"]
    assert {p["name"] for p in init[0]["properties"]} == {
        "commit agreement", "abort agreement", "consistent",
    }
    from stateright_tpu.core.fingerprint import fingerprint

    fp = int(init[0]["fingerprint"])
    nxt = states_view(m, [fp])
    assert nxt  # successor views expand on device, one row per request
    live = [v for v in nxt if not v["ignored"]]
    assert live
    assert all(v["fingerprint"] is not None for v in live)


def test_on_demand_over_tensor_model_completes():
    checker = as_host_model(TensorTwoPhaseSys(3)).checker().spawn_on_demand()
    checker.run_to_completion()
    checker.join()
    assert checker.unique_state_count() == 288


def test_explorer_http_roundtrip_over_tensor_model():
    server = serve(
        as_host_model(TensorTwoPhaseSys(3)).checker(), "localhost:0"
    )
    try:
        port = server.httpd.server_address[1]
        with urllib.request.urlopen(
            f"http://localhost:{port}/.states/", timeout=10
        ) as r:
            views = json.loads(r.read())
        assert len(views) == 1 and "working" in views[0]["state"]
        with urllib.request.urlopen(
            f"http://localhost:{port}/.status", timeout=10
        ) as r:
            status = json.loads(r.read())
        assert status["model"]
    finally:
        server.shutdown()


def test_resident_dump_states_is_exact_state_set():
    rs = ResidentSearch(TensorTwoPhaseSys(3), 256, 14)
    r = rs.run(budget=4)
    assert r.complete
    dump = rs.dump_states(decode=False)
    assert len(dump) == len(set(dump)) == 288
    # Exact set parity with a host traversal of the same model.
    rec = StateRecorder()
    as_host_model(TensorTwoPhaseSys(3)).checker().visitor(rec).spawn_bfs().join()
    assert set(dump) == {tuple(int(x) for x in s) for s in rec.states}


def test_sharded_dump_states_union_over_shards():
    from stateright_tpu.parallel import ShardedSearch, make_mesh

    ss = ShardedSearch(
        TensorTwoPhaseSys(3), mesh=make_mesh(4), batch_size=64, table_log2=12
    )
    assert ss.run(budget=4).complete
    dump = ss.dump_states(decode=False)
    assert len(dump) == len(set(dump)) == 288


def test_spawn_tpu_accepts_state_recorder():
    rec = StateRecorder()
    checker = (
        TensorTwoPhaseSys(3)
        .checker()
        .visitor(rec)
        .spawn_tpu(batch_size=256, table_log2=14)
        .join()
    )
    assert checker.unique_state_count() == 288
    assert len(rec.states) == 288
    # Decoded protocol-level states, e.g. every RM working in some state.
    assert any("working" in repr(s) for s in rec.states)


def test_tpu_checker_path_recorder_visitor():
    """PathRecorder-style visitors on the TPU checker.
    Parity oracle: the host BFS with the same visitor on the same model
    (every evaluated state visited with a valid parent-pointer path)."""
    from stateright_tpu.core.visitor import PathRecorder
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    model = TensorTwoPhaseSys(3)
    rec = PathRecorder()
    c = model.checker().visitor(rec).spawn_tpu(batch_size=64, table_log2=12)
    c.join()
    assert c.unique_state_count() == 288
    assert len(rec.paths) == 288  # one path per evaluated unique state
    # Every path must replay: start at an init state, end at its own state,
    # and its action labels must be consistent (non-None except the last).
    lens = set()
    for p in rec.paths:
        pairs = list(p)
        assert pairs[-1][1] is None
        assert all(a is not None for _, a in pairs[:-1])
        lens.add(len(pairs))
    assert max(lens) == 11  # max_depth golden for 2pc-3


def test_spawn_tpu_passes_engine_options_through():
    c = (
        TensorTwoPhaseSys(3)
        .checker()
        .spawn_tpu(
            batch_size=64, table_log2=12,
            table_layout="kv", append="scatter",
        )
        .join()
    )
    assert c.unique_state_count() == 288
    import pytest

    # Resident-only knobs still require the resident engine...
    with pytest.raises(ValueError, match="resident"):
        TensorTwoPhaseSys(3).checker().spawn_tpu(
            batch_size=64, table_log2=12, resident=False, table_layout="kv"
        )
    # ...but insert_variant reaches the host-orchestrated engine too (round
    # 6: FrontierSearch races the same visited-set designs).
    c2 = (
        TensorTwoPhaseSys(3)
        .checker()
        .spawn_tpu(
            batch_size=64, table_log2=12,
            resident=False, insert_variant="capped",
        )
        .join()
    )
    assert c2.unique_state_count() == 288
