"""Multi-chip sharded-search tests on the virtual 8-device CPU mesh
(conftest sets xla_force_host_platform_device_count=8): count parity against
the reference goldens and the single-chip engines, discovery parity, path
reconstruction across table shards, and early-exit policies.

Marker budget: the tier-1 run is wall-clock-bounded, so the long-running
golden configs (2pc-5/2pc-7 at scale, refine_check end-to-end, the
multi-mesh and checkpoint round-trips — each 10-80s on the virtual mesh)
carry @pytest.mark.slow; fast representatives of every behavior (2pc-3
golden, path reconstruction, chunked-vs-single parity, suspend/resume,
overflow detection, early exits) stay in tier-1."""

import pytest

from stateright_tpu.core.discovery import HasDiscoveries
from stateright_tpu.parallel import ShardedSearch, make_mesh
from stateright_tpu.tensor.models import TensorLinearEquation, TensorTwoPhaseSys


def test_mesh_helper():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    with pytest.raises(ValueError):
        make_mesh(1000)


def test_2pc3_golden_on_8_chips():
    # ref golden: 288 unique states (examples/2pc.rs:153-154); the generated
    # count matches the host BFS total.
    r = ShardedSearch(
        TensorTwoPhaseSys(3), mesh=make_mesh(8), batch_size=64, table_log2=12
    ).run()
    assert r.unique_state_count == 288
    assert r.state_count == 1146
    assert set(r.discoveries) == {"abort agreement", "commit agreement"}
    assert r.complete


@pytest.mark.slow
def test_2pc5_golden_on_8_chips():
    # ref golden: 8,832 unique states (examples/2pc.rs:158-159).
    r = ShardedSearch(
        TensorTwoPhaseSys(5), mesh=make_mesh(8), batch_size=256, table_log2=14
    ).run()
    assert r.unique_state_count == 8832


@pytest.mark.slow
def test_mesh_size_independence():
    # The same search on 2, 4, and 8 chips produces identical totals — the
    # shard layout must not be observable in results.
    totals = set()
    for n in (2, 4, 8):
        r = ShardedSearch(
            TensorTwoPhaseSys(4), mesh=make_mesh(n), batch_size=128, table_log2=13
        ).run()
        totals.add((r.state_count, r.unique_state_count, r.max_depth))
    assert len(totals) == 1


def test_path_reconstruction_across_shards():
    s = ShardedSearch(
        TensorLinearEquation(2, 10, 14),
        mesh=make_mesh(8),
        batch_size=128,
        table_log2=14,
    )
    r = s.run()
    assert "solvable" in r.discoveries
    path = s.reconstruct_path(r.discoveries["solvable"])
    # BFS shortest counterexample, same depth and final state as the
    # host/single-chip engines (ref: src/checker/bfs.rs:455-476). Which
    # equal-length path is recorded depends on parent-insertion races,
    # exactly as in the reference's multithreaded checker (bfs.rs:243).
    assert sorted(path.actions()) == ["IncreaseX", "IncreaseX", "IncreaseY"]
    assert path.last_state() == (2, 1)


def test_finish_when_any_early_exit():
    r = ShardedSearch(
        TensorTwoPhaseSys(3), mesh=make_mesh(4), batch_size=64, table_log2=12
    ).run(finish_when=HasDiscoveries.ANY)
    assert len(r.discoveries) >= 1
    assert r.unique_state_count < 288


def test_target_state_count_early_exit():
    r = ShardedSearch(
        TensorLinearEquation(2, 4, 7),
        mesh=make_mesh(4),
        batch_size=64,
        table_log2=16,
    ).run(target_state_count=500)
    assert r.state_count >= 500
    assert not r.complete


def test_overflow_detected():
    with pytest.raises(RuntimeError, match="overflow"):
        ShardedSearch(
            TensorTwoPhaseSys(4), mesh=make_mesh(2), batch_size=64, table_log2=6
        ).run()


@pytest.mark.slow
def test_sharded_at_scale_2pc7():
    """Multi-chip search on a state space large enough to stress the
    all-to-all routing and per-chip tables:
    2PC-7 = 296,448 unique / 2,744,706 generated (computed by the compiled
    CPU baseline checker, cross-validated against the reference goldens at
    3/5 RMs). Also asserts the fingerprint sharding actually balances."""
    r = ShardedSearch(
        TensorTwoPhaseSys(7),
        mesh=make_mesh(),
        batch_size=1024,
        table_log2=17,
    ).run()
    assert r.unique_state_count == 296_448
    assert r.state_count == 2_744_706
    assert r.complete
    per_chip = r.detail["per_chip_unique"]
    assert len(per_chip) == 8
    # Balanced ownership: no chip more than 10% off the mean.
    mean = sum(per_chip) / len(per_chip)
    assert max(per_chip) <= 1.1 * mean and min(per_chip) >= 0.9 * mean, per_chip


# -- chunked dispatch / checkpoint-resume -------------------------------------


@pytest.mark.slow
def test_sharded_chunked_matches_single_dispatch():
    # Slow-marked (tier-1 870s budget): chunked-vs-single identity stays
    # fast-tier in test_resident_chunked_matches_single_dispatch, and
    # the sharded chunked golden in
    # test_sharded_donated_chunked_run_matches_goldens.
    full = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    ).run()
    chunked = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    ).run(budget=3)
    assert chunked.complete
    assert chunked.state_count == full.state_count
    assert chunked.unique_state_count == full.unique_state_count
    assert chunked.max_depth == full.max_depth
    assert chunked.discoveries == full.discoveries


@pytest.mark.slow
def test_sharded_suspend_resume_and_progress():
    full = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    ).run()
    ss = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    )
    partial = ss.run(max_steps=2, budget=1)
    assert not partial.complete
    assert partial.state_count < full.state_count
    seen = []
    resumed = ss.run(progress=lambda sc, uc, md: seen.append(sc))
    assert resumed.complete
    assert resumed.state_count == full.state_count
    assert resumed.unique_state_count == full.unique_state_count
    assert seen and seen[-1] == full.state_count


@pytest.mark.slow
def test_sharded_kill_and_resume_reproduces_exact_counts(tmp_path):
    full = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    ).run()
    ss = ShardedSearch(
        TensorTwoPhaseSys(4), mesh=make_mesh(4), batch_size=128, table_log2=13
    )
    assert not ss.run(max_steps=2, budget=1).complete
    ckpt = str(tmp_path / "sharded.npz")
    ss.checkpoint(ckpt)
    del ss

    resumed = ShardedSearch.load_checkpoint(
        TensorTwoPhaseSys(4), ckpt, mesh=make_mesh(4)
    )
    r = resumed.run()
    assert r.complete
    assert r.state_count == full.state_count
    assert r.unique_state_count == full.unique_state_count
    assert r.max_depth == full.max_depth
    assert set(r.discoveries) == set(full.discoveries)
    path = resumed.reconstruct_path(r.discoveries["commit agreement"])
    assert path.last_state() is not None


@pytest.mark.slow
def test_sharded_overflow_checkpoints_then_regrows(tmp_path):
    full = ShardedSearch(
        TensorTwoPhaseSys(5), mesh=make_mesh(4), batch_size=128, table_log2=14
    ).run()
    # 2pc-5 has 8,832 unique states; 4 chips x 2^9 slots must overflow.
    ss = ShardedSearch(
        TensorTwoPhaseSys(5), mesh=make_mesh(4), batch_size=128, table_log2=9
    )
    with pytest.raises(RuntimeError, match="checkpoint"):
        ss.run(budget=2)
    ckpt = str(tmp_path / "overflowed.npz")
    ss.checkpoint(ckpt)
    del ss

    grown = ShardedSearch.load_checkpoint(
        TensorTwoPhaseSys(5), ckpt, mesh=make_mesh(4), table_log2=14
    )
    r = grown.run()
    assert r.complete
    assert r.state_count == full.state_count
    assert r.unique_state_count == full.unique_state_count
    assert r.discoveries == full.discoveries


def test_sharded_chip_count_mismatch_rejected(tmp_path):
    ss = ShardedSearch(
        TensorTwoPhaseSys(3), mesh=make_mesh(4), batch_size=64, table_log2=12
    )
    ss.run(max_steps=1, budget=1)
    ckpt = str(tmp_path / "s.npz")
    ss.checkpoint(ckpt)
    with pytest.raises(ValueError, match="chips"):
        ShardedSearch.load_checkpoint(
            TensorTwoPhaseSys(3), ckpt, mesh=make_mesh(2)
        )


@pytest.mark.slow
def test_refine_check_over_sharded_engine():
    """Incremental closure refinement driven by the MULTI-CHIP engine: gaps
    surface from every shard's queue and the final run is poison-free."""
    from stateright_tpu.actor.test_util import PingPongCfg
    from stateright_tpu.tensor.lowering import refine_check

    def boundary(view):
        counters = view.actor_feature(lambda i, s: s)
        return lambda s: (counters(s) <= 3).all(1)

    cfg = PingPongCfg(max_nat=3, maintains_history=False)
    r, _ = refine_check(
        cfg.into_model().with_lossy_network(False),
        batch_size=32,
        table_log2=10,
        seed_states=2,
        boundary=boundary,
        engine="sharded",
        mesh=make_mesh(4),
    )
    host = (
        cfg.into_model().with_lossy_network(False).checker().spawn_bfs().join()
    )
    assert r.complete
    assert r.unique_state_count == host.unique_state_count() == 7
    assert r.state_count == host.state_count()


@pytest.mark.slow
def test_sharded_append_variants_identical_results():
    # The mesh-platform default picks scatter on CPU meshes; pin the DUS
    # variant explicitly so its slack/guard path (queue rows = S + N*C,
    # DUS start never clamps) is exercised on the virtual mesh too.
    runs = {
        v: ShardedSearch(
            TensorTwoPhaseSys(4),
            mesh=make_mesh(4),
            batch_size=128,
            table_log2=12,
            append=v,
        ).run()
        for v in ("scatter", "dus")
    }
    a, b = runs["scatter"], runs["dus"]
    assert (a.state_count, a.unique_state_count) == (8258, 1568)
    assert (a.state_count, a.unique_state_count) == (
        b.state_count,
        b.unique_state_count,
    )
    assert a.discoveries.keys() == b.discoveries.keys()
    assert a.complete and b.complete


@pytest.mark.slow
def test_sharded_lowered_paxos2_golden():
    """The multichip engine on a LOWERED actor model with
    a consistency tester — proves history/ebits lanes route correctly across
    chips via the all-to-all (not just plain dedup). Golden: 2-client Paxos,
    32,971 generated / 16,668 unique (ref: examples/paxos.rs:327,351)."""
    from stateright_tpu.actor.network import Network
    from stateright_tpu.actor.register import GetOk
    from stateright_tpu.examples.paxos import NULL_VALUE, PaxosModelCfg
    from stateright_tpu.tensor import TensorProperty
    from stateright_tpu.tensor.lowering import lower_actor_model

    cfg = PaxosModelCfg(
        client_count=2,
        server_count=3,
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

    lowered = lower_actor_model(
        cfg.into_model(), properties=properties, closure="exact"
    )
    r = ShardedSearch(
        lowered, mesh=make_mesh(8), batch_size=256, table_log2=16
    ).run()
    assert r.unique_state_count == 16668
    assert r.state_count == 32971
    assert set(r.discoveries) == {"value chosen"}  # linearizability holds
