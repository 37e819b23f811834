"""Chaos plane + self-healing supervisor (stateright_tpu/faults/).

The contract under test is CRASH-ONLY RECOVERY: for every fault class the
seeded FaultPlan can inject (device OOM, XLA error, mid-chunk preemption,
spill-tier I/O error, torn checkpoint write, hang, one-shard failure,
poison service job), a supervised run must converge with discoveries and
state counts BIT-IDENTICAL to the fault-free golden, and the recovery
counters in `detail["faults"]` must account for every injected fault.

Speed discipline (tier-1 is timeout-bound): everything runs on 2pc-3-scale
models with deterministic seeds, zero backoff, and no sleeps beyond the
watchdog test's sub-second hang gate.
"""

import os

import numpy as np
import pytest

from stateright_tpu.faults import (
    CheckpointCorrupt,
    FaultPlan,
    SupervisorConfig,
    active,
    atomic_savez,
    load_latest,
    read_verified,
    run_supervised,
)
from stateright_tpu.faults.ckptio import _corrupt_file, normalize_ckpt_path
from stateright_tpu.tensor.frontier import FrontierSearch
from stateright_tpu.tensor.models import (
    TensorIncrementLock,
    TensorTwoPhaseSys,
)

GOLD = (1_146, 288)  # 2pc-3 generated/unique (ref examples/2pc.rs:153-159)
GOLD_INCLOCK4 = (257, 257)

M3 = TensorTwoPhaseSys(3)

# Zero-backoff, small-slice supervisor config: every test stays fast and
# deterministic.
CFG = SupervisorConfig(backoff_base_s=0.0, checkpoint_every_steps=3, seed=7)

# Small tiered config (288 uniques overflow a 2^9 table at high_water 0.5),
# so the spill/resolve fault boundaries genuinely execute.
TIERED = dict(
    batch_size=16, table_log2=9,
    store="tiered", high_water=0.5, summary_log2=12,
)


def golden_discoveries():
    global _GOLD_DISC
    if _GOLD_DISC is None:
        r = FrontierSearch(M3, batch_size=64, table_log2=12).run()
        _GOLD_DISC = dict(r.discoveries)
    return _GOLD_DISC


_GOLD_DISC = None


def assert_golden(result, faults_expected: int):
    f = result.detail["faults"]
    assert (result.state_count, result.unique_state_count) == GOLD, result
    assert result.discoveries == golden_discoveries(), result.discoveries
    assert f["injected_total"] == faults_expected, f
    return f


# -- plan unit layer -----------------------------------------------------------


def test_fault_plan_env_roundtrip():
    spec = (
        "seed=7;engine.step:oom:times=2;store.spill:io:after=1;"
        "service.step:poison:times=-1:job=3"
    )
    plan = FaultPlan.from_env(spec)
    assert plan.seed == 7
    assert len(plan.rules) == 3
    assert plan.rules[0].kind == "oom" and plan.rules[0].times == 2
    assert plan.rules[1].after == 1
    assert plan.rules[2].times == -1 and plan.rules[2].match == {"job": 3}
    # spec() re-serializes in the same grammar (replay currency).
    assert FaultPlan.from_env(plan.spec()).spec() == plan.spec()
    assert FaultPlan.from_env("") is None
    assert FaultPlan.from_env("   ") is None
    with pytest.raises(ValueError):
        FaultPlan.from_env("engine.step:bogus_kind")


def test_fault_plan_fires_deterministically():
    from stateright_tpu.faults import DeviceOOM

    plan = FaultPlan().rule("engine.step", "oom", after=1, times=2)
    plan.fire("engine.step", {})  # hit 1: skipped (after=1)
    with pytest.raises(DeviceOOM):
        plan.fire("engine.step", {})  # hit 2: fires
    with pytest.raises(DeviceOOM):
        plan.fire("engine.step", {})  # hit 3: fires (times=2)
    plan.fire("engine.step", {})  # hit 4: exhausted
    assert plan.injected == {"engine.step:oom": 2}
    # Context match filter: fires only when the batch reports the job.
    plan2 = FaultPlan().rule("service.step", "poison", match={"job": 9})
    plan2.fire("service.step", {"job": [1, 2]})  # no match
    with pytest.raises(Exception):
        plan2.fire("service.step", {"job": [9, 2]})


def test_maybe_fault_is_noop_without_plan():
    from stateright_tpu.faults import active_plan, maybe_fault

    assert active_plan() is None
    maybe_fault("engine.step")  # must be free and silent


# -- atomic checkpoint I/O -----------------------------------------------------


def test_atomic_savez_crc_roundtrip_and_torn_fallback(tmp_path):
    path = str(tmp_path / "ck.npz")
    atomic_savez(path, {"a": np.arange(5), "gen": np.asarray([1])})
    data = read_verified(path)
    assert list(data["a"]) == [0, 1, 2, 3, 4]
    # Second generation rotates the first to .prev.
    atomic_savez(path, {"a": np.arange(5), "gen": np.asarray([2])})
    assert os.path.exists(path + ".prev")
    # Corrupt the CURRENT generation both ways the injector simulates:
    # truncation (even seed) and a bit flip (odd seed).
    for seed in (0, 1):
        atomic_savez(path, {"a": np.arange(5), "gen": np.asarray([3 + seed])})
        _corrupt_file(path, seed)
        with pytest.raises(CheckpointCorrupt):
            read_verified(path)
        served, src = load_latest(path)
        assert src == path + ".prev"  # fell back to the previous good one
        assert int(served["gen"][0]) in (2, 3)
    # Both generations corrupt -> a named, actionable error.
    _corrupt_file(path + ".prev", 1)
    with pytest.raises(CheckpointCorrupt, match="no intact checkpoint"):
        load_latest(path)


def test_ckpt_write_torn_injection_consumed_by_writer(tmp_path):
    path = str(tmp_path / "t.npz")
    plan = FaultPlan(seed=1).rule("ckpt.write", "torn", times=1)
    with active(plan):
        atomic_savez(path, {"x": np.zeros(3)})  # corrupted post-write
        with pytest.raises(CheckpointCorrupt):
            read_verified(path)
        atomic_savez(path, {"x": np.ones(3)})  # rule exhausted: clean
    assert plan.injected == {"ckpt.write:torn": 1}
    data, src = load_latest(path)
    assert src == normalize_ckpt_path(path)
    assert data["x"].sum() == 3


def test_cross_process_load_latest_mid_write_falls_back_to_prev(tmp_path):
    # The fleet requeue sequence, exactly: replica A dies mid-checkpoint
    # (current generation torn, a torn `.tmp` left behind), and a SECOND
    # process — the router placing the job on replica B — calls
    # `load_latest` on the path. It must serve `.prev` (the last verified
    # generation), unaffected by process-local state like the
    # _WRITTEN_INTACT rotation cache.
    import subprocess
    import sys

    path = str(tmp_path / "fleetjob1.npz")
    atomic_savez(path, {"gen": np.asarray([1])})  # verified generation
    atomic_savez(path, {"gen": np.asarray([2])})  # gen 1 rotates to .prev
    _corrupt_file(path, seed=0)  # gen 2 torn mid-write
    with open(path + ".tmp", "wb") as f:  # srlint: ckpt-ok simulated torn tmp fixture, not a checkpoint write
        f.write(b"torn half-written next generation")
    code = (
        "import sys\n"
        "from stateright_tpu.faults.ckptio import load_latest\n"
        f"data, src = load_latest({path!r})\n"
        f"assert src == {path + '.prev'!r}, src\n"
        "assert int(data['gen'][0]) == 1, data['gen']\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_frontier_checkpoint_torn_file_falls_back_to_prev(tmp_path):
    # The satellite bugfix pin: a partial write must not poison resume.
    ck = str(tmp_path / "f.npz")
    fs = FrontierSearch(M3, batch_size=64, table_log2=12)
    fs.run(max_steps=2)
    fs.checkpoint(ck)  # generation 1
    fs.run(max_steps=2)
    fs.checkpoint(ck)  # generation 2 (gen 1 rotates to .prev)
    _corrupt_file(ck, seed=0)  # tear the CURRENT generation
    resumed = FrontierSearch.load_checkpoint(M3, ck, batch_size=64)
    r = resumed.run()
    # Resumed from the PREVIOUS generation (2 steps in) and still exact.
    assert (r.state_count, r.unique_state_count) == GOLD


# -- supervised fault matrix ---------------------------------------------------


def test_supervised_no_plan_matches_plain_run(tmp_path):
    r = run_supervised(
        M3, engine="frontier", plan=None, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=64, table_log2=12),
    )
    f = assert_golden(r, faults_expected=0)
    assert f["retries"] == 0 and f["restores"] == 0
    assert f["checkpoint_generations"] >= 1
    assert r.complete


def test_supervised_oom_and_xla_faults_bit_identical(tmp_path):
    plan = (
        FaultPlan(seed=3)
        .rule("engine.step", "oom", after=2)
        .rule("engine.step", "xla", after=5)
    )
    r = run_supervised(
        M3, engine="frontier", plan=plan, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=64, table_log2=12),
    )
    f = assert_golden(r, faults_expected=2)
    assert f["injected"] == {
        "engine.step:oom": 1, "engine.step:xla": 1,
    }
    assert f["retries"] == 2


def test_supervised_torn_checkpoint_recovers_from_prev_generation(tmp_path):
    # Corrupt the FIRST checkpoint generation, then fault late enough that
    # recovery must actually restore from a checkpoint: the supervisor
    # serves the newest intact generation.
    plan = (
        FaultPlan(seed=4)
        .rule("ckpt.write", "torn", times=1)
        .rule("engine.step", "oom", after=7)
    )
    r = run_supervised(
        M3, engine="frontier", plan=plan, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=64, table_log2=12),
    )
    f = assert_golden(r, faults_expected=2)
    assert f["restores"] >= 1  # recovery came from a checkpoint, not fresh


def test_supervised_tiered_spill_and_resolve_io_faults(tmp_path):
    plan = (
        FaultPlan(seed=5)
        .rule("store.spill", "io", times=1)
        .rule("store.resolve", "io", times=1)
    )
    r = run_supervised(
        M3, engine="frontier", plan=plan, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(TIERED),
    )
    f = assert_golden(r, faults_expected=2)
    assert f["retries"] == 2


def test_supervised_frontier_seed_phase_fault_recovers(tmp_path):
    # The r11 srlint SR004 find: frontier seeding runs device inserts
    # before the main loop and used to sit OFF the chaos plane. A fault
    # injected exactly at the seed boundary (phase match) must be retried
    # to golden parity like any step fault.
    plan = FaultPlan(seed=11).rule(
        "engine.step", "oom", match={"phase": "seed"},
    )
    r = run_supervised(
        M3, engine="frontier", plan=plan, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=64, table_log2=12),
    )
    f = assert_golden(r, faults_expected=1)
    assert f["injected"] == {"engine.step:oom": 1}
    assert f["retries"] == 1


def test_supervised_resident_tiered_service_fault_recovers(tmp_path):
    # The other r11 SR004 find: the resident engine's tiered host service
    # (queue compaction + suspect injection + eviction). The boundary sits
    # before any carry mutation, so an injected I/O fault there must be
    # cleanly retriable at golden parity.
    plan = FaultPlan(seed=12).rule("store.service", "io", times=1)
    r = run_supervised(
        M3, engine="resident", plan=plan, config=CFG,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(TIERED),
    )
    f = assert_golden(r, faults_expected=1)
    assert f["injected"] == {"store.service:io": 1}


def test_supervised_resident_preemption_and_watchdog_hang(tmp_path):
    # Mid-chunk preemption + an injected hang: the watchdog must convert
    # the hang into a retriable fault instead of waiting it out. The hang
    # fires at engine.step hit 2 — the second slice of the WARM first
    # build, so the 1 s watchdog deadline applies (compile_grace_s covers
    # only the first slice of each fresh build).
    plan = (
        FaultPlan(seed=6, hang_limit_s=20.0)
        .rule("engine.step", "hang", after=1, times=1)
        .rule("engine.chunk", "preempt", after=1)
    )
    cfg = SupervisorConfig(
        backoff_base_s=0.0, checkpoint_every_steps=4, seed=7,
        watchdog_s=1.0,
    )
    r = run_supervised(
        M3, engine="resident", plan=plan, config=cfg,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=64, table_log2=12),
    )
    f = assert_golden(r, faults_expected=2)
    assert f["watchdog_fired"] >= 1  # cancelled, not waited out
    assert "engine.step:hang" in f["injected"]


def test_supervised_sharded_one_shard_failure(tmp_path):
    # One shard's service transfer fails; the supervisor restores the whole
    # carry and the 2-chip result stays bit-identical. Per-shard 2^8 tables
    # at high_water 0.5 force real spill transfers at 2pc-3 scale (the
    # spill trigger lands at ~120 claims, under the ~144 uniques per
    # shard, so both shards genuinely evict).
    from stateright_tpu.parallel import make_mesh

    plan = FaultPlan(seed=9).rule(
        "shard.transfer", "shard", times=1, match={"shard": 1}
    )
    r = run_supervised(
        M3, engine="sharded", plan=plan,
        config=SupervisorConfig(
            backoff_base_s=0.0, checkpoint_every_steps=8, seed=7
        ),
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(
            mesh=make_mesh(2), batch_size=4, table_log2=8,
            store="tiered", high_water=0.5, summary_log2=12,
        ),
    )
    f = r.detail["faults"]
    assert (r.state_count, r.unique_state_count) == GOLD, r
    # Discovery WITNESSES are engine/batch-shape dependent (only counts are
    # engine-invariant), so bit-identicality is pinned against the same
    # engine + config run fault-free.
    from stateright_tpu.parallel.sharded import ShardedSearch

    golden = ShardedSearch(
        M3, mesh=make_mesh(2), batch_size=4, table_log2=8,
        store="tiered", high_water=0.5, summary_log2=12,
    ).run()
    assert r.discoveries == golden.discoveries, r.discoveries
    assert f["injected_total"] == 1, f
    assert f["injected"] == {"shard.transfer:shard": 1}


def test_degrade_ladder_escalates_and_is_recorded(tmp_path):
    # Enough consecutive failures walk the ladder: retry -> shrink_batch ->
    # tiered; the run still converges once the rule exhausts.
    plan = FaultPlan(seed=10).rule("engine.step", "oom", times=5)
    cfg = SupervisorConfig(
        backoff_base_s=0.0, checkpoint_every_steps=3, retries_per_rung=2,
        max_retries=10, seed=7,
    )
    r = run_supervised(
        M3, engine="frontier", plan=plan, config=cfg,
        checkpoint_path=str(tmp_path / "ck.npz"),
        engine_kwargs=dict(batch_size=128, table_log2=12),
    )
    f = assert_golden(r, faults_expected=5)
    assert f["degrade_steps"] >= 1
    assert 1 <= f["degrade_rung"] <= 2


def test_supervisor_gives_up_past_fault_budget(tmp_path):
    from stateright_tpu.faults import SupervisorGaveUp

    plan = FaultPlan(seed=11).rule("engine.step", "oom", times=-1)
    cfg = SupervisorConfig(
        backoff_base_s=0.0, checkpoint_every_steps=3, max_retries=3, seed=7,
    )
    with pytest.raises(SupervisorGaveUp):
        run_supervised(
            M3, engine="frontier", plan=plan, config=cfg,
            engine_kwargs=dict(batch_size=64, table_log2=12),
        )


# -- service hardening ---------------------------------------------------------


def test_service_poison_job_quarantined_group_and_service_survive():
    # The _fail_all blast-radius fix, pinned: a poison job is quarantined
    # after the retry budget; its SAME-GROUP sibling and an unrelated group
    # both finish bit-identical.
    from stateright_tpu.service import CheckService

    m3 = TensorTwoPhaseSys(3)
    mi = TensorIncrementLock(4)
    svc = CheckService(
        batch_size=256, table_log2=17, background=False, retry_limit=1
    )
    h_ok = svc.submit(m3)
    h_poison = svc.submit(m3)  # same model instance: same group
    h_other = svc.submit(mi)  # unrelated group
    plan = FaultPlan().rule(
        "service.step", "poison", times=-1, match={"job": h_poison.id}
    )
    with active(plan):
        svc.drain(timeout=300)
    r_ok, r_other = h_ok.result(), h_other.result()
    assert (r_ok.state_count, r_ok.unique_state_count) == GOLD
    assert (
        r_other.state_count, r_other.unique_state_count
    ) == GOLD_INCLOCK4
    poison = svc.poll(h_poison.id)
    assert poison["status"] == "error" and poison["quarantined"]
    faults = svc.stats()["faults"]
    assert faults["quarantined_jobs"] == 1
    assert faults["retries"] >= 1
    # Completed results carry the engine's fault counters under the
    # documented schema key.
    assert r_ok.detail["faults"]["quarantined_jobs"] == 1
    svc.close()


def test_service_transient_step_fault_retries_exactly():
    # A fault that stops (times=2) never reaches quarantine: the pushed-back
    # lanes retry exactly and every job completes bit-identical.
    from stateright_tpu.service import CheckService

    m3 = TensorTwoPhaseSys(3)
    svc = CheckService(
        batch_size=256, table_log2=17, background=False, retry_limit=3
    )
    h1, h2 = svc.submit(m3), svc.submit(m3)
    plan = FaultPlan().rule("service.step", "xla", after=1, times=2)
    with active(plan):
        svc.drain(timeout=300)
    for h in (h1, h2):
        r = h.result()
        assert (r.state_count, r.unique_state_count) == GOLD
    faults = svc.stats()["faults"]
    assert faults["step_faults"] == 2
    assert faults["retries"] == 2
    assert faults["quarantined_jobs"] == 0
    svc.close()


def test_service_http_fault_degrades_to_503():
    import json
    import urllib.error
    import urllib.request

    from stateright_tpu.service import CheckService, serve_service

    svc = CheckService(batch_size=64, table_log2=12, background=False)
    server = serve_service(svc, address="localhost:0")
    port = server.httpd.server_address[1]
    plan = FaultPlan().rule("service.http", "http", times=1)
    try:
        with active(plan):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://localhost:{port}/.status", timeout=10
                )
            assert exc.value.code == 503
            # The front end survives its own fault.
            with urllib.request.urlopen(
                f"http://localhost:{port}/.status", timeout=10
            ) as resp:
                assert resp.status == 200
                assert "faults" in json.load(resp)
    finally:
        server.shutdown()
        svc.close()


def test_push_front_preserves_pop_order():
    # The exactly-retriable unwind contract: lanes taken by a faulted step
    # go back to the FRONT, so the retry pops the identical order.
    from stateright_tpu.service.queue import Job

    job = Job(1, M3)
    P = 0
    mk = lambda a, b: (  # noqa: E731
        np.arange(a, b, dtype=np.uint32).reshape(-1, 1),
        np.arange(a, b, dtype=np.uint32),
        np.arange(a, b, dtype=np.uint32),
        np.zeros((b - a, P), dtype=bool),
        np.ones(b - a, dtype=np.uint32),
    )
    job.push(*mk(1, 6))
    job.push(*mk(6, 9))
    taken = job.take(4)
    assert list(taken[1]) == [1, 2, 3, 4]
    job.push_front(*taken)
    again = job.take(8)
    assert list(again[1]) == [1, 2, 3, 4, 5, 6, 7, 8]


# -- schema --------------------------------------------------------------------


def test_faults_detail_schema_is_documented():
    from stateright_tpu.obs.schema import (
        DETAIL_KEYS,
        FAULTS_DETAIL_KEYS,
        validate_detail,
    )

    assert "faults" in DETAIL_KEYS
    for key in (
        "injected_total", "injected", "retries", "backoff_ms",
        "degrade_steps", "checkpoint_generations", "restores",
        "watchdog_fired", "quarantined_jobs", "step_faults",
    ):
        assert key in FAULTS_DETAIL_KEYS
    detail = {
        "faults": {
            "injected_total": 2,
            "injected": {"engine.step:oom": 2},
            "retries": 2,
        }
    }
    assert validate_detail(detail) == []
    detail["faults"]["renamed_counter"] = 1
    assert validate_detail(detail) == ["faults.renamed_counter"]
