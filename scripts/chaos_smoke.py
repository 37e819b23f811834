#!/usr/bin/env python
"""Chaos smoke: the anchor workload under a canned FaultPlan, end-to-end.

CI-shaped proof of the robustness subsystem (stateright_tpu/faults/) in one
command: a seeded plan injects every fault class — device OOM, XLA error,
mid-chunk preemption, spill-tier I/O error, torn checkpoint write, a hang
(watchdog-converted), a one-shard transfer failure, a poison service job,
and an HTTP-plane fault — and every run must still converge BIT-IDENTICAL
to the fault-free golden, with the recovery counters accounting for every
injected fault. Exit code 0 iff every check passes.

    JAX_PLATFORMS=cpu python scripts/chaos_smoke.py [--skip-sharded]

The replayable plan specs are printed for each scenario (paste one into
SR_TPU_FAULTS= to reproduce it against any entry point).
"""

import json
import os
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GOLD = (1_146, 288)  # 2pc-3 generated/unique (ref examples/2pc.rs:153-159)


def main(argv) -> int:
    from stateright_tpu.faults import (
        FaultPlan,
        SupervisorConfig,
        active,
        run_supervised,
    )
    from stateright_tpu.service import CheckService, serve_service
    from stateright_tpu.tensor.models import (
        TensorIncrementLock,
        TensorTwoPhaseSys,
    )

    failures = []

    def check(ok: bool, what: str):
        print(("ok   " if ok else "FAIL ") + what)
        if not ok:
            failures.append(what)

    outdir = tempfile.mkdtemp(prefix="chaos_smoke_")
    model = TensorTwoPhaseSys(3)
    cfg = SupervisorConfig(
        backoff_base_s=0.0, checkpoint_every_steps=6, watchdog_s=2.0,
        seed=7,
    )
    # Tiny tiered config: 288 uniques overflow a 2^9 table at high_water
    # 0.5, so the spill/resolve boundaries genuinely run.
    tiered = dict(
        batch_size=16, table_log2=9,
        store="tiered", high_water=0.5, summary_log2=12,
    )

    def supervised(name, engine, plan, engine_kwargs):
        ck = os.path.join(outdir, f"{name}.ckpt.npz")
        r = run_supervised(
            model, engine=engine, plan=plan, config=cfg,
            checkpoint_path=ck, engine_kwargs=engine_kwargs,
        )
        f = r.detail["faults"]
        got = (r.state_count, r.unique_state_count)
        print(f"     {name}: plan={plan.spec() if plan else None}")
        print(f"     {name}: counts={got} faults={json.dumps(f)}")
        check(got == GOLD, f"{name}: counts bit-identical to golden {GOLD}")
        want = sum(max(r_.times, 0) for r_ in plan.rules) if plan else 0
        check(
            f["injected_total"] == want,
            f"{name}: recovery counters account for all {want} injected "
            f"faults (got {f['injected_total']})",
        )
        return r

    # 1. fault-free golden parity (supervisor overhead path only). An
    # EMPTY plan, not None: None falls back to SR_TPU_FAULTS, and a
    # leftover env var must not contaminate the baseline.
    supervised("baseline", "frontier", FaultPlan(), dict(
        batch_size=64, table_log2=12,
    ))

    # 2. frontier: device OOM + XLA error + spill-tier I/O + resolve fault.
    plan = (
        FaultPlan(seed=7)
        .rule("engine.step", "oom", after=2)
        .rule("engine.step", "xla", after=6)
        .rule("store.spill", "io", times=1)
        .rule("store.resolve", "io", times=1)
    )
    supervised("frontier-chaos", "frontier", plan, dict(tiered))

    # 3. resident: mid-chunk preemption + torn checkpoint + OOM (the torn
    # generation must be absorbed by the .prev fallback on restore) + hang
    # (watchdog-converted).
    plan = (
        FaultPlan(seed=8)
        .rule("engine.chunk", "preempt", after=1)
        .rule("ckpt.write", "torn", times=1)
        .rule("engine.step", "oom", after=4)
        .rule("engine.step", "hang", after=8, times=1)
    )
    r = supervised("resident-chaos", "resident", plan, dict(tiered))
    check(
        r.detail["faults"]["watchdog_fired"] >= 1
        or "engine.step:hang" in r.detail["faults"]["injected"],
        "resident-chaos: hang was converted, not waited out",
    )

    # 4. sharded: one-shard transfer failure on a 2-chip mesh.
    if "--skip-sharded" not in argv:
        from stateright_tpu.parallel import make_mesh

        plan = FaultPlan(seed=9).rule(
            "shard.transfer", "shard", times=1, match={"shard": 1}
        )
        # Per-shard 2^8 tables at high_water 0.5 (trigger ~120): 2pc-3's
        # ~144 uniques per shard force real per-shard spill transfers. The
        # small batch keeps one all-to-all receive within the table.
        supervised("sharded-chaos", "sharded", plan, dict(
            mesh=make_mesh(2), batch_size=4, table_log2=8,
            store="tiered", high_water=0.5, summary_log2=12,
        ))

    # 5. service: poison job quarantined; siblings + unrelated groups
    # bit-identical.
    m3 = TensorTwoPhaseSys(3)
    mi = TensorIncrementLock(4)
    svc = CheckService(
        batch_size=256, table_log2=17, background=False, retry_limit=1
    )
    h_ok = svc.submit(m3)
    h_poison = svc.submit(m3)
    h_other = svc.submit(mi)
    plan = FaultPlan().rule(
        "service.step", "poison", times=-1, match={"job": h_poison.id}
    )
    with active(plan):
        svc.drain(timeout=300)
    r_ok, r_other = h_ok.result(), h_other.result()
    check(
        (r_ok.state_count, r_ok.unique_state_count) == GOLD,
        "service: poison job's group sibling bit-identical to golden",
    )
    check(
        (r_other.state_count, r_other.unique_state_count) == (257, 257),
        "service: unrelated group unaffected by the poison job",
    )
    check(
        svc.poll(h_poison.id)["quarantined"],
        "service: poison job quarantined",
    )
    sf = svc.stats()["faults"]
    print(f"     service faults={json.dumps(sf)}")
    check(sf["quarantined_jobs"] == 1, "service: quarantine accounted")

    # 6. HTTP plane: an injected front-end fault degrades to a 503 and the
    # server keeps serving.
    server = serve_service(svc, address="localhost:0")
    port = server.httpd.server_address[1]
    plan = FaultPlan().rule("service.http", "http", times=1)
    with active(plan):
        try:
            urllib.request.urlopen(f"http://localhost:{port}/.status")
            code = 200
        except urllib.error.HTTPError as e:
            code = e.code
        check(code == 503, "http: injected fault served as 503")
        with urllib.request.urlopen(
            f"http://localhost:{port}/.status"
        ) as resp:
            check(resp.status == 200, "http: server alive after the fault")
    server.shutdown()
    svc.close()

    print(f"\nartifacts: {outdir}")
    if failures:
        print(f"{len(failures)} check(s) FAILED")
        return 1
    print("all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
