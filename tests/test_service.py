"""Check service: continuous-batching multi-job scheduler over shared
device state tables (stateright_tpu/service/).

The contract under test is ISOLATED MULTIPLEXING: N concurrent jobs share
one device hash table (job-salted fingerprints) and one batch pipeline, yet
each job's counts, discoveries, and reconstructed paths are bit-identical
to a standalone single-job engine run of the same batch size. Plus the
serving behaviors a scheduler owes its jobs: cancellation frees lanes
mid-flight, preempt→resume is golden-exact, timeouts fire, and the HTTP
front end round-trips submissions.

All service tests share one module-scoped FOREGROUND service (driven by
pump()/drain(), deterministic) so each model's fused step compiles once.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from stateright_tpu.service import (
    CheckService,
    JobStatus,
    serve_service,
)
from stateright_tpu.service.server import ModelRegistry
from stateright_tpu.tensor.fingerprint import job_salt, pack_fp, salt_fp
from stateright_tpu.tensor.frontier import FrontierSearch
from stateright_tpu.tensor.models import (
    TensorIncrementLock,
    TensorTwoPhaseSys,
)

GOLD_2PC3 = (1_146, 288)
GOLD_2PC4 = (8_258, 1_568)
GOLD_INCLOCK4 = (257, 257)

# Module-level model instances: jobs submitted with the SAME instance share
# one compiled step (and batch lanes) — the continuous-batching contract.
M3 = TensorTwoPhaseSys(3)
M4 = TensorTwoPhaseSys(4)
MI = TensorIncrementLock(4)


@pytest.fixture(scope="module")
def svc():
    s = CheckService(batch_size=256, table_log2=17, background=False)
    yield s
    s.close()


# -- salt unit layer -----------------------------------------------------------


def test_salt_fp_is_a_nonzero_involution():
    rng = np.random.default_rng(3)
    lo = rng.integers(1, 2**32, 4096, dtype=np.uint32)
    hi = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    sl, sh = job_salt(7)
    klo, khi = salt_fp(lo, hi, sl, sh)
    assert (klo != 0).all()  # sentinel contract survives salting
    ulo, uhi = salt_fp(klo, khi, sl, sh)  # unsalt = same call (involution)
    assert (ulo == lo).all() and (uhi == hi).all()
    # Injective: no two inputs map to one key.
    assert len(set(pack_fp(klo, khi).tolist())) == len(
        set(pack_fp(lo, hi).tolist())
    )
    # The remapped point: lo == salt_lo would produce 0 without the remap.
    klo1, _ = salt_fp(np.asarray([sl]), np.asarray([sh]), sl, sh)
    assert klo1[0] == sl != 0


def test_job_salts_are_distinct_per_job():
    salts = {tuple(int(x) for x in job_salt(j)) for j in range(1, 200)}
    assert len(salts) == 199


# -- the acceptance bar: 8 concurrent mixed jobs, bit-identical ----------------


def test_eight_concurrent_mixed_jobs_bit_identical_to_standalone(svc):
    handles = [svc.submit(m) for m in (M3, M3, M3, M4, M4, M4, MI, MI)]
    svc.drain(timeout=600)
    gold = {id(M3): GOLD_2PC3, id(M4): GOLD_2PC4, id(MI): GOLD_INCLOCK4}
    for h in handles:
        r = h.result()
        assert r.complete
        assert (r.state_count, r.unique_state_count) == gold[id(h._job.model)]

    # Same-model jobs that ran to exhaustion are bit-identical to each
    # other: per-job BFS order is invariant to how lanes were granted.
    by_model: dict = {}
    for h in handles:
        by_model.setdefault(id(h._job.model), []).append(h.result())
    for results in by_model.values():
        first = results[0]
        for r in results[1:]:
            assert r.discoveries == first.discoveries
            assert r.max_depth == first.max_depth

    # ... and bit-identical to a STANDALONE engine of the same batch size:
    # unsalted discovery fingerprints and replayed paths match exactly,
    # even though the service run shared its table with 7 other jobs.
    alone = FrontierSearch(M3, batch_size=256, table_log2=14)
    r_alone = alone.run()
    r_svc = handles[0].result()
    assert (
        r_svc.state_count, r_svc.unique_state_count, r_svc.max_depth
    ) == (
        r_alone.state_count, r_alone.unique_state_count, r_alone.max_depth
    )
    assert r_svc.discoveries == r_alone.discoveries  # packed fps, bit-equal
    svc_paths = handles[0].discoveries()
    for name, fp in r_alone.discoveries.items():
        assert svc_paths[name].actions() == alone.reconstruct_path(fp).actions()

    # Continuous batching did pack jobs together: the 8 jobs consumed far
    # fewer fused steps than 8 standalone runs would (3x 11 + 3x 14 + 2x 17).
    total_steps = sum(h.result().steps for h in handles)
    assert svc.stats()["device_steps"] < total_steps


# -- cancellation frees lanes mid-flight ---------------------------------------


def test_cancellation_mid_flight_frees_lanes(svc):
    h1 = svc.submit(M4)
    h2 = svc.submit(M4)
    svc.pump(3)
    assert h1.status() == JobStatus.RUNNING
    assert h1._job.pending_lanes > 0
    assert h1.cancel() is True
    assert h1.status() == JobStatus.CANCELLED
    assert h1._job.pending_lanes == 0  # frontier dropped on the spot
    assert h1.cancel() is False  # idempotent: already finished
    svc.drain(timeout=300)
    r2 = h2.result()  # the survivor is unaffected by the shared table
    assert (r2.state_count, r2.unique_state_count) == GOLD_2PC4
    with pytest.raises(RuntimeError, match="cancelled"):
        h1.result()


# -- preempt -> resume golden parity -------------------------------------------


def test_preempt_resume_golden_parity(svc, tmp_path):
    svc.max_resident = 1
    svc.preempt_steps = 3
    svc.spill_dir = str(tmp_path)
    try:
        ha = svc.submit(M4)
        hb = svc.submit(M4)
        svc.drain(timeout=600)
    finally:
        svc.max_resident = None
        svc.preempt_steps = None
        svc.spill_dir = None
    ra, rb = ha.result(), hb.result()
    assert (ra.state_count, ra.unique_state_count) == GOLD_2PC4
    assert (rb.state_count, rb.unique_state_count) == GOLD_2PC4
    # With 1 resident slot and 2 jobs, both got parked at least once, the
    # parked frontier went through the checkpoint-machinery disk spill, and
    # resumption was exact (the goldens above).
    assert ra.detail["service"]["preemptions"] >= 1
    assert rb.detail["service"]["preemptions"] >= 1


# -- timeouts ------------------------------------------------------------------


def test_job_timeout_finishes_incomplete(svc):
    h = svc.submit(M4, timeout=0.0)
    svc.drain(timeout=120)
    r = h.result()
    assert r.complete is False
    assert r.detail.get("timed_out") is True


# -- Checker adapter -----------------------------------------------------------


def test_spawn_service_checker_adapter(svc):
    c = M3.checker().spawn_service(svc)
    svc.drain(timeout=300)
    c.join()
    assert c.is_done()
    assert (c.state_count(), c.unique_state_count()) == GOLD_2PC3
    c.assert_any_discovery("abort agreement")
    c.assert_no_discovery("consistent")
    assert sorted(c.discoveries()) == ["abort agreement", "commit agreement"]


# -- HTTP front end ------------------------------------------------------------


def test_http_front_end_round_trip(svc):
    # Registry maps onto the module's model instances, so HTTP submissions
    # join the already-compiled groups (no new compile in this test).
    srv = serve_service(
        svc, address="localhost:0",
        registry=ModelRegistry({"2pc3": lambda: M3}),
    )
    try:
        base = "http://" + srv.address

        def get(p):
            return json.loads(urllib.request.urlopen(base + p, timeout=10).read())

        def post(p, body=None):
            req = urllib.request.Request(
                base + p, data=json.dumps(body or {}).encode(), method="POST"
            )
            return json.loads(urllib.request.urlopen(req, timeout=10).read())

        jid = post("/jobs", {"model": "2pc3"})["job"]
        svc.drain(timeout=300)
        p = get(f"/jobs/{jid}")
        assert p["status"] == JobStatus.DONE
        assert (p["state_count"], p["unique_state_count"]) == GOLD_2PC3
        assert p["discoveries"] == ["abort agreement", "commit agreement"]
        assert p["metrics"]["device_steps"] > 0
        d = get(f"/jobs/{jid}/discoveries")
        assert set(d) == {"abort agreement", "commit agreement"}
        assert d["abort agreement"]["actions"]
        s = get("/.status")
        assert s["jobs"][JobStatus.DONE] >= 1
        assert any(row["id"] == jid for row in s["job_rows"])
        jid2 = post("/jobs", {"model": "2pc3"})["job"]
        assert post(f"/jobs/{jid2}/cancel")["cancelled"] is True
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/jobs/99999", timeout=10)
        assert e.value.code == 404
    finally:
        srv.shutdown()


class _Unnamed(TensorTwoPhaseSys):
    """A 2PC model that names no definition: its group is its instance."""


def test_equal_models_share_one_group(svc):
    """Jobs of equal models share one group (one compiled step, one batch)
    whichever instance each was submitted with, as the registry builds one
    per submission; each instance of a model that names no definition has
    its own group."""
    from stateright_tpu.service.scheduler import group_key

    groups = svc._engine.groups
    before = set(groups)
    reg = ModelRegistry()
    models = [TensorTwoPhaseSys(3), TensorTwoPhaseSys(3),
              reg.get("2pc", {"n": 3}), reg.get("2pc", {"n": 3})]
    assert len({id(m) for m in models}) == 4
    handles = [svc.submit(m) for m in models]
    svc.drain(timeout=300)
    for h in handles:
        r = h.result()
        assert (r.state_count, r.unique_state_count) == GOLD_2PC3
    assert {group_key(m) for m in models} == {group_key(M3)}
    assert set(groups) - before <= {group_key(M3)}
    unnamed = [_Unnamed(3), _Unnamed(3)]
    assert group_key(unnamed[0]) != group_key(unnamed[1])
    handles = [svc.submit(m) for m in unnamed]
    svc.drain(timeout=300)
    assert [(h.result().state_count, h.result().unique_state_count)
            for h in handles] == [GOLD_2PC3, GOLD_2PC3]
    assert {group_key(m) for m in unnamed} <= set(groups)


# -- tiered store shared across jobs -------------------------------------------


def test_tiered_service_jobs_share_spill_tier():
    svc = CheckService(
        batch_size=32, table_log2=10, store="tiered",
        high_water=0.55, summary_log2=14, background=False,
    )
    try:
        h1 = svc.submit(M3)
        h2 = svc.submit(M3)
        svc.drain(timeout=600)
        for h in (h1, h2):
            r = h.result()
            assert (r.state_count, r.unique_state_count) == GOLD_2PC3
            assert r.complete
        st = svc.store_stats()
        # Two 288-unique jobs through a 1024-slot table past a 0.55 water
        # mark: the spill tier really engaged, and both jobs' discovery
        # paths still reconstruct through the salted spill parent chains.
        assert st["spilled_states"] > 0 and st["spill_events"] >= 1
        paths = h2.discoveries()
        assert set(paths) == {"abort agreement", "commit agreement"}
        # The per-job spill attribution rides the job metrics.
        svc_detail = h1.result().detail
        assert svc_detail["store"] == "tiered"
    finally:
        svc.close()


# -- submission guardrails -----------------------------------------------------


def test_submit_rejects_host_models(svc):
    with pytest.raises(TypeError, match="TensorModel"):
        svc.submit(object())
