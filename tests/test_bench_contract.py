"""bench.py JSON-contract tests.

The driver records bench.py's one-line JSON as BENCH_r{N}.json; the judge and
dashboards read `value`/`vs_baseline` from it.  The contract: those fields describe the DEVICE engine only — when no device result
exists they must be null, never the C++ baseline number, so an empty-device
run can't masquerade as a healthy 1.0x.
"""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench", pathlib.Path(__file__).resolve().parent.parent / "bench.py"
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

BASE = {"paxos-3": {"states_per_sec": 1_674_699.0, "sec": 1.0}}


def test_device_result_reports_device_number_and_ratio():
    dev = {"paxos-3": {"states_per_sec": 3_349_398.0, "sec": 1.0}}
    metric, value, vs_baseline = bench.headline_summary(dev, BASE)
    assert value == 3_349_398.0
    assert vs_baseline == 2.0
    assert "device whole-search" in metric


def test_empty_device_reports_nulls_not_baseline():
    metric, value, vs_baseline = bench.headline_summary({}, BASE)
    assert value is None
    assert vs_baseline is None
    assert "device unavailable" in metric


def test_device_failed_on_headline_reports_nulls():
    # Device produced *some* result but not the headline workload.
    dev = {"2pc-4": {"states_per_sec": 1000.0, "sec": 1.0}}
    metric, value, vs_baseline = bench.headline_summary(dev, BASE)
    assert value is None
    assert vs_baseline is None
    assert "device failed on paxos-3" in metric


def test_smoke_mode_says_not_run_instead_of_failed():
    metric, value, vs_baseline = bench.headline_summary({}, BASE, smoke=True)
    assert value is None and vs_baseline is None
    assert "not run in smoke mode" in metric


def test_no_baseline_still_reports_device_value():
    dev = {"paxos-3": {"states_per_sec": 5.0, "sec": 1.0}}
    metric, value, vs_baseline = bench.headline_summary(dev, {})
    assert value == 5.0
    assert vs_baseline is None


def test_device_detail_pins_tier_occupancy_keys():
    # The tiered store's per-tier counters are part of the artifact
    # contract: a tiered run's degradation must be observable in every
    # BENCH_r*.json (hot-tier fill, spilled-state count, spill events).
    for key in ("hot_fill", "spilled_states", "spill_events"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 1000.0,
            "sec": 2.0,
            "hot_fill": 0.51,
            "spilled_states": 636,
            "spill_events": 3,
            "compile_sec": 9.0,  # not a detail field: must not leak
        }
    )
    assert row["hot_fill"] == 0.51
    assert row["spilled_states"] == 636
    assert row["spill_events"] == 3
    assert "compile_sec" not in row


def test_device_detail_omits_tier_keys_for_device_store_runs():
    row = bench.device_detail({"states_per_sec": 1000.0, "sec": 2.0})
    assert "hot_fill" not in row and "spilled_states" not in row


def test_device_detail_pins_telemetry_fields():
    # The telemetry spine's bench surface (ISSUE 4): the step digest rides
    # in detail.device, and the BENCH_OBS=1 A/B row must carry the
    # measured telemetry-on overhead so the <= 2% acceptance is auditable
    # in the artifact itself.
    for key in ("telemetry", "sec_off", "telemetry_overhead_pct"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 1000.0,
            "sec": 2.0,
            "telemetry": {"steps": 11, "lane_util": 0.37},
            "sec_off": 1.96,
            "telemetry_overhead_pct": 2.0,
        }
    )
    assert row["telemetry"]["steps"] == 11
    assert row["telemetry_overhead_pct"] == 2.0


def test_detail_counter_keys_conform_to_obs_schema():
    # One documented schema for every SearchResult.detail counter
    # (stateright_tpu/obs/schema.py): the tier keys bench copies verbatim,
    # the per-job service keys, and the telemetry digest keys must all be
    # spelled there — a producer renaming a counter breaks THIS pin, not a
    # dashboard three rounds later.
    from stateright_tpu.obs.schema import (
        DETAIL_KEYS,
        SERVICE_DETAIL_KEYS,
        TELEMETRY_KEYS,
        validate_detail,
    )

    for key in ("hot_fill", "spilled_states", "spill_events",
                "per_chip_unique", "per_shard_spilled", "telemetry"):
        assert key in DETAIL_KEYS
    # Every detail-shaped bench field is schema-known (service/bench-row
    # scalars like n_jobs/vs_serial are bench-JSON-only, not detail keys).
    for key in ("hot_fill", "spilled_states", "spill_events", "telemetry"):
        assert key in bench.DEVICE_DETAIL_FIELDS and key in DETAIL_KEYS
    # JobMetrics.to_dict's vocabulary (service/metrics.py) is the schema's.
    from stateright_tpu.service.metrics import JobMetrics

    jm = JobMetrics(submitted_at=0.0)
    jm.suspects_checked = 3  # exercise the optional spill keys too
    assert set(jm.to_dict(10)) <= set(SERVICE_DETAIL_KEYS)
    # A conforming synthetic detail validates clean; a drifted one is named.
    detail = {
        "store": "tiered",
        "hot_fill": 0.5,
        "spilled_states": 1,
        "spill_events": 1,
        "service": {"device_steps": 2},
        "telemetry": {k: 0 for k in TELEMETRY_KEYS},
    }
    assert validate_detail(detail) == []
    detail["telemetry"]["renamed_counter"] = 1
    assert validate_detail(detail) == ["telemetry.renamed_counter"]


def test_device_detail_pins_journal_row_keys():
    # The BENCH_OBS=1 flight-recorder journal A/B sub-row is part of the
    # artifact contract: the journal-off wall time, the measured
    # journal-on overhead through the check service (acceptance <= 5%),
    # and the recorded event count must survive into detail.device so the
    # "recording is free" claim is auditable in every BENCH_r*.json.
    for key in ("sec_journal_off", "journal_overhead_pct", "journal_events"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 6600.0,
            "sec": 1.25,
            "sec_journal_off": 1.24,
            "journal_overhead_pct": 0.8,
            "journal_events": 17,
        }
    )
    assert row["sec_journal_off"] == 1.24
    assert row["journal_overhead_pct"] == 0.8
    assert row["journal_events"] == 17


def test_event_vocabulary_conforms_to_obs_schema():
    # The flight-recorder event vocabulary is the documented obs schema's
    # (obs/schema.py EVENT_TYPES): every emit site in the library must use
    # a declared name (srlint SR003 enforces the literal sites; this pins
    # the schema's own shape), and the timeline CLI's lifecycle logic
    # depends on these exact spellings.
    from stateright_tpu.obs.schema import EVENT_TYPES, TERMINAL_EVENTS

    for name in (
        "job.submitted", "router.route", "router.failover", "replica.admit",
        "engine.chunk", "ckpt.write", "fault.injected", "fleet.steal",
        "job.requeued", "job.resumed", "job.done",
    ):
        assert name in EVENT_TYPES
        assert isinstance(EVENT_TYPES[name], tuple)
    for name in TERMINAL_EVENTS:
        assert name in EVENT_TYPES
    # Required-field maps name real correlation currency.
    assert "job" in EVENT_TYPES["job.submitted"]
    assert set(EVENT_TYPES["fleet.steal"]) == {"job", "src", "dst"}
    assert set(EVENT_TYPES["fault.injected"]) == {"point", "kind"}


def test_device_detail_pins_faults_row_keys():
    # The BENCH_FAULTS=1 supervisor-overhead A/B row is part of the
    # artifact contract: the recovery digest plus the unsupervised wall
    # time and the measured overhead (expected within noise with injection
    # disabled) must survive into detail.device so the "supervision is
    # free" claim is auditable in every BENCH_r*.json.
    for key in ("faults", "sec_unsupervised", "supervisor_overhead_pct"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 1000.0,
            "sec": 2.03,
            "faults": {"injected_total": 0, "retries": 0},
            "sec_unsupervised": 2.0,
            "supervisor_overhead_pct": 1.5,
        }
    )
    assert row["faults"]["injected_total"] == 0
    assert row["supervisor_overhead_pct"] == 1.5
    # And the faults vocabulary itself is the documented obs schema's
    # (obs/schema.py FAULTS_DETAIL_KEYS) — renames break this pin.
    from stateright_tpu.faults import FaultPlan, SupervisorConfig, Supervisor
    from stateright_tpu.obs.schema import (
        DETAIL_KEYS,
        FAULTS_DETAIL_KEYS,
        validate_detail,
    )
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    assert "faults" in DETAIL_KEYS
    sup = Supervisor(
        TensorTwoPhaseSys(3), engine="frontier", plan=FaultPlan(),
        config=SupervisorConfig(),
    )
    stats = sup.fault_stats()
    assert set(stats) <= set(FAULTS_DETAIL_KEYS)
    assert validate_detail({"faults": stats}) == []


def test_device_detail_pins_pallas_row_keys():
    # The BENCH_PALLAS=1 insert A/B row is part of the artifact contract:
    # the capped-insert wall time and the pallas-vs-capped speed ratio must
    # survive into detail.device so the ROADMAP-item-2 "biggest raw-speed
    # lever" claim is auditable in every BENCH_r*.json next to the
    # costmodel's committed ranking (costmodel.predict_ranking).
    for key in ("sec_capped", "pallas_vs_capped"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 33000.0,
            "sec": 0.25,
            "sec_capped": 0.26,
            "pallas_vs_capped": 1.04,
        }
    )
    assert row["sec_capped"] == 0.26
    assert row["pallas_vs_capped"] == 1.04


def test_device_detail_pins_fleet_row_keys():
    # The BENCH_FLEET=1 scale-out A/B row is part of the artifact
    # contract: N-replica jobs/s, the vs-one-replica ratio, the p50/p99
    # submit→result latency digest, and the robustness counters (steals,
    # requeues) must survive into detail.device so the ROADMAP-item-1
    # "N beats 1, zero lost jobs" claim is auditable in every BENCH_r*.json.
    for key in (
        "n_replicas", "fleet_jobs_per_sec", "sec_one_replica",
        "vs_one_replica", "fleet_p50_ms", "fleet_p99_ms",
        "fleet_steals", "fleet_requeued",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 3100.0,
            "sec": 9.1,
            "n_replicas": 3,
            "fleet_jobs_per_sec": 0.88,
            "sec_one_replica": 14.2,
            "vs_one_replica": 1.56,
            "fleet_p50_ms": 4100.0,
            "fleet_p99_ms": 8900.0,
            "fleet_steals": 2,
            "fleet_requeued": 0,
        }
    )
    assert row["n_replicas"] == 3
    assert row["vs_one_replica"] == 1.56
    assert row["fleet_p99_ms"] == 8900.0


def test_device_detail_pins_autoscale_row_keys():
    # The BENCH_AUTOSCALE=1 A/B row is part of the artifact contract:
    # fixed-1 vs autoscaled throughput, the ratio, the autoscaled run's
    # latency digest, and the control loop's scale-event evidence must
    # survive into detail.device so the ISSUE-17 "scaling is invisible in
    # the answers, visible in the wall clock" claim is auditable in every
    # BENCH_r*.json.
    for key in (
        "auto_max_replicas", "auto_jobs_per_sec", "auto_p50_ms",
        "auto_p99_ms", "auto_replicas_high_water", "auto_scale_outs",
        "auto_scale_ins", "sec_fixed_one", "vs_fixed_one",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 4600.0,
            "sec": 6.2,
            "auto_max_replicas": 3,
            "auto_jobs_per_sec": 1.28,
            "auto_p50_ms": 4218.0,
            "auto_p99_ms": 6240.0,
            "auto_replicas_high_water": 3,
            "auto_scale_outs": 2,
            "auto_scale_ins": 1,
            "sec_fixed_one": 13.3,
            "vs_fixed_one": 2.13,
        }
    )
    assert row["auto_replicas_high_water"] == 3
    assert row["vs_fixed_one"] == 2.13
    assert row["auto_p99_ms"] == 6240.0


def test_autoscale_counter_keys_conform_to_obs_schema():
    # The autoscaler's metrics() vocabulary (the "autoscaler" /metrics
    # source) is the documented obs schema's — a stub fleet is enough to
    # pin the shape without building a replica.
    from stateright_tpu.obs.schema import (
        AUTOSCALE_COUNTER_KEYS,
        REGISTRY_SOURCES,
    )
    from stateright_tpu.service.autoscale import Autoscaler

    assert "autoscaler" in REGISTRY_SOURCES

    class _Router:
        @staticmethod
        def stats():
            return {"healthy": 0, "queued": 0, "per_replica": {}}

    class _Fleet:
        router = _Router()

    scaler = Autoscaler(_Fleet())
    try:
        assert set(scaler.metrics()) == set(AUTOSCALE_COUNTER_KEYS)
        scaler.tick()
        assert set(scaler.metrics()) == set(AUTOSCALE_COUNTER_KEYS)
    finally:
        scaler.close()


def test_tenant_detail_keys_conform_to_obs_schema():
    # detail["tenant"] (present only on non-default-tenant jobs) is a
    # declared sub-schema: validate_detail accepts exactly its keys and
    # flags drift, so a rename breaks this pin, not a dashboard later.
    from stateright_tpu.obs.schema import (
        TENANT_DETAIL_KEYS,
        validate_detail,
    )

    tenant = {k: 0 for k in TENANT_DETAIL_KEYS}
    assert validate_detail({"tenant": tenant}) == []
    assert validate_detail(
        {"tenant": dict(tenant, renamed_key=1)}
    ) == ["tenant.renamed_key"]


def test_device_detail_pins_blob_row_keys():
    # The BENCH_BLOB=1 local-vs-blob backend A/B row is part of the
    # artifact contract: the local-filesystem wall time, the measured
    # blob-backend overhead, and the blob client's op/retry counters
    # must survive into detail.device so the ISSUE-15 "object store
    # costs only the wire, never the answers" claim is auditable in
    # every BENCH_r*.json.
    # The managed-dialect legs (ISSUE 20) pin the same trio per
    # provider: signed wall time, overhead vs sec_local_fs, counters.
    for key in (
        "sec_local_fs", "blob_overhead_pct", "blob_ops", "blob_retries",
        "sec_s3", "s3_overhead_pct", "s3_ops", "s3_retries",
        "sec_gcs", "gcs_overhead_pct", "gcs_ops", "gcs_retries",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 2900.0,
            "sec": 9.4,
            "sec_local_fs": 9.1,
            "blob_overhead_pct": 3.3,
            "blob_ops": 412,
            "blob_retries": 2,
            "sec_s3": 9.8,
            "s3_overhead_pct": 7.7,
            "s3_ops": 415,
            "s3_retries": 3,
            "sec_gcs": 9.6,
            "gcs_overhead_pct": 5.5,
            "gcs_ops": 414,
            "gcs_retries": 1,
        }
    )
    assert row["sec_local_fs"] == 9.1
    assert row["blob_overhead_pct"] == 3.3
    assert row["blob_ops"] == 412
    assert row["sec_s3"] == 9.8
    assert row["s3_retries"] == 3
    assert row["sec_gcs"] == 9.6
    assert row["gcs_ops"] == 414


def test_fleet_counter_keys_conform_to_obs_schema():
    # The fleet router's stats() vocabulary (its `/.status` body and the
    # "fleet" /metrics source) is the documented obs schema's — renames
    # break this pin, not a dashboard three rounds later. A replica-less
    # router is enough to pin the shape without compiling anything.
    from stateright_tpu.obs.schema import FLEET_COUNTER_KEYS, REGISTRY_SOURCES
    from stateright_tpu.service.router import FleetRouter

    assert "fleet" in REGISTRY_SOURCES
    router = FleetRouter([])
    try:
        assert set(router.stats()) == set(FLEET_COUNTER_KEYS)
    finally:
        router.close()


def test_device_detail_pins_corpus_row_keys():
    # The BENCH_CORPUS=1 warm-start A/B row is part of the artifact
    # contract: the cold wall time, the cold/warm ratio (ROADMAP item 4
    # acceptance >= 5x with bit-identical results), the preloaded-state
    # count, and the corrupted-entry CRC verdict must survive into
    # detail.device so the "repeat checks are ~free and never wrong"
    # claim is auditable in every BENCH_r*.json.
    for key in (
        "sec_cold", "warm_speedup", "warm_speedup_near",
        "warm_speedup_partial", "corpus_preloaded", "corrupt_detected",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 60000.0,
            "sec": 0.14,
            "sec_cold": 1.9,
            "warm_speedup": 13.6,
            "warm_speedup_near": 11.2,
            "warm_speedup_partial": 2.4,
            "corpus_preloaded": 1568,
            "corrupt_detected": True,
        }
    )
    assert row["warm_speedup"] == 13.6
    assert row["warm_speedup_near"] == 11.2
    assert row["warm_speedup_partial"] == 2.4
    assert row["corpus_preloaded"] == 1568
    assert row["corrupt_detected"] is True
    # And the corpus vocabulary itself is the documented obs schema's:
    # detail["corpus"] keys, the REGISTRY source, and the warm-start
    # event all resolve through obs/schema.py (srlint SR003 gates the
    # literal sites; this pins the schema's own shape). v2: the event
    # carries the warm KIND (exact | near | delta | partial —
    # knobs.WARM_KINDS), detail["corpus"] may carry it too, and the v2 +
    # Spec-CI delta counters are part of the registry vocabulary.
    from stateright_tpu.knobs import WARM_KINDS
    from stateright_tpu.obs.schema import (
        CORPUS_DELTA_COUNTERS,
        CORPUS_DETAIL_KEYS,
        CORPUS_V2_COUNTERS,
        DETAIL_KEYS,
        EVENT_TYPES,
        REGISTRY_SOURCES,
        validate_detail,
    )

    assert "corpus" in DETAIL_KEYS and "corpus" in REGISTRY_SOURCES
    assert EVENT_TYPES["job.warm_start"] == ("job", "kind")
    assert WARM_KINDS == ("exact", "near", "partial", "delta")
    assert "warm_kind" in CORPUS_DETAIL_KEYS
    assert "delta_class" in CORPUS_DETAIL_KEYS
    for key in (
        "partial_publishes", "partial_preloads", "near_match_hits",
        "superseded_entries",
    ):
        assert key in CORPUS_V2_COUNTERS
    assert CORPUS_DELTA_COUNTERS == (
        "delta_hits", "delta_refusals", "component_reuse",
    )
    detail = {"corpus": {k: 1 for k in CORPUS_DETAIL_KEYS}}
    assert validate_detail(detail) == []


def test_device_detail_pins_delta_row_keys():
    # The BENCH_DELTA=1 Spec-CI A/B row: the property-edit cold wall
    # time, the delta-rung ratio (ISSUE 18 acceptance >= 2x with
    # bit-identical counts), and the classifier's named edit class must
    # survive into detail.device so "a one-line model edit is a warm
    # run" is auditable in every BENCH_r*.json.
    for key in ("sec_cold", "warm_speedup_delta", "delta_class"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 94000.0,
            "sec": 0.09,
            "sec_cold": 0.85,
            "warm_speedup_delta": 9.8,
            "delta_class": "properties-only",
        }
    )
    assert row["warm_speedup_delta"] == 9.8
    assert row["delta_class"] == "properties-only"


def test_analysis_row_pins_budget_keys():
    # The BENCH_ANALYSIS=1 static-analysis budget row is part of the
    # artifact contract: srlint finding count, knob-registry drift, and
    # each engine anchor's audited step totals vs the costmodel must keep
    # these spellings so a BENCH_r*.json can answer "did the compiled step
    # program grow" across rounds without re-profiling. worker_analysis()
    # (bench.py) produces exactly this shape; here we pin the vocabulary
    # without importing jax.
    assert bench.ANALYSIS_ROW_FIELDS == (
        "srlint_findings", "knob_drift", "engines", "clean",
    )
    for key in ("step_hbm_bytes", "step_flops", "transfer_bytes",
                "model_bytes", "ratio", "ratio_ok", "violations", "skipped"):
        assert key in bench.ANALYSIS_ENGINE_FIELDS
    # A worker_analysis-shaped row conforms to the pinned vocabulary: every
    # top-level key is a row field, every per-engine key an engine field.
    row = {
        "srlint_findings": 0,
        "knob_drift": 0,
        "engines": {
            "frontier": {
                "step_hbm_bytes": 81_037_075,
                "step_flops": 299_275_389,
                "transfer_bytes": 8448,
                "model_bytes": 5_964_248,
                "ratio": 13.59,
                "ratio_ok": True,
                "violations": [],
            },
            "sharded": {"skipped": "needs 8 devices"},
        },
        "clean": True,
    }
    assert set(row) == set(bench.ANALYSIS_ROW_FIELDS)
    for eng in row["engines"].values():
        assert set(eng) <= set(bench.ANALYSIS_ENGINE_FIELDS)


def test_device_detail_pins_service_row_keys():
    # The BENCH_SERVICE=1 check-service row is part of the artifact
    # contract: mixed-job-batch throughput and the serial A/B ratio must
    # survive into detail.device so the "service beats serial" claim is
    # auditable in every BENCH_r*.json.
    for key in ("n_jobs", "jobs_per_sec", "vs_serial", "serial_sec"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 3400.0,
            "sec": 12.7,
            "n_jobs": 8,
            "jobs_per_sec": 0.63,
            "vs_serial": 1.74,
            "serial_sec": 22.2,
            "service_steps": 54,
            "serial_steps": 125,
        }
    )
    assert row["n_jobs"] == 8
    assert row["vs_serial"] == 1.74
    assert row["jobs_per_sec"] == 0.63
    assert row["service_steps"] == 54


def test_device_detail_pins_semantics_row_keys():
    # The BENCH_SEMANTICS=1 dedup-first verdict-plane A/B row (ISSUE 13):
    # the cache-only wall time, the measured ratio (acceptance >= 2x with
    # bit-identical verdicts), and the plane's evidence counters must all
    # survive into detail.device so the speedup claim is auditable in
    # every BENCH_r*.json.
    for key in (
        "sec_legacy", "semantics_speedup", "verdict_negatives",
        "canonical_collapsed", "witness_guided_hits", "full_searches",
        "batch_parallel_evals",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 14000.0,
            "sec": 0.43,
            "sec_legacy": 2.4,
            "semantics_speedup": 5.58,
            "verdict_negatives": 5977,
            "canonical_collapsed": 0,
            "witness_guided_hits": 1501,
            "full_searches": 332,
            "batch_parallel_evals": 331,
        }
    )
    assert row["semantics_speedup"] == 5.58
    assert row["sec_legacy"] == 2.4
    assert row["witness_guided_hits"] == 1501
    assert row["full_searches"] == 332


def test_device_detail_pins_simulation_row_keys():
    # The BENCH_SIM=1 fourth-checker-mode A/B row (ISSUE 14): the host
    # walker's wall time and rates, the device walks/s and the measured
    # ratio (acceptance >= 2x with identical verdicts), the continuous-
    # batching evidence (lane_util ~1, restarts > 0), the shared-table
    # dedup hit rate, and the same-seed determinism verdict must all
    # survive into detail.device so the "device simulation beats the host
    # walker" claim is auditable in every BENCH_r*.json.
    for key in (
        "sec_host_sim", "host_states_per_sec", "sim_walks_per_sec",
        "host_walks_per_sec", "sim_speedup", "sim_lane_util",
        "sim_restarts", "sim_dedup_hit_rate", "sim_bit_identical",
    ):
        assert key in bench.DEVICE_DETAIL_FIELDS
    row = bench.device_detail(
        {
            "states_per_sec": 321657.0,
            "sec": 0.19,
            "sec_host_sim": 1.69,
            "host_states_per_sec": 35419.8,
            "sim_walks_per_sec": 39106.2,
            "host_walks_per_sec": 6557.6,
            "sim_speedup": 5.96,
            "sim_lane_util": 1.0,
            "sim_restarts": 8528,
            "sim_dedup_hit_rate": 0.996,
            "sim_bit_identical": True,
        }
    )
    assert row["sim_speedup"] == 5.96
    assert row["sim_lane_util"] == 1.0
    assert row["sim_bit_identical"] is True
    # And the walk-plane vocabulary itself is the documented obs schema's:
    # telemetry keys, the REGISTRY source, and the dedup knob universe all
    # resolve through one registry each.
    from stateright_tpu.knobs import CHECKER_MODES, SIM_DEDUP_KINDS
    from stateright_tpu.obs.schema import (
        REGISTRY_SOURCES,
        TELEMETRY_KEYS,
        validate_detail,
    )

    assert "simulation" in REGISTRY_SOURCES
    for key in ("walks", "walks_per_sec", "restarts", "stale_restarts",
                "dedup_hit_rate"):
        assert key in TELEMETRY_KEYS
    assert SIM_DEDUP_KINDS == ("trace", "shared")
    assert CHECKER_MODES == ("search", "simulation")
    detail = {
        "telemetry": {
            "steps": 77, "walks": 8528, "walks_per_sec": 39106.2,
            "lane_util": 1.0, "restarts": 8528, "dedup_hit_rate": 0.996,
            "stale_restarts": 0, "generated_total": 61171,
        }
    }
    assert validate_detail(detail) == []


def test_semantics_counters_exported_through_registry_schema():
    # The plane's counters flow through the obs REGISTRY "semantics"
    # source (pinned in obs/schema.py REGISTRY_SOURCES) and the corpus
    # detail schema names the verdict-preload key.
    from stateright_tpu.obs.schema import (
        CORPUS_DETAIL_KEYS,
        REGISTRY_SOURCES,
    )
    from stateright_tpu.semantics.linearizability import verdict_cache_stats

    assert "semantics" in REGISTRY_SOURCES
    assert "verdict_preloads" in CORPUS_DETAIL_KEYS
    stats = verdict_cache_stats()
    for key in (
        "canonical_hits", "canonical_collapsed", "witness_guided_hits",
        "batch_evals", "batch_eval_ms_total", "preloaded_verdicts",
        "trims", "canonical_entries",
    ):
        assert key in stats


def test_device_detail_pins_calib_row_keys():
    # The BENCH_CALIB=1 measured-vs-predicted A/B row (ISSUE 19): the
    # drift digest and the comparator-off wall time / overhead must ride
    # in the artifact so the within-noise acceptance is auditable, and
    # the digest vocabulary is the obs schema's.
    from stateright_tpu.obs.schema import DETAIL_KEYS, REGISTRY_SOURCES

    for key in ("calib", "sec_off", "calib_overhead_pct"):
        assert key in bench.DEVICE_DETAIL_FIELDS
    assert "calib" in DETAIL_KEYS and "calib" in REGISTRY_SOURCES
    row = bench.device_detail(
        {
            "states_per_sec": 1000.0,
            "sec": 2.0,
            "calib": {"drift_ratio": 1.02, "predicted_ms": 12.9},
            "sec_off": 1.98,
            "calib_overhead_pct": 1.0,
        }
    )
    assert row["calib"]["drift_ratio"] == 1.02
    assert row["calib_overhead_pct"] == 1.0


def test_calib_comparator_conforms_to_obs_schema():
    # A live comparator's metrics() is exactly the pinned counter set
    # (the "calib" REGISTRY source) and its detail() exactly the pinned
    # detail sub-dict — renames break this pin, not a dashboard later.
    from stateright_tpu.obs.calib import CalibConfig, Comparator
    from stateright_tpu.obs.schema import (
        CALIB_COUNTER_KEYS,
        CALIB_DETAIL_KEYS,
        validate_detail,
    )
    from stateright_tpu.tensor.costmodel import V5E

    cfg = CalibConfig(engine="resident", variant="split", lanes=8,
                      max_actions=4, batch=256, table_log2=12)
    comp = Comparator(cfg, device=V5E, chunk_steps=4)
    comp.observe(4, 4000.0, generated_total=2048)
    assert comp.chunks == 1
    assert set(comp.metrics()) == set(CALIB_COUNTER_KEYS)
    detail = comp.detail()
    assert set(detail) == set(CALIB_DETAIL_KEYS)
    assert validate_detail({"calib": detail}) == []
