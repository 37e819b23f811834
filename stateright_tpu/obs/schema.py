"""The ONE documented counter schema for `SearchResult.detail`.

Before this module, per-engine counters were ad-hoc: `store_stats()` invented
tier keys, the sharded engine added balance lists, the check service nested
its own dict — each consumer (bench.py's DEVICE_DETAIL_FIELDS, the
bench-contract tests, the Explorer `/.status`) had to know every producer's
private spelling. This schema pins the shared vocabulary: every key an engine
may put in `SearchResult.detail` is named here with its owner and meaning,
`tests/test_bench_contract.py` pins the schema against bench's field list,
and `validate_detail` gives tests a one-call check that an engine has not
drifted off it.
"""

from __future__ import annotations

from typing import Optional

#: Top-level `SearchResult.detail` keys (owner → meaning).
DETAIL_KEYS = {
    # tiered state store (store/tiered.py `stats()`)
    "store": "state-store kind; 'tiered' when the two-tier store is active",
    "hot_fill": "device hot-tier fill fraction (claimed slots / table slots)",
    "spilled_states": "states resident in the host spill tier",
    "spill_events": "high-water eviction sweeps completed",
    "suspects_checked": "Bloom-positive claims resolved exactly on host",
    "suspects_dup": "suspects confirmed as spilled duplicates",
    "evict_bytes_pcie": "bytes actually moved over PCIe by eviction",
    "evict_bytes_unfiltered": "bytes full-window eviction would have moved",
    # sharded engine (parallel/sharded.py)
    "per_chip_unique": "per-shard unique-state counts (balance evidence)",
    "per_shard_spilled": "per-shard spill-tier occupancy (tiered only)",
    # check service (service/scheduler.py `build_result`)
    "service": "per-job service metrics sub-dict (SERVICE_DETAIL_KEYS)",
    "timed_out": "True when the job hit its service deadline",
    # telemetry spine (obs/ring.py `StepRing.summary`)
    "telemetry": "step-telemetry digest sub-dict (TELEMETRY_KEYS)",
    # the resident and sharded engines' whole-search call, staged
    # (tensor/search_host.py Stages)
    "phases": "per-stage wall seconds of the search call sub-dict "
              "(PHASES_DETAIL_KEYS)",
    # chaos plane + supervisor (stateright_tpu/faults/)
    "faults": "fault-injection/recovery counters sub-dict "
              "(FAULTS_DETAIL_KEYS)",
    # flight recorder (obs/events.py): the job-scoped trace id minted at
    # submission and carried through every replica the job touched — the
    # key that joins this result to its journal events and Chrome spans.
    "trace": "job-scoped trace correlation id (service/fleet jobs)",
    # warm-start corpus (store/corpus.py)
    "corpus": "cross-job warm-start sub-dict (CORPUS_DETAIL_KEYS)",
    # multi-tenant control plane (service/tenancy.py) — present only on
    # jobs submitted under a non-default tenant, so default-tenant results
    # stay byte-identical to the pre-tenancy goldens.
    "tenant": "per-tenant accounting sub-dict (TENANT_DETAIL_KEYS)",
    # calibration observatory (obs/calib.py Comparator) — present only
    # when the comparator is enabled AND closed at least one chunk, so
    # calib-off runs (SR_TPU_CALIB=0) keep their pre-observatory shape.
    "calib": "measured-vs-predicted cost sub-dict (CALIB_DETAIL_KEYS)",
}


#: Keys of `detail["calib"]` (obs/calib.py Comparator.detail) — the
#: live measured-vs-predicted join for the run's exact config. `terms`
#: is the one intentionally-dynamic sub-dict: one predicted-ms entry per
#: costmodel OpCost name the active variant prices.
CALIB_DETAIL_KEYS = {
    "engine": "which engine the comparator observed "
              "(frontier/resident/sharded/simulation/service)",
    "variant": "costmodel variant the prediction priced "
               "(costmodel.ENGINE_VARIANTS value)",
    "device": "DeviceSpec kind predictions used (overlay-aware)",
    "predicted_ms": "costmodel ms/step for the last chunk's new_frac",
    "measured_p50_ms": "measured ms/step, step-weighted p50 over chunks",
    "measured_p95_ms": "measured ms/step, step-weighted p95 over chunks",
    "drift_ratio": "measured/predicted, step-weighted p50 over chunks",
    "new_frac": "populated-lane fraction the capped prediction used "
                "(quantized; from drained generated counts)",
    "chunks": "comparison chunks closed (~chunk_steps steps each)",
    "out_of_band": "chunks whose ratio left the seeded drift band",
    "drift_events": "drift episodes journaled (K consecutive chunks out)",
    "terms": "per-term predicted-ms attribution sub-dict (OpCost names)",
    "top_term": "largest predicted term — the blame heuristic a drift "
                "episode names",
}

#: The `"calib"` REGISTRY source (obs/calib.py Comparator.metrics) —
#: scrape names on both /metrics front doors, pinned like every source.
CALIB_COUNTER_KEYS = {
    "chunks": "comparison chunks closed",
    "out_of_band": "chunks outside the seeded drift band",
    "drift_events": "drift episodes (K consecutive out-of-band chunks)",
    "drift_active": "1 while an episode is open, else 0",
    "last_ratio": "latest chunk's measured/predicted",
    "last_predicted_ms": "latest chunk's predicted ms/step",
    "last_measured_ms": "latest chunk's measured ms/step",
    "records_flushed": "durable observation-record merges written",
    "record_errors": "record writes that failed (store unreachable)",
}

#: Keys of `detail["corpus"]` (service/scheduler.py `build_result`, the
#: engines' warm_start paths) — present only on corpus-enabled runs.
CORPUS_DETAIL_KEYS = {
    "warm_start": "True when the job preloaded a published visited set",
    "warm_kind": "which warm-ladder rung served the preload: 'exact' | "
                 "'near' | 'delta' | 'partial' (knobs.WARM_KINDS; absent "
                 "on cold runs)",
    "preloaded_states": "states preloaded into the spill tier + summary",
    "verdict_preloads": "semantics verdict bits the warm preload seeded "
                        "into the canonical cache (dedup-first semantics)",
    "published": "True when this job published a NEW corpus entry "
                 "(complete or partial)",
    "key": "content-key prefix (model definition + lowering + finish hash)",
    "delta_class": "Spec-CI edit class the delta rung salvaged: "
                   "'properties-only' | 'boundary-only' "
                   "(store/specdelta.py; absent off the delta rung)",
}

#: Corpus-v2 REGISTRY counters (store/corpus.py `metrics()`, "corpus"
#: source) — the delta-proportional re-verification plane's scrape names,
#: pinned here (and in tests/test_bench_contract.py) exactly like the
#: detail keys above so dashboards never chase a renamed counter.
CORPUS_V2_COUNTERS = (
    "partial_publishes",    # partial entries written on non-DONE exits
    "partial_preloads",     # warm-from-partial admissions
    "near_match_hits",      # family-index fallbacks that served an entry
    "superseded_entries",   # partials deleted by a later complete publish
)

#: Spec-CI definition-delta counters (store/specdelta.py through
#: store/corpus.py `metrics()`, same "corpus" scrape source) — pinned
#: separately from CORPUS_V2_COUNTERS because they account EDITS, not
#: re-checks of the same definition.
CORPUS_DELTA_COUNTERS = (
    "delta_hits",        # edits the delta rung salvaged (replay/continue)
    "delta_refusals",    # candidate edits refused salvage (ran cold)
    "component_reuse",   # per-hit unchanged definition components reused
)

#: Keys of `detail["service"]` (service/metrics.py JobMetrics.to_dict).
SERVICE_DETAIL_KEYS = {
    "queue_wait": "seconds between submission and first lane grant",
    "device_steps": "fused device steps the job held >= 1 lane in",
    "lanes_held": "cumulative lanes across those steps (device share)",
    "preemptions": "times the job was parked for waiting jobs",
    "suspects_checked": "the job's Bloom-positive claims",
    "suspects_dup": "...of which were confirmed spilled duplicates",
    "spill_share": "suspects_checked / unique states (spill pressure)",
}

#: Keys of `detail["tenant"]` (service/scheduler.py `build_result`) —
#: present only when the job ran under a non-default tenant, so the
#: default namespace's result dicts (and every pre-tenancy golden) are
#: untouched.
TENANT_DETAIL_KEYS = {
    "name": "the tenant identity the job was submitted under",
    "lane_seconds": "device lane-seconds the job charged against the "
                    "tenant's budget (lanes x wall-seconds of fused "
                    "steps it held lanes in)",
}

#: Autoscaler REGISTRY counters (service/autoscale.py `metrics()`, the
#: "autoscaler" source) — the reconciliation loop's scrape names, pinned
#: here (and in tests/test_bench_contract.py) like every other source.
AUTOSCALE_COUNTER_KEYS = {
    "ticks": "reconcile ticks completed (signal read + decision)",
    "scale_outs": "replicas spawned into probation by the autoscaler",
    "scale_ins": "replicas drained, lease-revoked, and retired",
    "aborted_ticks": "reconcile ticks abandoned by an injected "
                     "`fleet.autoscale` fault with NOTHING changed",
    "cooldown_skips": "wanted moves suppressed by the cooldown window",
    "hysteresis_holds": "ticks where the signals sat between the "
                        "scale-out and scale-in bands (no move wanted)",
    "replicas": "current fleet size as of the last tick",
    "replicas_high_water": "peak fleet size the autoscaler ever reached",
    "last_queue_depth": "fleet-wide queued jobs as of the last tick",
    "last_lane_util": "mean per-replica lane utilization, last tick",
    "last_p99_ms": "p99 admission latency (ms) as of the last tick",
}


#: Keys of `detail["telemetry"]` (obs/ring.py StepRing.summary).
TELEMETRY_KEYS = {
    "steps": "total engine steps observed",
    "captured_steps": "steps with a retained telemetry row",
    "dropped_steps": "steps without a retained row (ring overwrite on "
                     "device, or evicted from the host retention window)",
    "generated_total": "sum of per-step generated counts over every "
                       "DRAINED row (exact unless the device ring wrapped)",
    "claimed_total": "sum of per-step fresh table claims over every "
                     "drained row",
    "active_lanes": "batch occupancy digest {mean,p50,p95,max}",
    "generated_per_step": "per-step generated digest {mean,p50,p95,max}",
    "claimed_per_step": "per-step claim digest {mean,p50,p95,max}",
    "queue_len_max": "peak frontier-queue occupancy",
    "fill": "table-fill trajectory {last,p95,max}",
    "lane_util": "mean active lanes / batch size",
    "step_us": "per-step wall-time digest {mean,p50,p95,max} where timed",
    "suspects_max": "peak suspect-buffer occupancy (tiered only)",
    "shard_imbalance": "max/mean of per-shard claimed totals (sharded only)",
    # Device random-simulation engine (tensor/simulation.py): the walk-plane
    # digest. `lane_util` above is reused (mean active lanes / traces) —
    # with continuous walk batching it stays ~1 instead of collapsing to
    # the tail walk.
    "walks": "random walks completed (simulation engine)",
    "walks_per_sec": "completed walks per second of device time (simulation)",
    "restarts": "lane re-seeds: walks started beyond the initial batch "
                "(continuous walk batching; simulation)",
    "stale_restarts": "walks cut short by the staleness knob after "
                      "stale_limit consecutive already-visited states "
                      "(shared dedup only)",
    "dedup_hit_rate": "fraction of walk states already present in the "
                      "shared visited table (dedup='shared' only)",
}


#: Keys of `detail["phases"]` (tensor/search_host.py `Stages`, the whole-search
#: path of the resident and the sharded engines): wall seconds of the stages
#: jit runs for the search program in this run, each timed always and
#: spanned as `<engine>.<stage>` inside `<engine>.search` (`resident` or
#: `sharded`). The first three are 0 when the run took its executable from
#: the cache: an earlier run of the same engine, or of any engine of the
#: same model definition and configuration (compile_cache.py).
PHASES_DETAIL_KEYS = {
    "trace_s": "tracing the search closure to a jaxpr",
    "lower_s": "lowering the jaxpr to an HLO module",
    "compile_s": "backend compile, or the load of the executable from "
                 "the persistent compilation cache",
    "execute_s": "running the executable through the one summary fetch",
}


#: Counters of `Stages.totals` (tensor/search_host.py), reported by the
#: `resident` and `sharded` registry sources through the engines'
#: `metrics()`: the stages' seconds over the engine's runs, and how its
#: whole-search calls found their executable.
STAGES_COUNTER_KEYS = {
    "trace_seconds_total": "seconds tracing the search (trace_s, summed)",
    "lower_seconds_total": "seconds lowering it (lower_s, summed)",
    "compile_seconds_total": "seconds compiling or loading it (compile_s, "
                             "summed)",
    "execute_seconds_total": "seconds running it (execute_s, summed)",
    "executable_cache_hits_total": "whole-search calls whose executable "
                                   "came from the cache: no trace, "
                                   "lowering or load",
    "executable_cache_misses_total": "whole-search calls that traced, "
                                     "lowered and compiled or loaded "
                                     "their executable",
}


#: Keys of `detail["faults"]` (faults/supervisor.py `fault_stats` and the
#: check service's engine-level fault counters). `injected` is the one
#: intentionally-dynamic sub-dict: its keys are "<point>:<kind>" pairs from
#: the active FaultPlan.
FAULTS_DETAIL_KEYS = {
    "injected_total": "faults injected by the active FaultPlan, total",
    "injected": "per-injection-point counts sub-dict ('point:kind' keys)",
    "retries": "recovery retries (supervisor slices / service step retries)",
    "backoff_ms": "cumulative retry backoff, milliseconds",
    "degrade_steps": "degrade-ladder escalations taken",
    "degrade_rung": "final ladder rung index (faults.RUNGS order)",
    "checkpoint_generations": "atomic checkpoint generations written",
    "restores": "engine rebuilds served from a checkpoint generation",
    "watchdog_fired": "hangs the watchdog cancelled or abandoned",
    "drained": "graceful SIGTERM drains taken",
    "step_faults": "service fused-step faults absorbed (group-scoped)",
    "quarantined_jobs": "poison jobs parked by the service retry policy",
}


#: Source names components may register metric providers under
#: (obs/registry.py REGISTRY.register). The srlint pass
#: (stateright_tpu/analysis/) rejects a register() call whose literal source
#: name is not declared here — /metrics scrape names are part of the
#: dashboard contract, exactly like the detail keys above.
REGISTRY_SOURCES = {
    "frontier": "host-orchestrated engine (tensor/frontier.py)",
    "resident": "device-resident engine (tensor/resident.py)",
    "sharded": "multi-chip engine (parallel/sharded.py)",
    "service": "check service scheduler (service/api.py)",
    "supervisor": "self-healing supervisor (faults/supervisor.py)",
    "fleet": "multi-replica fleet router (service/router.py)",
    "corpus": "cross-job warm-start corpus store (store/corpus.py)",
    "semantics": "consistency-tester verdict planes: the legacy "
                 "per-identity memos plus the dedup-first canonical cache "
                 "(semantics/canonical.py — class collapse, witness "
                 "guidance, batch evals, corpus preloads, trims)",
    "lease": "epoch-fenced checkpoint leases (service/lease.py)",
    "simulation": "device random-simulation engine (tensor/simulation.py — "
                  "walks, restarts, shared-table dedup hits)",
    "blob": "object-store backend client (faults/blobstore.py — ops, "
            "retries, backoff, torn puts, stale lists, unavailability, "
            "Retry-After floor waits, auth retries)",
    "blob_s3": "managed S3 backend client (faults/blobstore_s3.py — same "
               "counter keys as \"blob\"; SigV4-signed wire ops)",
    "blob_gcs": "managed GCS backend client (faults/blobstore_gcs.py — "
                "same counter keys as \"blob\"; bearer-authed JSON API)",
    "creds": "managed-store credential chain (faults/creds.py — "
             "resolves, refreshes, refresh failures, grace-window "
             "serves, SDK-unavailable degrades)",
    "autoscaler": "elastic control plane reconciliation loop "
                  "(service/autoscale.py — AUTOSCALE_COUNTER_KEYS)",
    "calib": "calibration observatory comparator (obs/calib.py — "
             "CALIB_COUNTER_KEYS; one provider per live engine)",
}


#: Keys of the fleet router's `stats()` (service/router.py) — the fleet
#: `/.status` body and the "fleet" `/metrics` source. Pinned by
#: tests/test_bench_contract.py exactly like the detail schemas above;
#: `per_replica` is the one intentionally-dynamic sub-dict (one row per
#: replica index, fleet.Replica.snapshot_row).
FLEET_COUNTER_KEYS = {
    "replicas": "replicas the fleet was built with",
    "healthy": "replicas currently passing health probes",
    "jobs": "fleet jobs by status sub-dict (routed/done/cancelled/error)",
    "queued": "inner jobs waiting in replica admission queues, fleet-wide",
    "jobs_routed": "successful job placements (initial + requeue + steal)",
    "router_retries": "submissions retried after a replica timeout/fault",
    "router_backoff_ms": "cumulative deterministic submit backoff, ms",
    "probe_failures": "health probes that failed or timed out",
    "replica_crashes": "replicas declared dead and removed from the ring",
    "requeued_jobs": "jobs moved off a dead replica (zero-lost-jobs ledger)",
    "restored_jobs": "requeued jobs resumed from an intact checkpoint "
                     "generation (the rest restarted fresh)",
    "steals": "queued jobs pulled to an idle replica (work stealing)",
    "probe_skipped": "health probes deferred by the per-replica "
                     "exponential probe backoff (failing members)",
    "rejoins": "dead/fenced members re-admitted into probation with a "
               "fresh lease epoch (replica REJOIN)",
    "rejoin_promotions": "rejoined members that passed their probation "
                         "probes and re-entered the ring (only their own "
                         "keys move back)",
    "scale_outs": "replicas joined at a BRAND-NEW index (autoscaler "
                  "scale-out; enters probation exactly like a rejoin)",
    "scale_ins": "replicas gracefully drained and retired (autoscaler "
                 "scale-in; backlog requeued loss-free, lease revoked)",
    "quota_rejected": "submissions refused at admission because the "
                      "tenant was over its in-flight or lane-seconds "
                      "quota (HTTP 429 + Retry-After; retryable)",
    "lease_revokes": "ring-member leases revoked before requeueing "
                     "(0 on a lease-less fleet)",
    "lease_reseals": "orphan checkpoint generations re-sealed under the "
                     "router's lease at requeue time",
    "lease_rejected": "fenced writes/reads/events refused or discarded "
                      "because the writer's lease epoch was revoked "
                      "(router-side view; per-replica counts live in the "
                      "'lease' registry source of each process)",
    "per_replica": "one status row per replica sub-dict",
    "events_recent": "last-N flight-recorder events (obs/events.py ring; "
                     "[] when the fleet journals nothing)",
}


#: The flight-recorder event vocabulary (obs/events.py journals): event
#: type -> the field names every emission of that type MUST carry (beyond
#: the stamps the journal adds itself: ts / seq / writer / pid, and the
#: job-scoped `trace` correlation id where one exists). `EventJournal.emit`
#: rejects an undeclared type or a missing required field, and the srlint
#: SR003 pass rejects a literal `events.emit("<name>", ...)` whose name is
#: not spelled here — the journal is a cross-replica forensic contract
#: (obs/timeline.py reconstructs job lifecycles from it), so the
#: vocabulary drifts only through this map.
EVENT_TYPES = {
    # job lifecycle (the timeline CLI's per-trace spine)
    "job.submitted": ("job",),       # accepted by a router or service
    "replica.admit": ("job",),       # granted lanes on a service/replica
    "job.preempted": ("job",),       # parked for waiting jobs (re-admits)
    "job.requeued": ("job", "src"),  # moved off a dead replica
    "job.resumed": ("job",),         # re-admitted from a checkpoint journal
    "job.warm_start": ("job", "kind"),  # corpus preloaded at admission
    # (states=n; kind=exact|near|delta|partial — the warm-ladder rung)
    "job.quarantined": ("job",),     # poison job parked by the retry policy
    "job.quota_rejected": ("tenant",),  # admission refused over-quota (429)
    "job.done": ("job",),
    "job.cancelled": ("job",),
    "job.error": ("job",),
    # router / fleet choreography
    "router.route": ("job", "replica"),    # placement bound job -> replica
    "router.failover": ("job", "replica"), # submit attempt failed; walking on
    "router.probe": ("replica", "ok"),     # health-probe FAILURE accounting
    "router.unavailable": ("reason",),     # 503 surface (no healthy replica)
    "replica.crash": ("replica",),         # declared dead, removed from ring
    "replica.rejoin": ("replica", "phase"),  # probation entered / ring re-add
    "fleet.steal": ("job", "src", "dst"),  # queued job pulled to idle replica
    # elastic control plane (service/autoscale.py): every scale decision
    # the reconciler actuates is journaled — the flight recorder is the
    # audit log that explains why the fleet is the size it is.
    "fleet.scale_out": ("replica",),  # new member spawned into probation
    "fleet.scale_in": ("replica",),   # member drained, revoked, retired
    # engine / durability plane
    "engine.chunk": ("jobs",),       # one fused service step (jobs: id list)
    "ckpt.write": ("job",),          # atomic checkpoint generation written
    "fault.injected": ("point", "kind"),  # chaos plane (faults/plan.py)
    # epoch-fenced checkpoint leases (service/lease.py): the router is the
    # single lease authority, so grant/revoke are router-journal events;
    # reject is written by WHOEVER refused the fenced write/read (a zombie
    # replica's own journal records its fencing — rejection is evidence,
    # so it is deliberately NOT itself lease-gated).
    "lease.grant": ("member", "epoch"),
    "lease.revoke": ("member", "epoch"),
    "lease.reject": ("member",),     # surface=write|read|event, epoch=n
    # calibration observatory (obs/calib.py): the comparator's ratio left
    # the seeded band for K consecutive chunks — `term` names the largest
    # predicted term (the recalibration suspect); ratio/predicted_ms/
    # measured_ms/variant/device/jobs ride along as optional evidence so
    # the timeline CLI can answer "which job, which engine, which term,
    # when" from the journal alone.
    "calib.drift": ("engine", "term"),
    # One managed-store credential resolve/refresh attempt (faults/
    # creds.py CredentialChain._refresh — provider s3|gcs; ok=1 carries
    # the chain rung that produced the credentials in `source`, ok=0 the
    # failing exception type). Journaled only while a chaos plan is
    # recording, like fault.injected.
    "creds.refresh": ("provider",),
}

#: Event types that end a job's timeline — obs/timeline.py flags a trace
#: with none of these as the `no_terminal` anomaly.
TERMINAL_EVENTS = ("job.done", "job.cancelled", "job.error",
                   "job.quarantined")

#: Event types a revoked lease FENCES (service/lease.py FencedEvents drops
#: them at emit time; obs/timeline.py drops any that still reached a
#: journal — the bounded-flush race — at merge time). Exactly the
#: terminal/requeue-relevant vocabulary: a zombie replica limping through
#: orphaned job copies may journal hot-path engine.chunk rows (harmless,
#: ungated — gating them would put file I/O on the step path), but it can
#: never record an admission, resumption, checkpoint, or verdict the
#: timeline would mistake for the surviving copy's.
LEASE_GATED_EVENTS = TERMINAL_EVENTS + (
    "replica.admit", "job.resumed", "ckpt.write", "job.warm_start",
)

#: Finish-status string -> terminal event name. Both job vocabularies
#: (service JobStatus and fleet FleetJobStatus) spell their terminal
#: statuses "done"/"cancelled"/"error", so this is the ONE map their
#: finalizers emit through — a rename edits the vocabulary here, not in
#: per-layer copies.
TERMINAL_EVENT_BY_STATUS = {
    "done": "job.done",
    "cancelled": "job.cancelled",
    "error": "job.error",
}


#: The nested sub-dict vocabularies under `SearchResult.detail` — the ONE
#: declaration srlint's SR003 chain-walk and both validators below share;
#: a new sub-schema added here is picked up by all three.
DETAIL_SUBSCHEMAS = (
    ("service", SERVICE_DETAIL_KEYS),
    ("telemetry", TELEMETRY_KEYS),
    ("phases", PHASES_DETAIL_KEYS),
    ("faults", FAULTS_DETAIL_KEYS),
    ("corpus", CORPUS_DETAIL_KEYS),
    ("tenant", TENANT_DETAIL_KEYS),
    ("calib", CALIB_DETAIL_KEYS),
)


def all_detail_key_paths() -> set:
    """Every declared `SearchResult.detail` key path ("store", "service.
    queue_wait", ...) — the flat vocabulary the srlint undeclared-key rule
    checks literal subscripts against."""
    paths = set(DETAIL_KEYS)
    for sub, allowed in DETAIL_SUBSCHEMAS:
        paths.update(f"{sub}.{k}" for k in allowed)
    return paths


def validate_detail(detail: Optional[dict]) -> list:
    """Key paths in a `SearchResult.detail` dict that the schema does not
    name (empty list = conforming). Tests assert `== []`."""
    if detail is None:
        return []
    bad = [k for k in detail if k not in DETAIL_KEYS]
    for sub, allowed in DETAIL_SUBSCHEMAS:
        if isinstance(detail.get(sub), dict):
            bad.extend(
                f"{sub}.{k}" for k in detail[sub] if k not in allowed
            )
    return bad
