"""Async client API of the check service.

`CheckService` is the persistent front door: `submit(model, ...)` returns a
`JobHandle` immediately; a scheduler thread packs every admitted job's
frontier lanes into shared fused device steps (continuous batching — see
scheduler.py) until each job finishes, is cancelled, or times out. All jobs
share ONE device hash table via job-salted fingerprints, so a service
outlives any single check the way an inference server outlives any single
request.

Scheduling policy:

- admission: jobs wait in a priority queue; at most `max_resident` jobs
  hold lanes at once (None = unlimited — continuous batching itself is the
  fairness mechanism then).
- fairness: per-step lane grants are waterfilled round-robin across a
  group's runnable jobs, and the grant rotation advances every step.
- preemption: with `preempt_steps=N`, a job that has consumed N device
  steps since admission while others wait is parked (its frontier spilled
  through the checkpoint machinery when `spill_dir` is set) and re-queued
  behind its priority class; its visited set stays in the shared table, so
  resumption is exact.
- cancellation (`JobHandle.cancel()`) drops the job's frontier on the spot;
  its lanes are free for other jobs at the very next step — no batch drain.

Synchronous use: `CheckService(background=False)` runs no thread; tests and
scripts drive it deterministically with `pump()` / `drain()`.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

from ..core.discovery import HasDiscoveries
from ..checker.base import Checker
from ..faults.ckptio import CheckpointCorrupt
from ..faults.plan import active_plan
from ..obs import (
    REGISTRY,
    TERMINAL_EVENT_BY_STATUS,
    EventJournal,
    LogHistogram,
    Tracer,
    as_events,
    as_tracer,
    mint_trace_id,
)
from .queue import AdmissionQueue, Job, JobStatus
from .scheduler import ServiceEngine, ServiceError, StepFault, group_key
from .tenancy import DEFAULT_TENANT, QuotaExceeded, TenantQuotas


class JobHandle:
    """Client-side handle to a submitted job (the service analogue of the
    `Checker` handle a spawn returns)."""

    def __init__(self, service: "CheckService", job: Job):
        self._service = service
        self._job = job

    @property
    def id(self) -> int:
        return self._job.id

    def status(self) -> str:
        return self._job.status

    def poll(self) -> dict:
        return self._service.poll(self._job.id)

    def result(self, wait: bool = True, timeout: Optional[float] = None):
        """The job's SearchResult. Raises on cancelled/errored jobs; with
        wait=False returns None while the job is still in flight."""
        return self._service.result(self._job.id, wait=wait, timeout=timeout)

    def cancel(self) -> bool:
        return self._service.cancel(self._job.id)

    def discoveries(self) -> dict:
        """{property name: Path} — reconstructed through the shared table's
        salted parent chain (scheduler.reconstruct_path)."""
        return self._service.discovery_paths(self._job.id)

    def metrics(self) -> dict:
        return self._job.metrics.to_dict(self._job.unique_count)

    def as_checker(self) -> "ServiceChecker":
        return ServiceChecker(self)


class CheckService:
    def __init__(
        self,
        batch_size: int = 1024,
        table_log2: int = 20,
        insert_variant: str = "sort",
        store: str = "device",
        high_water: float = 0.85,
        low_water: Optional[float] = None,
        summary_log2: int = 20,
        max_resident: Optional[int] = None,
        preempt_steps: Optional[int] = None,
        spill_dir: Optional[str] = None,
        background: bool = True,
        telemetry: bool = True,
        telemetry_log2: int = 12,
        trace_out: Optional[str] = None,
        retry_limit: int = 2,
        events=None,
        events_out: Optional[str] = None,
        corpus_dir: Optional[str] = None,
        quotas: Optional[TenantQuotas] = None,
        quota_gate: bool = True,
    ):
        """`telemetry=True` records one step-metrics row per fused device
        step (obs/ring.py; digest in `stats()["telemetry"]`, `/.status`,
        and `/metrics`). `trace_out=<path>` records the service lifecycle
        (admission, fused steps, eviction, preemption, finalize) as Chrome
        trace-event JSON — flushed periodically (obs/trace.py cadence) so
        a crash leaves a loadable partial trace, and saved on `close()` —
        load it in Perfetto.

        `events` / `events_out=<path>` attach the flight recorder
        (obs/events.py): every job lifecycle transition (submit, admit,
        preempt, resume, quarantine, done/cancelled/error) and every fused
        step lands in the append-only JSONL journal, keyed by the job's
        `trace` id; `GET /jobs/<id>/events` on the HTTP front end tails
        it live. Pass an `EventJournal` to share one (the fleet's
        per-replica wiring) or a path to own one.

        `corpus_dir=<path>` turns on the cross-job warm-start corpus
        (store/corpus.py; requires `store="tiered"`): completed exhaustive
        jobs publish their visited set as a content-addressed, CRC-checked
        generation there, and a later submission whose content key (model
        definition x lowering config x finish policy) matches preloads it
        into the spill tier + Bloom summary — the repeat check completes
        ≥5x faster with bit-identical results. Fleet replicas pointed at
        ONE directory share generations (ServiceFleet(corpus_dir=...)).
        Corrupt entries are detected by the ckptio CRC footer and ignored
        (cold run, never wrong results). The corpus also powers Spec-CI
        (store/specdelta.py, `python -m stateright_tpu.ci`): an EDITED
        model definition of the same spec geometry is diffed against the
        family's per-component digests, and a properties-only or
        boundary-only edit still warm-starts on the "delta" rung.

        `quotas` (a service/tenancy.py TenantQuotas) arms per-tenant
        admission control: submissions carrying a non-default `tenant=`
        are gated on the tenant's in-flight cap and lane-seconds budget
        (over-quota raises tenancy.QuotaExceeded → HTTP 429 with
        Retry-After on the front ends), and each successful fused step
        charges its lane-seconds against the submitting tenant. The
        default tenant is never gated, so tenant-less deployments are
        unchanged. `quota_gate=False` keeps the CHARGING but disables
        the admission gate — how fleet replicas run: the FleetRouter is
        the single admission authority, and a requeued/stolen job
        re-submitted here must never bounce off a budget its first
        admission already passed (that would turn a replica death into
        a quota-shaped job loss).

        `retry_limit` is the per-group step-fault budget: a group whose
        fused step keeps failing is retried that many times (the faulted
        lanes were pushed back, so retries are exact), then each job is
        probed SOLO and only the job(s) whose step fails in isolation are
        quarantined — one poison job cannot kill its group, let alone the
        service (see scheduler.StepFault)."""
        self._trace_out = trace_out
        self._tracer = as_tracer(
            Tracer(annotate=True, out=trace_out) if trace_out else None
        )
        self._events_owned = None
        if events is None and events_out:
            events = self._events_owned = EventJournal(
                events_out, writer="service"
            )
        self._events = as_events(events)
        self._engine = ServiceEngine(
            batch_size=batch_size,
            table_log2=table_log2,
            insert_variant=insert_variant,
            store=store,
            high_water=high_water,
            low_water=low_water,
            summary_log2=summary_log2,
            telemetry=telemetry,
            telemetry_log2=telemetry_log2,
            tracer=self._tracer if trace_out else None,
            events=events,
            corpus_dir=corpus_dir,
            quotas=quotas,
        )
        self.quotas = quotas
        self._quota_gate = bool(quota_gate)
        self._quota_rejected = 0
        # Bounded recent queue-wait samples (seconds) — the autoscaler's
        # p99 admission-latency signal, appended at each first admission.
        self._queue_waits: deque = deque(maxlen=256)
        # Prometheus-shaped distributions behind the two autoscaler
        # signals: queue waits in ms, lane occupancy in 0..1. The
        # `/.status` scalars above stay; these add `*_bucket`/`_sum`/
        # `_count` text on both `/metrics` front doors.
        self._adm_hist = LogHistogram()
        self._lane_hist = LogHistogram(lo=1.0 / 128, hi=1.0)
        # Central counter registry (obs/registry.py): both HTTP front ends'
        # `/metrics` render every registered source; weakly held, so a
        # dropped service unregisters itself.
        self._metrics_name = REGISTRY.register("service", self.metrics)
        self.max_resident = max_resident
        self.preempt_steps = preempt_steps
        self.spill_dir = spill_dir
        self.retry_limit = retry_limit
        self._adm = AdmissionQueue()
        self._jobs: dict[int, Job] = {}
        # Jobs finished but not yet completed: (job, status, publish
        # payload) triples whose off-lock half (_drain_finalizers) still
        # has to run — corpus npz write, result build, event, wakeup.
        self._finalizing: list = []
        # Fire-and-forget corpus publish payloads from the NON-finalize
        # terminal/park paths (cancel, preemption, shutdown): partial
        # entries whose npz write must still happen off-lock, but whose
        # job needs no result/event completion here.
        self._publishing: list = []
        self._next_id = 1
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._idle = threading.Condition(self._lock)
        self._closed = False
        self._failed: Optional[str] = None
        self._thread = None
        if background:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    # -- client surface --------------------------------------------------------

    def submit(
        self,
        model,
        finish_when: HasDiscoveries = HasDiscoveries.ALL,
        target_state_count: Optional[int] = None,
        target_max_depth: Optional[int] = None,
        timeout: Optional[float] = None,
        priority: int = 0,
        journal: bool = False,
        resume=None,
        trace: Optional[str] = None,
        tenant: str = DEFAULT_TENANT,
    ) -> JobHandle:
        """Enqueue a check job; returns immediately. The model must be a
        TensorModel; submit the SAME model instance for jobs that should
        share a compiled step (and batch lanes) with each other.

        `journal=True` records the job's (fp, parent fp) claims host-side
        so a fleet replica can checkpoint it for requeue-resume; `resume`
        (a queue.JobResume) admits the job mid-search from such a
        checkpoint — both are the service fleet's plumbing (service/
        fleet.py), not a client-facing knob. `trace` is the flight-recorder
        correlation id: the fleet router mints one at ITS front door and
        passes it through here, so the job's events on every replica key
        to one timeline; a direct submission mints its own.

        `tenant` is the tenancy-plane identity (service/tenancy.py): it
        scopes quota enforcement, the two-level fair-share waterfill, and
        the corpus namespace. Over-quota submissions raise
        `tenancy.QuotaExceeded` (→ 429 + Retry-After on the HTTP front
        ends). The default tenant is gate-free and byte-identical to the
        pre-tenancy behavior."""
        from ..tensor.model import TensorModel

        if not isinstance(model, TensorModel):
            raise TypeError(
                "CheckService.submit requires a stateright_tpu.tensor."
                f"TensorModel; got {type(model).__name__}"
            )
        # Corpus prefetch OFF the service lock (ROADMAP item 4 leftover):
        # the content-key jaxpr trace and the entry npz read+decode happen
        # on the CLIENT thread before the lock is ever taken — a slow
        # corpus read can no longer stall an unrelated job's poll. The
        # probe Job is thrown away if admission control rejects below.
        prefetch: Optional[Job] = None
        if self._engine.has_corpus:
            prefetch = Job(
                0, model,
                finish_when=finish_when,
                target_state_count=target_state_count,
                target_max_depth=target_max_depth,
                tenant=tenant,
            )
            try:
                self._engine.prefetch_warm(prefetch)
            except Exception:  # noqa: BLE001 — warm-start is an optimization
                prefetch = None
        with self._work:
            if self._closed:
                # srlint: fault-ok caller-contract guard, not an I/O/device surface
                raise RuntimeError("service is closed")
            if self._failed:
                raise ServiceError(self._failed)
            if (
                self.quotas is not None and self._quota_gate
                and tenant != DEFAULT_TENANT
            ):
                # Live in-flight scan (no release bookkeeping to leak):
                # finished jobs simply stop counting.
                in_flight = sum(
                    1 for j in self._jobs.values()
                    if j.tenant == tenant
                    and j.status not in JobStatus.FINISHED
                )
                try:
                    self.quotas.admit(tenant, in_flight)
                except QuotaExceeded:
                    self._quota_rejected += 1
                    self._events.emit("job.quota_rejected", tenant=tenant)
                    raise
            job = Job(
                self._next_id,
                model,
                finish_when=finish_when,
                target_state_count=target_state_count,
                target_max_depth=target_max_depth,
                timeout=timeout,
                priority=priority,
                # The warm-start corpus publishes from the journal (the
                # job's full unsalted visited set), so a corpus-enabled
                # service journals every job.
                journal=journal or self._engine.has_corpus,
                resume=resume,
                trace=trace or mint_trace_id(),
                tenant=tenant,
            )
            if prefetch is not None:
                job.content_key = prefetch.content_key
                job.warm_entry = prefetch.warm_entry
                job.warm_entry_kind = prefetch.warm_entry_kind
                job.warm_checked = prefetch.warm_checked
                # The off-lock prefetch already seeded the canonical verdict
                # cache (scheduler.prefetch_warm); carry the count so the
                # real job's detail["corpus"] reports it.
                job.verdict_preloads = prefetch.verdict_preloads
                # Spec-CI rung state (scheduler._delta_lookup runs inside
                # the prefetch): the named edit class, the "delta" partial
                # kind, and the no-publish mark on widened continuations.
                job.delta_class = prefetch.delta_class
                job.partial_kind = prefetch.partial_kind
                job.no_publish = prefetch.no_publish
            self._next_id += 1
            self._jobs[job.id] = job
            self._adm.push(job)
            self._events.emit(
                "job.submitted", job=job.id, trace=job.trace,
                resumed=bool(resume) or None,
            )
            self._work.notify_all()
            return JobHandle(self, job)

    def withdraw(self, job_id: int) -> bool:
        """Atomically remove a still-QUEUED job (the fleet work-stealing
        primitive: a queued job has no table state, so moving it to another
        replica is a clean cancel-here/submit-there). Returns False once
        the job was admitted (or finished) — stealing running jobs is the
        checkpoint plane's business, not the queue's. Deliberately emits
        no journal event: the router's `fleet.steal` records the move, and
        the job's trace continues on the thief replica."""
        job = self._get(job_id)
        with self._work:
            if job.status != JobStatus.QUEUED:
                return False
            self._adm.remove(job)
            job.status = JobStatus.CANCELLED
            job.metrics.finished_at = time.monotonic()
            job.event.set()
            self._idle.notify_all()
            return True

    def poll(self, job_id: int) -> dict:
        job = self._get(job_id)
        with self._lock:
            return {
                "id": job.id,
                "status": job.status,
                "trace": job.trace,
                "state_count": job.state_count,
                "unique_state_count": job.unique_count,
                "max_depth": job.max_depth,
                "steps": job.metrics.device_steps,
                "pending_lanes": job.pending_lanes,
                "discoveries": sorted(job.discoveries),
                "error": job.error,
                "quarantined": job.quarantined,
                "metrics": job.metrics.to_dict(job.unique_count),
            }

    def result(
        self, job_id: int, wait: bool = True, timeout: Optional[float] = None
    ):
        job = self._get(job_id)
        if wait:
            if not job.event.wait(timeout):
                raise TimeoutError(f"job {job_id} still running")
        elif not job.event.is_set():
            return None
        if job.status == JobStatus.CANCELLED:
            # srlint: fault-ok caller-contract guard (cancellation is the caller's own act)
            raise RuntimeError(f"job {job_id} was cancelled")
        if job.status == JobStatus.ERROR:
            raise ServiceError(job.error or f"job {job_id} failed")
        return job.result

    def cancel(self, job_id: int) -> bool:
        """Cancel a job mid-flight. Its frontier lanes are reclaimed at the
        next scheduling round; already-inserted table entries stay (salted,
        so they shadow nothing). Returns False once the job had finished."""
        job = self._get(job_id)
        with self._work:
            if job.status in JobStatus.FINISHED:
                return False
            self._adm.remove(job)
            job.status = JobStatus.CANCELLED
            # Partial-publish what the job visited (corpus v2) BEFORE
            # retire drops the frontier and the journal is released — a
            # cancelled check's successor warm-starts from the cut.
            payload = self._engine.prepare_publish(job)
            if payload is not None:
                self._publishing.append(payload)
            self._engine.retire(job)
            job.metrics.finished_at = time.monotonic()
            job.journal = None  # finished: no checkpoint consumer
            self._events.emit(
                "job.cancelled", job=job.id, trace=job.trace
            )
            job.event.set()
            self._work.notify_all()
            self._idle.notify_all()
        self._drain_publishes()  # npz write off-lock, on the caller
        return True

    def discovery_paths(self, job_id: int) -> dict:
        job = self._get(job_id)
        with self._lock:
            return {
                name: self._engine.reconstruct_path(job, fp)
                for name, fp in job.discoveries.items()
            }

    def job_ids(self) -> list:
        with self._lock:
            return sorted(self._jobs)

    def stats(self) -> dict:
        """Service-level counters for dashboards and the HTTP `/.status`."""
        with self._lock:
            by_status: dict[str, int] = {}
            for j in self._jobs.values():
                by_status[j.status] = by_status.get(j.status, 0) + 1
            out = {
                "jobs": by_status,
                "queued": len(self._adm),
                # The autoscaler-signal pair, in the same vocabulary as
                # the fleet's per-replica rows (fleet.Replica._signal_row)
                # so one dashboard reads both deployments.
                "lane_util": round(self._engine.lane_util(), 4),
                "adm_p99_ms": self.admission_p99_ms(),
                "device_steps": self._engine.total_steps,
                "groups": len(self._engine.groups),
                "table_fill": round(
                    self._engine.hot_claims / self._engine.table.size, 4
                ),
                "store": self._engine.store_stats(),
                # Step-telemetry digest (obs/ring.py) — merged into the
                # HTTP `/.status` through this dict.
                "telemetry": self._engine.telemetry_summary(),
                # Robustness counters (step faults absorbed, exact
                # retries, quarantined poison jobs) — the service half of
                # the chaos plane's accounting.
                "faults": dict(self._engine.fault_counters),
            }
            # Warm-start corpus counters (store/corpus.py) — present only
            # on corpus-enabled services so plain deployments' `/.status`
            # stays byte-identical to before.
            corpus = self._engine.corpus_stats()
            if corpus is not None:
                out["corpus"] = corpus
            # Tenancy accounting — present only on quota-armed services,
            # so plain deployments' `/.status` stays byte-identical.
            if self.quotas is not None:
                out["tenants"] = self.quotas.snapshot()
                out["quota_rejected"] = self._quota_rejected
            # Measured-vs-predicted calibration join (obs/calib.py) —
            # present only once the comparator has closed a chunk, so
            # calibration-less deployments' `/.status` stays byte-identical.
            calib = self._engine.calib_detail()
            if calib is not None:
                out["calib"] = calib
            return out

    def lane_util(self) -> float:
        """Last fused step's batch occupancy (0..1) — the autoscaler's
        per-replica lane-utilization signal (also in snapshot_row)."""
        with self._lock:
            return self._engine.lane_util()

    def admission_p99_ms(self) -> float:
        """p99 of recent queue waits, milliseconds (0.0 before any
        admission) — the autoscaler's latency signal."""
        with self._lock:
            waits = sorted(self._queue_waits)
        if not waits:
            return 0.0
        idx = min(len(waits) - 1, int(0.99 * len(waits)))
        return round(waits[idx] * 1e3, 3)

    def store_stats(self) -> Optional[dict]:
        with self._lock:
            return self._engine.store_stats()

    def telemetry_summary(self) -> Optional[dict]:
        with self._lock:  # a scrape must not race the scheduler's appends
            return self._engine.telemetry_summary()

    def table_fill(self) -> float:
        """The shared table's fill fraction alone — the reporter's per-tick
        read, without rebuilding the whole stats()/telemetry digest."""
        with self._lock:
            return round(
                self._engine.hot_claims / self._engine.table.size, 4
            )

    def drift_ratio(self) -> Optional[float]:
        """Last closed calibration chunk's measured/predicted ratio
        (obs/calib.py) — the reporter's `drift=` read; lock-free plain
        attribute access like the fleet's signal row."""
        calib = self._engine._calib
        return calib.drift_ratio() if calib is not None else None

    def metrics(self) -> dict:
        """Flat counters for the obs registry / `GET /metrics` (service
        stats plus the engine's step digest; per-job rows stay in
        `/.status` — unbounded label cardinality does not belong in
        Prometheus gauges)."""
        out = self.stats()
        # Real histograms (registry.LogHistogram) for the two autoscaler
        # signals — render_prometheus turns each into a `*_bucket`/`_sum`/
        # `_count` triplet on both `/metrics` doors.
        out["admission_wait_ms"] = self._adm_hist
        out["lane_util_window"] = self._lane_hist
        return out

    def events_tail(
        self, job_id: Optional[int] = None, since: int = 0,
        wait_s: float = 0.0,
    ) -> tuple:
        """Flight-recorder tail (the `GET /jobs/<id>/events` long-poll
        primitive): `(events, next_cursor)` with cursor >= `since`,
        filtered to `job_id` when given. ([], since) with no recorder."""
        return self._events.tail(since=since, job=job_id, wait_s=wait_s)

    # -- scheduling ------------------------------------------------------------

    def _get(self, job_id: int) -> Job:
        with self._lock:
            try:
                return self._jobs[job_id]
            except KeyError:
                raise KeyError(f"no such job {job_id}") from None

    def _resident(self) -> list:
        return [
            j for j in self._jobs.values() if j.status == JobStatus.RUNNING
        ]

    def _has_work(self) -> bool:
        return bool(
            (len(self._adm) and self._admittable())
            or any(g.runnable() for g in self._engine.groups.values())
        )

    def _admittable(self) -> bool:
        return (
            self.max_resident is None
            or len(self._resident()) < self.max_resident
        )

    def _finalize(self, job: Job, status: str = JobStatus.DONE) -> None:
        """Mark a job finished (under the lock) and queue its completion
        work. The EXPENSIVE half of finishing — the corpus publish's npz
        write + Bloom rehash — runs OFF the service lock in
        `_drain_finalizers` (the caller's loop drains right after the lock
        is released), so a slow publish can no longer stall an unrelated
        job's poll. The job's result/event land there too, AFTER the
        publish, so `detail["corpus"]["published"]` stays truthful."""
        self._tracer.instant(
            "service.finalize", cat="service", job=job.id, status=status,
            trace=job.trace,
        )
        job.status = status
        job.metrics.finished_at = time.monotonic()
        # Under-lock half of the publish: gate + journal/frontier snapshot
        # (memory concatenation only). MUST run before retire — retire
        # drops the frontier a partial publish snapshots.
        payload = self._engine.prepare_publish(job)
        self._engine.retire(job)
        self._finalizing.append((job, status, payload))

    def _drain_publishes(self) -> None:
        """Write out fire-and-forget partial-publish payloads (cancel /
        preemption / shutdown cuts) off-lock. Chaos-covered like every
        publish: an aborted write degrades to an unpublished entry, never
        a wrong one."""
        while True:
            with self._lock:
                if not self._publishing:
                    return
                payload = self._publishing.pop(0)
            self._engine.publish_payload(payload)  # never raises

    def _drain_finalizers(self) -> None:
        """Complete every deferred finalize: publish off-lock, then (back
        under the lock) build the result, release the journal, emit the
        terminal event, and wake waiters. Called with the service lock
        NOT held (pump()/_loop() drain after releasing it; close() after
        joining the scheduler thread)."""
        self._drain_publishes()
        while True:
            with self._lock:
                if not self._finalizing:
                    return
                job, status, payload = self._finalizing.pop(0)
            published = False
            if payload is not None:
                # The slow half (Bloom rehash + crash-atomic npz write) —
                # no lock held; never raises.
                published = self._engine.publish_payload(payload)
            with self._lock:
                if payload is not None:
                    job.published = published
                job.result = self._engine.build_result(job)
                # The journal (the job's full visited set, ~16 B/state)
                # has no consumer past this point — finished jobs are
                # never checkpointed or resumed — and finished Job objects
                # stay in self._jobs for the service lifetime, so release
                # it or a long-lived corpus-enabled service (journal
                # forced on) grows with every job ever served.
                job.journal = None
                self._events.emit(
                    TERMINAL_EVENT_BY_STATUS[status],
                    job=job.id, trace=job.trace,
                    states=job.state_count, unique=job.unique_count,
                    timed_out=job.timed_out or None,
                )
                job.event.set()
                self._idle.notify_all()

    def _expire_timeouts(self) -> None:
        now = time.monotonic()
        for job in list(self._jobs.values()):
            if job.status in JobStatus.FINISHED or job.deadline is None:
                continue
            if now > job.deadline:
                self._adm.remove(job)
                job.timed_out = True
                self._finalize(job)

    def _admit_waiting(self) -> None:
        while len(self._adm) and self._admittable():
            job = self._adm.pop_next()
            if job.status == JobStatus.PREEMPTED:
                try:
                    job.load_frontier()
                except CheckpointCorrupt as e:
                    # A torn preemption spill loses ONLY this job's
                    # frontier — fail it alone instead of letting the
                    # exception escalate to the service-wide bail-out.
                    job.status = JobStatus.ERROR
                    job.error = f"preemption spill unreadable: {e}"
                    job.metrics.finished_at = time.monotonic()
                    self._events.emit(
                        "job.error", job=job.id, trace=job.trace,
                        error=job.error,
                    )
                    job.event.set()
                    self._idle.notify_all()
                    continue
                job.status = JobStatus.RUNNING
                job.steps_since_admit = 0
                self._engine.group_of(job).jobs.append(job)
                # Re-admission after a preempt: legal because the timeline
                # saw `job.preempted` in between (obs/timeline.py treats a
                # second admit WITHOUT one as the duplicate-admission
                # anomaly).
                self._events.emit(
                    "replica.admit", job=job.id, trace=job.trace,
                    preempted=True,
                )
                continue
            resumed = job.resume is not None
            try:
                with self._tracer.span(
                    "service.admit", cat="service", job=job.id,
                    trace=job.trace,
                ):
                    done = self._engine.admit(job)
            except ServiceError:
                raise
            except Exception as e:  # noqa: BLE001 — a bad model fails its job
                job.status = JobStatus.ERROR
                job.error = f"admission failed: {e}"
                job.metrics.finished_at = time.monotonic()
                self._events.emit(
                    "job.error", job=job.id, trace=job.trace, error=job.error
                )
                job.event.set()
                self._idle.notify_all()
                continue
            job.metrics.admitted_at = time.monotonic()
            qw = job.metrics.queue_wait()
            if qw is not None:
                # p99 admission-latency sample (autoscaler signal).
                self._queue_waits.append(qw)
                self._adm_hist.observe(qw * 1000.0)
            job.status = JobStatus.RUNNING
            job.steps_since_admit = 0
            # `job.resumed` (a fleet requeue continuing from its journal
            # checkpoint) vs a first admission — the timeline's crash →
            # requeue → resume hop is exactly this pair of spellings.
            self._events.emit(
                "job.resumed" if resumed else "replica.admit",
                job=job.id, trace=job.trace,
            )
            if done is not None:
                self._finalize(job)

    def _preempt_if_due(self) -> None:
        """Park the longest-running over-budget job (at most one per round)
        when waiting jobs cannot be admitted — round-robin lane grants at
        admission-queue scale."""
        if self.preempt_steps is None or not len(self._adm):
            return
        if self._admittable():
            return  # free capacity: nothing to preempt for
        head = self._adm.peek()
        due = [
            j for j in self._resident()
            if j.steps_since_admit >= self.preempt_steps
            # Never preempt for a strictly lower-priority waiter — that
            # would just swap the pair back and forth round after round.
            and head.priority >= j.priority
        ]
        if not due:
            return
        job = max(due, key=lambda j: j.steps_since_admit)
        self._tracer.instant(
            "service.preempt", cat="service", job=job.id, trace=job.trace
        )
        self._events.emit("job.preempted", job=job.id, trace=job.trace)
        g = self._engine.groups.get(group_key(job.model))
        if g is not None and job in g.jobs:
            g.jobs.remove(job)
        job.status = JobStatus.PREEMPTED
        job.metrics.preemptions += 1
        # Partial-publish the preemption cut (corpus v2) BEFORE the spill
        # drops the in-memory frontier: if this replica dies while the job
        # is parked, a successor process warm-starts from the published
        # prefix instead of cold. The npz write drains off-lock with the
        # round's other deferred completion work.
        payload = self._engine.prepare_publish(job)
        if payload is not None:
            self._publishing.append(payload)
        if self.spill_dir is not None and job.pending_lanes:
            job.spill_frontier(
                os.path.join(self.spill_dir, f"job{job.id}.frontier.npz")
            )
        self._adm.push(job)
        self._admit_waiting()

    def _handle_step_fault(self, fault: StepFault) -> None:
        """Per-group retry, then solo-probe quarantine. The faulted lanes
        were already pushed back (scheduler.step_group's unwind), so:

        1. within the retry budget, just let the next round re-step the
           group — the retry is exact;
        2. past the budget, probe each of the group's runnable jobs SOLO:
           a job whose step fails in isolation is the poison — quarantine
           it; healthy jobs keep their (exactly preserved) progress and
           resume shared batching. Unrelated groups never notice."""
        group = fault.group
        group.fault_count += 1
        if group.fault_count <= self.retry_limit:
            self._engine.fault_counters["retries"] += 1
            self._tracer.instant(
                "service.step_retry", cat="service",
                attempt=group.fault_count,
            )
            return
        group.fault_count = 0
        for job in list(group.runnable()):
            try:
                finished = self._engine.step_group(group, only=[job])
            except StepFault as probe:
                self._quarantine(job, probe)
            except ServiceError:
                raise
            else:
                for j in finished:
                    self._finalize(j)

    def _quarantine(self, job: Job, fault: StepFault) -> None:
        """Park a poison job as an ERROR with the quarantined marker; its
        table entries stay (salted — they shadow nothing) and its lanes
        free up at the next round."""
        self._tracer.instant(
            "service.quarantine", cat="service", job=job.id, trace=job.trace
        )
        job.quarantined = True
        job.status = JobStatus.ERROR
        job.error = (
            f"quarantined after repeated step faults: {fault.cause!r}"
        )
        job.metrics.finished_at = time.monotonic()
        self._engine.retire(job)
        self._engine.fault_counters["quarantined_jobs"] += 1
        self._events.emit(
            "job.quarantined", job=job.id, trace=job.trace, error=job.error
        )
        job.event.set()
        self._idle.notify_all()

    def _round(self) -> bool:
        """One scheduling round: timeouts, admission, preemption, one fused
        step of the next runnable group. Returns True if a step ran. A
        `StepFault` is absorbed here (retry/quarantine policy) — one bad
        group or job never takes the scheduler down."""
        plan = active_plan()
        if (
            plan is not None
            and self._events.enabled
            and (plan.events is None or plan.events.closed)
        ):
            # The flight recorder adopts the active chaos plan: every
            # injected fault is journaled as `fault.injected`, so a chaos
            # run is an auditable recording, not just a survived one. A
            # plan outliving a previous recorded run (its journal closed)
            # is re-adopted here rather than emitting into the dead one.
            plan.events = self._events
        self._expire_timeouts()
        self._admit_waiting()
        self._preempt_if_due()
        group = self._engine.next_group()
        if group is None:
            return False
        try:
            finished = self._engine.step_group(group)
        except StepFault as e:
            self._handle_step_fault(e)
            return True
        # Lane-occupancy sample per fused step (the distribution behind
        # the `/.status` `lane_util` point value).
        self._lane_hist.observe(self._engine.lane_util())
        for job in finished:
            self._finalize(job)
        return True

    def _loop(self) -> None:
        try:
            while True:
                with self._work:
                    while (
                        not self._closed
                        and not self._has_work()
                        and not self._finalizing
                    ):
                        # The wait doubles as the timeout poll for deadlines.
                        self._work.wait(timeout=0.05)
                        self._expire_timeouts()
                    if self._closed:
                        return
                    try:
                        self._round()
                    except ServiceError as e:
                        self._failed = str(e)
                        self._idle.notify_all()
                        return
                    except Exception as e:  # noqa: BLE001 — never die silently
                        # A scheduler bug outside the StepFault envelope
                        # used to kill this thread silently, hanging every
                        # client in result(); fail loudly instead.
                        self._failed = (
                            f"scheduler error: {type(e).__name__}: {e}"
                        )
                        self._engine._fail_all(self._failed)
                        self._idle.notify_all()
                        return
                # Off-lock: the expensive completion half (corpus publish)
                # of any jobs this round finished — polls proceed meanwhile.
                self._drain_finalizers()
        finally:
            self._drain_finalizers()  # error exits still complete waiters

    # -- foreground driving (background=False) ---------------------------------

    def pump(self, rounds: int = 1) -> int:
        """Run up to `rounds` scheduling rounds in the calling thread;
        returns how many actually dispatched a step. Deferred completion
        work (the off-lock corpus publish half) drains after the lock is
        released — a pump always leaves finished jobs fully completed."""
        ran = 0
        try:
            with self._lock:
                for _ in range(rounds):
                    try:
                        if self._round():
                            ran += 1
                        elif not self._has_work():
                            break
                    except ServiceError as e:
                        self._failed = str(e)
                        raise
        finally:
            self._drain_finalizers()
        return ran

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted job has finished."""
        deadline = None if timeout is None else time.monotonic() + timeout

        def all_done():
            with self._lock:
                return all(
                    j.status in JobStatus.FINISHED and j.event.is_set()
                    for j in self._jobs.values()
                )

        if self._thread is None:
            # Foreground: pump WITHOUT holding the lock across rounds
            # (pump takes it per burst and drains the off-lock completion
            # work between bursts — the no-stall contract applies to
            # foreground services too).
            while not all_done():
                if self._failed:
                    raise ServiceError(self._failed)
                if not self.pump(64):
                    with self._lock:
                        self._expire_timeouts()
                        idle_now = not self._has_work()
                    self._drain_finalizers()
                    if not all_done() and idle_now:
                        time.sleep(0.01)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("drain timed out")
            return
        with self._idle:
            while not all_done():
                if self._failed:
                    raise ServiceError(self._failed)
                left = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if left is not None and left <= 0:
                    raise TimeoutError("drain timed out")
                self._idle.wait(timeout=0.05 if left is None else min(left, 0.05))

    def close(self) -> None:
        """Stop the scheduler thread; queued/running jobs are cancelled."""
        with self._work:
            self._closed = True
            for job in list(self._jobs.values()):
                if job.status not in JobStatus.FINISHED:
                    self._adm.remove(job)
                    job.status = JobStatus.CANCELLED
                    # Shutdown cut: publish the visited prefix so a fresh
                    # process resumes warm (drained below, off-lock).
                    payload = self._engine.prepare_publish(job)
                    if payload is not None:
                        self._publishing.append(payload)
                    self._engine.retire(job)
                    self._events.emit(
                        "job.cancelled", job=job.id, trace=job.trace,
                        shutdown=True,
                    )
                    job.event.set()
            self._work.notify_all()
            self._idle.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        # Any finalize still deferred (scheduler died mid-drain, or a
        # foreground service closed between pumps) must complete its
        # waiters — result() clients hang on job events otherwise.
        self._drain_finalizers()
        REGISTRY.unregister(self._metrics_name)
        if self._trace_out:
            try:
                self._tracer.save(self._trace_out)
            except OSError:
                pass  # tracing must never fail a clean shutdown
        # Release a chaos plan that adopted this recorder — the plan may
        # outlive us, and its next journaled run must re-adopt a LIVE one.
        plan = active_plan()
        if plan is not None and plan.events is self._events:
            plan.events = None
        # The recorder outlives the service only when it was handed in
        # (the fleet owns its per-replica journals); an owned one closes.
        if self._events_owned is not None:
            self._events_owned.close()
        else:
            self._events.flush()


class ServiceChecker(Checker):
    """`Checker`-shaped adapter over a JobHandle — the same handle surface
    `spawn_tpu` gives (counts, discoveries, join, assertions), served by a
    shared CheckService instead of a dedicated engine. Spawn one via
    `model.checker().spawn_service(service)`."""

    def __init__(self, handle: JobHandle):
        super().__init__(handle._job.model)
        self._handle = handle

    def state_count(self) -> int:
        return self._handle._job.state_count

    def unique_state_count(self) -> int:
        return self._handle._job.unique_count

    def max_depth(self) -> int:
        return self._handle._job.max_depth

    def discoveries(self) -> dict:
        if not self._handle._job.event.is_set():
            return {}
        return self._handle.discoveries()

    def join(self) -> "ServiceChecker":
        self._handle.result(wait=True)
        return self

    def is_done(self) -> bool:
        return self._handle._job.event.is_set()

    def store_stats(self) -> Optional[dict]:
        return self._handle._service.store_stats()

    def table_fill(self) -> Optional[float]:
        return self._handle._service.table_fill()

    def drift_ratio(self) -> Optional[float]:
        return self._handle._service.drift_ratio()

    def telemetry_summary(self) -> Optional[dict]:
        return self._handle._service.telemetry_summary()
