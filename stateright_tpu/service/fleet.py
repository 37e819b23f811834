"""Replica drivers + the ServiceFleet assembly (router front door over N
CheckService replicas).

A `Replica` wraps one CheckService (one device / device-mesh worth of
shared table) in a crash-only driver: it pumps the service's scheduling
rounds, checkpoints its journaled jobs through the r10 atomic checkpoint
plane (faults/ckptio.py — every write leaves a verified `.prev`
generation), and DIES on the first unhandled fault — including the
injected `fleet.replica_crash` chaos kind and the service-wide
`ServiceError` class the single-service deployment could only abort on.
Recovery is never the replica's business: the `FleetRouter`
(service/router.py) detects the death through its health probes and
requeues the replica's jobs onto survivors from their newest intact
checkpoint generation.

`ServiceFleet` is the assembly: N replicas + one router + (background
mode) one driver thread per replica and one router supervision thread.
Foreground mode (`background=False`) runs no threads at all — tests drive
the whole fleet deterministically with `pump()` / `drain()`, the same
discipline CheckService itself uses.

    fleet = ServiceFleet(n_replicas=3, service_kwargs=dict(
        batch_size=4096, table_log2=22))
    h = fleet.submit(model, timeout=600)
    r = h.result()          # survives any single replica's death
    serve_fleet(fleet)      # HTTP front door: POST /jobs, /.status, /metrics
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Callable, Optional

from ..faults.blobstore import is_blob_uri, normalize_root
from ..faults.ckptio import fenced_savez
from ..faults.plan import FaultError, maybe_fault
from ..obs import EventJournal, as_events, as_tracer
from .api import CheckService
from .lease import (
    FencedEvents,
    LeaseRevoked,
    LeaseStore,
    load_fenced_resume,
)
from .queue import JobResume, JobStatus
from .router import (  # noqa: F401
    FleetJobStatus,
    FleetRouter,
    ReplicaDead,
    ResumeToken,
    lease_member,
    serve_fleet,
)

__all__ = ["Replica", "ServiceFleet", "serve_fleet"]


class Replica:
    """One CheckService behind a crash-only driver. The service always runs
    foreground (`background=False`) — THIS object owns the pumping, so the
    chaos plane has one seam (`fleet.replica_crash`) through which to kill
    the whole replica, and the fleet's foreground mode can drive it
    deterministically."""

    def __init__(
        self,
        idx: int,
        service_factory: Callable[[], CheckService],
        ckpt_every_spins: int = 1,
        pump_rounds: int = 4,
        tracer=None,
        events=None,
        lease=None,
    ):
        self.idx = idx
        self.service = service_factory()
        self.ckpt_every_spins = ckpt_every_spins
        self.pump_rounds = pump_rounds
        # Epoch fence (service/lease.py): every checkpoint generation this
        # driver writes is stamped + re-validated against this lease; a
        # revoked replica (the router declared it dead — possibly wrongly,
        # the zombie case) refuses its own writes and dies instead of
        # publishing stale generations for requeued jobs.
        self.lease = lease
        if lease is not None:
            corpus = getattr(self.service._engine, "_corpus", None)
            if corpus is not None:
                # The corpus write path is fenced with the same token:
                # zombie publishes refuse themselves and stale entries are
                # stamp-rejected at lookup.
                corpus.set_lease(lease)
        self.error: Optional[str] = None
        self._dead = False
        self._spins = 0
        self._ckpt_paths: dict[int, str] = {}  # inner job id -> ckpt path
        self._tracer = as_tracer(tracer)
        # Flight-recorder journal (obs/events.py) shared with this
        # replica's CheckService: the driver adds the durability events
        # (`ckpt.write`) and flushes on death so a crash's journal tail
        # survives for the forensic pass.
        self._events = as_events(events)
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Condition()

    # -- router-facing surface -------------------------------------------------

    @property
    def alive(self) -> bool:
        return not self._dead

    def submit(self, spec: dict, ckpt_path: Optional[str] = None):
        """Submit one job spec (CheckService.submit kwargs + journal/
        resume) to this replica; registers its checkpoint path with the
        driver. Raises ReplicaDead instead of touching a dead service.

        A `ResumeToken` resume is resolved HERE (the replica seam's side
        of the contract): the newest fenced checkpoint generation is
        loaded in this process — stale (revoked-epoch) generations from a
        zombie writer are rejected by the stamp check and the fallback
        generation serves instead."""
        if self._dead:
            raise ReplicaDead(
                f"replica {self.idx} is dead ({self.error})"
            )
        spec = dict(spec)
        spec.pop("model_ref", None)  # in-proc: the model object itself rides
        resume = spec.get("resume")
        if isinstance(resume, ResumeToken):
            spec["resume"] = self._resolve_resume(resume)
        handle = self.service.submit(**spec)
        if ckpt_path is not None:
            self._ckpt_paths[handle.id] = ckpt_path
        with self._wake:
            self._wake.notify_all()
        return handle

    def _resolve_resume(self, token: ResumeToken) -> Optional[JobResume]:
        """ResumeToken -> JobResume through the fenced loader; None (fresh
        restart, still exact) when no generation passes CRC + fence."""
        return load_fenced_resume(
            token.path, self.lease.store if self.lease is not None else None
        )

    def withdraw(self, inner_job_id: int) -> bool:
        """Work-stealing primitive: atomically remove a still-QUEUED job
        (see CheckService.withdraw)."""
        if self._dead:
            return False
        return self.service.withdraw(inner_job_id)

    def probe(self) -> dict:
        """Health probe (the router's `/.status`-plane check): raises on a
        dead replica, answers cheap live counters otherwise. Deliberately
        lock-free — a replica mid-compile must read as healthy, and a
        truly wedged one is caught by the router's probe deadline (the
        `fleet.replica_hang` chaos point parks right here). The
        `fleet.partition` point fires here too (and in every RemoteReplica
        HTTP request): an injected partition makes this replica
        unreachable from the router while the replica itself keeps
        running — the false-positive death the lease fence covers."""
        maybe_fault("fleet.partition", replica=self.idx)
        maybe_fault("fleet.replica_hang", replica=self.idx)
        if self._dead:
            raise ReplicaDead(
                f"replica {self.idx} is dead ({self.error})"
            )
        failed = self.service._failed
        if failed:
            raise ReplicaDead(f"replica {self.idx} service failed: {failed}")
        return {
            "replica": self.idx,
            "queued": len(self.service._adm),
            "device_steps": self.service._engine.total_steps,
            **self._signal_row(),
        }

    def _signal_row(self) -> dict:
        """The autoscaler's per-replica signal pair (lane utilization,
        p99 admission wait), read LOCK-FREE like the rest of the probe
        plane: a replica mid-compile holds the service lock and must
        still report. The racy deque snapshot degrades to empty — a
        missing sample, never a wedged probe."""
        svc = self.service
        try:
            waits = sorted(svc._queue_waits)
        except RuntimeError:  # srlint: fault-ok racy deque snapshot
            waits = []
        p99 = 0.0
        if waits:
            p99 = round(
                waits[min(len(waits) - 1, int(0.99 * len(waits)))] * 1e3, 3
            )
        row = {
            "lane_util": round(svc._engine.lane_util(), 4),
            "adm_p99_ms": p99,
        }
        # Measured/predicted cost ratio from the calibration comparator
        # (obs/calib.py) — same lock-free discipline: a plain attribute
        # read of the last closed chunk, absent until one closes.
        calib = svc._engine._calib
        if calib is not None:
            ratio = calib.drift_ratio()
            if ratio is not None:
                row["drift"] = round(ratio, 3)
        return row

    def idle(self) -> bool:
        """True iff this replica has nothing queued and nothing runnable —
        the steal-eligibility test (mirrors CheckService._has_work without
        taking the service lock)."""
        if self._dead:
            return False
        svc = self.service
        if len(svc._adm):
            return False
        try:
            return not any(
                g.runnable() for g in svc._engine.groups.values()
            )
        except RuntimeError:  # srlint: fault-ok racy dict walk reads as busy
            return False

    def snapshot_row(self) -> dict:
        """One `/.status` row. Dead replicas report liveness only — crash
        semantics say their service state is gone."""
        if self._dead:
            return {"alive": 0, "error": self.error}
        svc = self.service
        return {
            "alive": 1,
            "queued": len(svc._adm),
            "jobs": len(svc._jobs),
            "device_steps": svc._engine.total_steps,
            "spins": self._spins,
            # Per-replica autoscaler signals, also the `/.status` +
            # `/metrics` per-replica depth/utilization surface.
            **self._signal_row(),
        }

    # -- the crash-only driver -------------------------------------------------

    def spin(self) -> int:
        """One driver turn: the chaos seam, a bounded pump, and the
        checkpoint cadence. Returns rounds that dispatched work; a fault
        anywhere kills the replica (recovery is the router's job)."""
        if self._dead:
            return 0
        try:
            # Chaos-plane boundary: `fleet.replica_crash` (kind `crash`)
            # kills this replica for good — BEFORE the pump, so the last
            # written checkpoint generation is a sound resume point.
            maybe_fault("fleet.replica_crash", replica=self.idx)
            ran = self.service.pump(self.pump_rounds)
            self._spins += 1
            if self._spins % self.ckpt_every_spins == 0:
                self._checkpoint_jobs()
            return ran
        except Exception as e:  # noqa: BLE001 — crash-only: die, never limp
            self._die(e)
            return 0

    def _die(self, e: BaseException) -> None:
        self._dead = True
        self.error = f"{type(e).__name__}: {e}"
        self._tracer.instant(
            "fleet.replica_crash", cat="fleet", replica=self.idx,
            error=type(e).__name__,
        )
        # Crash-durability: push the journal tail and the partial trace to
        # disk NOW — this driver never runs again, and the flight recorder
        # exists exactly for this moment. (The `replica.crash` journal
        # event itself is the router's to write: it is the single
        # authority on fleet membership, so event counts match its
        # `replica_crashes` counter.)
        self._events.flush()
        self._tracer.flush()

    def _checkpoint_jobs(self) -> None:
        """Write one atomic generation per RUNNING journaled job. The
        snapshot is taken under the service lock (no step mutates
        mid-copy); the write happens outside it."""
        for jid, path in list(self._ckpt_paths.items()):
            job = self.service._jobs.get(jid)
            if job is None or job.status in JobStatus.FINISHED:
                self._ckpt_paths.pop(jid, None)
                continue
            if job.status != JobStatus.RUNNING or job.journal is None:
                continue
            with self.service._lock:
                arrays = job.fleet_snapshot()
            try:
                with self._tracer.span(
                    "ckpt.write", cat="fleet", job=jid, replica=self.idx,
                    trace=job.trace,
                ):
                    fenced_savez(path, arrays, lease=self.lease)
            except LeaseRevoked as e:
                # The router fenced this replica out (it declared us dead
                # and requeued our jobs — we are the zombie). The refusal
                # was counted by the lease store; record the evidence and
                # die: a fenced-out replica must never write again, and
                # crash-only semantics say it must not limp either.
                self._events.emit(
                    "lease.reject", member=lease_member(self.idx),
                    epoch=self.lease.epoch if self.lease else 0,
                    surface="write", job=jid, trace=job.trace,
                )
                self._die(e)
                return
            self._events.emit(
                "ckpt.write", job=jid, trace=job.trace, replica=self.idx
            )

    def _drive(self) -> None:
        while not self._stop and not self._dead:
            ran = self.spin()
            if not ran and not self._stop:
                with self._wake:
                    self._wake.wait(timeout=0.002)

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._drive, daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop = True
        with self._wake:
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def close(self) -> None:
        self.stop()
        if not self._dead:
            self.service.close()

    def retire_driver(self) -> None:
        """Graceful local teardown AFTER the router's scale-in drain
        (`FleetRouter.retire` already revoked the lease and requeued
        every fleet job): stop pumping, close the service, and read as
        not-alive from here on — without the crash narrative `_die`
        writes, because retirement is a decision, not a failure."""
        self.stop()
        if self._dead:
            return
        try:
            self.service.close()
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        self._dead = True
        self.error = "retired (scale-in)"


class ServiceFleet:
    """N CheckService replicas behind one consistent-hash router — the
    production deployment of the check service (ROADMAP item 1): replica
    death is routine (requeue-resume from the checkpoint plane), imbalance
    is routine (cross-replica work stealing), and the whole fleet reports
    through one `/.status` + `/metrics` plane."""

    def __init__(
        self,
        n_replicas: int = 2,
        service_kwargs: Optional[dict] = None,
        router_kwargs: Optional[dict] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_every_spins: int = 1,
        pump_rounds: int = 4,
        max_resident: Optional[int] = 8,
        background: bool = True,
        tracer=None,
        journal_dir: Optional[str] = None,
        corpus_dir: Optional[str] = None,
        lease_dir: Optional[str] = None,
        remote: bool = False,
        store_root: Optional[str] = None,
        spawn_timeout_s: float = 180.0,
        quotas=None,
    ):
        """`service_kwargs` configure every replica's CheckService
        (batch_size, table_log2, store, ...). `max_resident` bounds each
        replica's admitted jobs so overload is visible as queue depth —
        what work stealing feeds on (None disables the bound AND
        stealing's signal). `ckpt_dir` (default: a managed tempdir) holds
        the per-job requeue-resume generations.

        `journal_dir` turns on the flight recorder (obs/events.py): the
        router journals to `<journal_dir>/router.jsonl` and each replica
        (driver + its CheckService) to `<journal_dir>/replica<i>.jsonl`,
        all keyed by the per-job trace id the router mints — the input
        set for `python -m stateright_tpu.obs.timeline`.

        `corpus_dir` turns on the cross-job warm-start corpus on EVERY
        replica over the one shared directory (store/corpus.py): the
        first replica to finish a content key publishes its visited set
        as a content-addressed ckptio generation, every replica's next
        same-key submission — fresh, requeued after a crash, or stolen —
        preloads that shared generation instead of re-deriving it.
        Implies `store="tiered"` on the replica services (set here as a
        default when service_kwargs doesn't choose a store).

        `lease_dir` turns on the epoch-fenced lease plane (service/
        lease.py) for IN-PROC replicas: the router grants one lease per
        replica, revokes it before requeueing a dead replica's jobs, and
        every replica write path (checkpoint generations, terminal journal
        events) re-validates its lease — a false-positive death (hung but
        alive) can waste cycles but can never corrupt a resumed job.

        `remote=True` runs every replica as a separate PROCESS: N
        `replica_main` subprocesses (each its own `serve_service`-shaped
        HTTP server over a `Replica` driver) sharing `store_root`
        (checkpoints, journals, leases, corpus), driven through
        `RemoteReplica` HTTP stubs behind the same router. The lease plane
        and the flight recorder are always on in remote mode — they are
        what makes cross-process death declarations sound. Requires
        `background=True` (subprocesses cannot be foreground-pumped).

        `store_root` (and every *_dir) may be a ``blob://host:port[/pfx]``
        URI (faults/blobstore.py): checkpoint generations, lease records,
        corpus entries, and member-discovery records then live in the
        object store — the TRUE multi-host root, where the root URI is
        the only configuration replicas share. Journals stay local-write
        (a scratch directory) and are blob-synced at flush boundaries;
        replica addresses are discovered from ``members/`` records in the
        root (service/discovery.py) instead of hand-wired port files.

        `quotas` (service/tenancy.py `TenantQuotas`) arms the tenancy
        plane fleet-wide: the ROUTER is the single admission gate
        (per-tenant in-flight cap + lane-seconds budget → 429 with
        Retry-After over HTTP), and every in-proc replica shares the
        same ledger for lane-seconds charging with its own gate OFF
        (`quota_gate=False` — a requeued job must never bounce off a
        budget its first admission already passed). Remote replicas
        cannot share the in-memory ledger across processes, so remote
        fleets gate on the in-flight cap only."""
        if n_replicas < 1:
            raise ValueError("a fleet needs at least one replica")
        self._tracer = as_tracer(tracer)
        self._tracer_raw = tracer
        self._tmpdir = None
        self._scratch_tmp = None
        self.remote = bool(remote)
        store_root = normalize_root(store_root)
        ckpt_dir = normalize_root(ckpt_dir)
        journal_dir = normalize_root(journal_dir)
        lease_dir = normalize_root(lease_dir)
        corpus_dir = normalize_root(corpus_dir)
        self.store_root = store_root
        self.scratch_dir: Optional[str] = None
        if remote:
            if not background:
                raise ValueError(
                    "remote fleets are background-only (subprocess replicas "
                    "cannot be foreground-pumped)"
                )
            if store_root is None:
                self._tmpdir = tempfile.TemporaryDirectory(
                    prefix="srtpu-fleet-root-"
                )
                self.store_root = store_root = self._tmpdir.name
            if is_blob_uri(store_root):
                # A blob root holds the shared durable state; local-write
                # surfaces (journals, child logs) need a scratch directory
                # on THIS host, synced/irrelevant-to the blob root.
                self._scratch_tmp = tempfile.TemporaryDirectory(
                    prefix="srtpu-fleet-scratch-"
                )
                self.scratch_dir = self._scratch_tmp.name
            else:
                os.makedirs(store_root, exist_ok=True)
                self.scratch_dir = store_root
            ckpt_dir = ckpt_dir or os.path.join(store_root, "ckpt")
            journal_dir = journal_dir or os.path.join(store_root, "journal")
            lease_dir = lease_dir or os.path.join(store_root, "leases")
            if corpus_dir is None and "corpus_dir" in (service_kwargs or {}):
                corpus_dir = (service_kwargs or {}).get("corpus_dir")
        if ckpt_dir is None:
            self._tmpdir = tempfile.TemporaryDirectory(prefix="srtpu-fleet-")
            ckpt_dir = self._tmpdir.name
        if not is_blob_uri(ckpt_dir):
            os.makedirs(ckpt_dir, exist_ok=True)
        self.journal_dir = journal_dir
        self._journals: list = []
        router_journal = None
        if journal_dir is not None:
            jpath, jsync = self._journal_path("router.jsonl")
            router_journal = EventJournal(
                jpath, writer="router", sync_uri=jsync
            )
            self._journals.append(router_journal)
        # Lease plane: grants happen HERE, before any replica starts (a
        # remote member ACQUIRES the granted lease at boot; an in-proc one
        # is handed its Lease directly).
        self.lease_store = None
        router_lease = None
        if lease_dir is not None:
            self.lease_store = LeaseStore(lease_dir)
            router_lease = self.lease_store.grant("router")
        kw = dict(service_kwargs or {})
        kw.setdefault("max_resident", max_resident)
        self.quotas = quotas
        if quotas is not None and not remote:
            # Shared lane-seconds ledger on every in-proc replica —
            # charging only; the router is the single admission gate.
            kw["quotas"] = quotas
            kw["quota_gate"] = False
        if corpus_dir is not None:
            if not is_blob_uri(corpus_dir):
                os.makedirs(corpus_dir, exist_ok=True)
            kw["corpus_dir"] = corpus_dir
            kw.setdefault("store", "tiered")
        self.corpus_dir = corpus_dir
        kw["background"] = False  # the Replica driver owns the pumping
        self._service_kw = kw
        self._ckpt_every_spins = ckpt_every_spins
        self._pump_rounds = pump_rounds
        self._spawn_timeout_s = spawn_timeout_s
        self._retired: list = []  # dead incarnations replaced by rejoins
        self._incarnations: dict = {}  # idx -> rejoin count (lease-less)
        # Serializes rejoin_replica end-to-end: deadness is monotonic
        # except through rejoin, so holding this across check+grant+spawn
        # +rejoin means a lost race can never burn a fresh epoch that
        # would implicitly fence the WINNING incarnation (grant bumps the
        # member's epoch, revoking older ones).
        self._rejoin_lock = threading.Lock()

        self._procs: list = []
        if remote:
            from .remote import RemoteReplica, spawn_replica_proc

            self.replicas = []
            try:
                for i in range(n_replicas):
                    self.lease_store.grant(lease_member(i))
                    proc, url = spawn_replica_proc(
                        i, store_root, kw, timeout_s=spawn_timeout_s,
                        scratch=self.scratch_dir, live_procs=self._procs,
                    )
                    self._procs.append(proc)
                    self.replicas.append(
                        RemoteReplica(
                            i, url, proc=proc, tracer=tracer,
                            store_root=store_root,
                        )
                    )
            except BaseException:
                # A mid-boot spawn failure must not leak the replicas that
                # DID come up (full jax processes) — nobody will ever call
                # close() on a constructor that raised.
                self._kill_procs()
                for j in self._journals:
                    j.close()
                if self.lease_store is not None:
                    self.lease_store.close()
                if self._scratch_tmp is not None:
                    self._scratch_tmp.cleanup()
                if self._tmpdir is not None:
                    self._tmpdir.cleanup()
                raise
        else:
            self.replicas = [
                self._make_inproc_replica(i) for i in range(n_replicas)
            ]
        self.router = FleetRouter(
            self.replicas,
            background=background,
            ckpt_dir=ckpt_dir,
            tracer=tracer,
            events=router_journal,
            lease_store=self.lease_store,
            router_lease=router_lease,
            quotas=quotas,
            **(router_kwargs or {}),
        )
        self.background = background
        self._closed = False
        self._router_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        if background:
            for r in self.replicas:
                r.start()
            self._router_thread = threading.Thread(
                target=self._supervise, daemon=True
            )
            self._router_thread.start()

    # -- construction helpers --------------------------------------------------

    def _journal_path(self, name: str) -> tuple:
        """(local write path, blob sync URI or None) for one journal file:
        journals are always LOCAL-write (an emit must never pay a network
        round trip); on a blob journal root the local file lives in the
        scratch directory and mirrors to the root at flush boundaries."""
        jd = self.journal_dir
        if not is_blob_uri(jd):
            os.makedirs(jd, exist_ok=True)
            return os.path.join(jd, name), None
        if self.scratch_dir is None:
            self._scratch_tmp = tempfile.TemporaryDirectory(
                prefix="srtpu-fleet-scratch-"
            )
            self.scratch_dir = self._scratch_tmp.name
        local_dir = os.path.join(self.scratch_dir, "journal")
        os.makedirs(local_dir, exist_ok=True)
        return os.path.join(local_dir, name), os.path.join(jd, name)

    def _make_inproc_replica(self, i: int, rejoin: bool = False) -> Replica:
        """One in-proc Replica driver (fresh service, fresh lease epoch).
        A REJOINED incarnation journals to its own file under the writer
        name ``replica<i>@e<epoch>``: per-writer seq order stays monotonic
        across the restart (the merge contract), and the timeline fence
        tells the fenced old incarnation from this one by epoch."""
        member = lease_member(i)
        lease = (
            self.lease_store.grant(member)
            if self.lease_store is not None else None
        )
        writer, fname = member, f"replica{i}.jsonl"
        if rejoin:
            n = (
                lease.epoch if lease is not None
                else self._incarnations.get(i, 1) + 1
            )
            self._incarnations[i] = n
            writer, fname = f"{member}@e{n}", f"replica{i}.e{n}.jsonl"
        journal = None
        if self.journal_dir is not None:
            path, sync = self._journal_path(fname)
            journal = EventJournal(path, writer=writer, sync_uri=sync)
            self._journals.append(journal)
            if lease is not None:
                # Gate terminal/requeue-relevant events behind the
                # lease: a fenced-out replica's journal can no longer
                # record admissions/verdicts the timeline would trust.
                journal = FencedEvents(journal, lease)
        return Replica(
            i,
            lambda: CheckService(events=journal, **self._service_kw),
            ckpt_every_spins=self._ckpt_every_spins,
            pump_rounds=self._pump_rounds,
            tracer=self._tracer_raw,
            events=journal,
            lease=lease,
        )

    # -- replica rejoin --------------------------------------------------------

    def rejoin_replica(self, idx: int) -> bool:
        """Re-admit a dead/fenced member as a FRESH incarnation (ROADMAP
        item 1's rejoin residue): grant it a fresh lease epoch, rebuild
        the driver (in-proc) or respawn the subprocess (remote — it
        re-publishes its member-discovery record, so the router learns
        the new address from the store root alone), and hand it to
        `FleetRouter.rejoin`, which quarantines it behind probation
        probes before moving its keys back. Returns False when the member
        is still alive, or when the ``fleet.rejoin`` chaos point aborted
        the rejoin (the fresh incarnation is torn down; retry later).

        The fresh epoch is what makes a rejoin racing its own stale
        zombie safe: the moment the grant lands, the old incarnation's
        epoch fails the exact-epoch check on every fenced write/read —
        the zombie refuses itself, the rejoined member proceeds.

        Serialized (`_rejoin_lock`): two concurrent rejoins of one member
        must not both grant — the second grant would implicitly revoke
        the first incarnation's epoch and silently fence the winner."""
        with self._rejoin_lock:
            return self._rejoin_replica_locked(idx)

    def _rejoin_replica_locked(self, idx: int) -> bool:
        old = self.replicas[idx]
        if idx not in self.router._dead:
            # The ROUTER's verdict is the one that matters: only a
            # declared-dead member may rejoin (the old PROCESS may well
            # still be alive — the zombie case; its stale epoch is what
            # the fresh grant fences). A racing rejoin that already won
            # also lands here — and critically, nothing is GRANTED for a
            # member the router still considers a member.
            return False
        try:
            # Chaos boundary: BEFORE the grant and the spawn, so an
            # injected fault aborts the rejoin with nothing changed —
            # not even a burned lease epoch.
            maybe_fault("fleet.rejoin", replica=idx)
        except FaultError:
            return False
        member = lease_member(idx)
        proc = None
        if self.remote:
            from .remote import RemoteReplica, spawn_replica_proc

            lease = self.lease_store.grant(member)
            proc, url = spawn_replica_proc(
                idx, self.store_root, self._service_kw,
                timeout_s=self._spawn_timeout_s,
                scratch=self.scratch_dir,
                incarnation=lease.epoch,
                live_procs=self._procs,
            )
            new = RemoteReplica(
                idx, url, proc=proc, tracer=self._tracer_raw,
                store_root=self.store_root,
            )
        else:
            new = self._make_inproc_replica(idx, rejoin=True)
        if not self.router.rejoin(new):
            # Injected fleet.rejoin fault (or a racing recovery): tear the
            # fresh incarnation down — the member stays dead, nothing
            # leaks, and the caller retries on its own cadence.
            if proc is not None:
                self._kill_one(proc)
            else:
                new.close()
            return False
        self.replicas[idx] = new
        self._retired.append(old)
        if proc is not None:
            self._procs.append(proc)
        if self.background:
            new.start()
        if self.lease_store is not None:
            epoch, _state = self.lease_store.state(member)
            self.router._events.emit(
                "lease.grant", member=member, epoch=epoch
            )
        return True

    # -- autoscaling (service/autoscale.py drives these) -----------------------

    def scale_out(self) -> Optional[int]:
        """Grow the fleet by one replica at the next free index. The new
        member enters through `FleetRouter.rejoin`'s brand-new-index door:
        registered, leased, probed — but quarantined behind the same
        probation the rejoin path uses, so a flapping new member never
        receives work it would immediately orphan. Journals
        `fleet.scale_out`; counts `scale_outs`. Returns the new index, or
        None when the ``fleet.autoscale`` chaos point aborted the grow —
        which fires FIRST, before the grant and the spawn, so an injected
        fault changes literally nothing (not even a burned epoch).

        Shares `_rejoin_lock` with rejoin_replica: membership growth and
        member recovery are serialized against each other."""
        with self._rejoin_lock:
            try:
                maybe_fault("fleet.autoscale", action="scale_out")
            except FaultError:
                return None
            idx = len(self.replicas)
            member = lease_member(idx)
            proc = None
            if self.remote:
                from .remote import RemoteReplica, spawn_replica_proc

                self.lease_store.grant(member)
                proc, url = spawn_replica_proc(
                    idx, self.store_root, self._service_kw,
                    timeout_s=self._spawn_timeout_s,
                    scratch=self.scratch_dir,
                    live_procs=self._procs,
                )
                new = RemoteReplica(
                    idx, url, proc=proc, tracer=self._tracer_raw,
                    store_root=self.store_root,
                )
            else:
                new = self._make_inproc_replica(idx)
            if not self.router.rejoin(new):
                # Unreachable for a brand-new index today; keep the same
                # no-leak teardown discipline as rejoin_replica anyway.
                if proc is not None:
                    self._kill_one(proc)
                else:
                    new.close()
                return None
            self.replicas.append(new)
            if proc is not None:
                self._procs.append(proc)
            if self.background:
                new.start()
            if self.lease_store is not None:
                epoch, _state = self.lease_store.state(member)
                self.router._events.emit(
                    "lease.grant", member=member, epoch=epoch
                )
            return idx

    def scale_in(self, idx: Optional[int] = None) -> Optional[int]:
        """Retire one replica — by default the least-loaded healthy
        member (ties retire the newest index). Loss-free by construction:
        the replica's RUNNING journaled jobs get one final checkpoint
        generation (in-proc; remote drivers checkpoint every spin
        anyway), then `FleetRouter.retire` revokes the lease, drains the
        backlog onto survivors (resumed where a generation exists), and
        only then is the local driver stopped. Journals `fleet.scale_in`;
        counts `scale_ins`. Returns the retired index, or None when
        there is no eligible member (never drains below one healthy
        replica) or the ``fleet.autoscale`` chaos point aborted the
        retirement — fired FIRST, so an injected fault leaves the fleet
        exactly as it was."""
        with self._rejoin_lock:
            try:
                maybe_fault("fleet.autoscale", action="scale_in")
            except FaultError:
                return None
            if idx is None:
                idx = self._scale_in_candidate()
            if idx is None or not (0 <= idx < len(self.replicas)):
                return None
            r = self.replicas[idx]
            if not self.remote and r.alive:
                # Final flush BEFORE the lease revoke inside retire():
                # after the revoke this driver's own writes would refuse
                # themselves, and the drain would restart instead of
                # resume.
                try:
                    r._checkpoint_jobs()
                except Exception:  # noqa: BLE001 — flush is best-effort
                    pass
            if not self.router.retire(idx):
                return None
            if self.remote:
                r.stop()  # completion mirror: the handles were requeued
                if getattr(r, "proc", None) is not None:
                    self._kill_one(r.proc)
            else:
                r.retire_driver()
            # The slot stays occupied (self.replicas is index-addressed;
            # the router keeps reporting the member as a dead row, and
            # close() reaps it from the list) — a later scale_out grows
            # at the NEXT index, and rejoin_replica can even resurrect
            # this one.
            return idx

    def _scale_in_candidate(self) -> Optional[int]:
        """Least-loaded healthy member by unfinished fleet-job count;
        probation/draining members are ineligible (mid-transition), and
        the last healthy member is never a candidate."""
        router = self.router
        with router._lock:
            live = [
                i for i in router.replicas
                if i not in router._dead
                and i not in router._draining
                and i not in router._probation
                and router.replicas[i].alive
            ]
            if len(live) <= 1:
                return None
            load: dict[int, int] = {}
            for fj in router._jobs.values():
                if (
                    fj.status not in FleetJobStatus.FINISHED
                    and fj.replica is not None
                ):
                    load[fj.replica] = load.get(fj.replica, 0) + 1
        return min(live, key=lambda i: (load.get(i, 0), -i))

    # -- client surface --------------------------------------------------------

    def submit(self, model, **opts):
        return self.router.submit(model, **opts)

    def stats(self) -> dict:
        return self.router.stats()

    def store_stats(self) -> Optional[dict]:
        rows = [
            r.service.store_stats()
            for r in self.replicas
            if r.alive and getattr(r, "service", None) is not None
        ]
        rows = [s for s in rows if s]
        return rows[0] if len(rows) == 1 else (rows or None)

    # -- foreground driving ----------------------------------------------------

    def pump(self, rounds: int = 1) -> int:
        """Foreground mode: drive every live replica and one router tick
        per round; returns how many replica pumps dispatched work."""
        ran = 0
        for _ in range(rounds):
            for r in self.replicas:
                if r.alive:
                    ran += 1 if r.spin() else 0
            self.router.tick()
        return ran

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every fleet job has finished (requeues included)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self.router.all_done():
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError("fleet drain timed out")
            if self.background:
                time.sleep(0.005)  # router/replica threads make progress
            else:
                self.pump(4)

    @staticmethod
    def _kill_one(p) -> None:
        """SIGTERM first (the child drains + flushes its journal), then
        the hard kill — teardown must never hang on a wedged child."""
        try:
            if p.poll() is None:
                p.terminate()
                p.wait(timeout=10.0)
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        try:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=5.0)
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass

    def _kill_procs(self) -> None:
        """Stop every replica subprocess (rejoined incarnations included)."""
        for p in self._procs:
            self._kill_one(p)

    def _supervise(self) -> None:
        while not self._stop.is_set():
            self.router.tick()
            self._stop.wait(timeout=0.01)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._router_thread is not None:
            self._router_thread.join(timeout=5.0)
            self._router_thread = None
        for r in list(self.replicas) + self._retired:
            r.close()
        self.router.close()
        self._kill_procs()
        for j in self._journals:
            j.close()
        if self.lease_store is not None:
            self.lease_store.close()
        if self._scratch_tmp is not None:
            self._scratch_tmp.cleanup()
            self._scratch_tmp = None
        if self._tmpdir is not None:
            self._tmpdir.cleanup()
            self._tmpdir = None
