"""Continuous-batching engine of the multi-job check service.

The inference-serving idea (Orca-style continuous batching), translated to
model checking: ONE device-resident visited set (hash table + optional
tiered spill store) is shared by every co-resident job, and each fused
device step packs frontier lanes from MANY jobs — admitted, preempted, and
retired between steps without draining anything.

Sharing is sound because every key the table sees is job-salted
(tensor/fingerprint.salt_fp): a bijection per job keeps within-job dedup
bit-identical to a standalone run while making cross-job collisions exactly
as (im)probable as any two unrelated 64-bit fingerprints.

Job-to-batch packing ("groups"): lanes in one fused step must share one
`TensorModel.expand` kernel, so jobs are grouped by model instance — jobs
of the same model share batches lane-by-lane (the continuous-batching win:
four small same-model jobs fill one batch four deep instead of running four
quarter-full searches), while distinct models time-share the device
round-robin, all against the one shared table.

Per-batch bookkeeping mirrors FrontierSearch.run (tensor/frontier.py)
order-for-order per job — property discovery scan, eventually-bit
clear/terminal check, early exit BEFORE count accumulation, suspect
resolution, successor append, spill eviction. Parity argument: a job's
queue order is INVARIANT to lane-grant segmentation (successors append in
queue order whatever the batch boundaries), so for a job that runs to
exhaustion the counts, discovery fingerprints (first sat state in pop
order), and reconstructed paths are bit-identical to a standalone run —
even mid-multiplex. The one segmentation-sensitive quantity is the
discarded final-batch contribution of an EARLY-EXITING job (all
properties found): its discovery set is still exact, but its state_count
can differ from a standalone run by the lanes that shared its last batch.
"""

from __future__ import annotations

import time
import weakref
from functools import partial
from typing import Hashable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.model import Expectation
from ..faults.plan import maybe_fault
from ..knobs import STORE_KINDS, WARM_KINDS
from ..store import warm as warm_seam
from ..obs import REGISTRY, StepRing, as_events, as_tracer
from ..tensor.fingerprint import pack_fp, salt_fp, unpack_fp
from ..tensor.frontier import (
    FrontierSearch,
    SearchResult,
    compact_flags,
    compact_new,
    expand_insert,
    replay_fp_chain,
    seed_init,
)
from .queue import Job, JobResume, JobStatus


def _build_service_step(model, K, props, insert, store):
    """The fused multi-job step: property masks, expand, salted visited-set
    insert, successor compaction, Bloom suspect marking — FrontierSearch's
    step plus per-lane job salts and per-row generated counts.

    Suspects are detected on the SALTED keys — the spill tier stores table
    keys, and the salt is what keeps one job's spilled states from
    shadowing another's. expand_insert probes the summary on exactly those
    keys (fused into the Pallas kernel's own partition pass when that
    insert is selected — salting happens before routing, so the kernel's
    disjoint hash-bit layout sees only salted bits)."""
    tiered = store is not None
    s_cfg = (
        (store.config.summary_log2, store.config.summary_hashes)
        if tiered
        else None
    )

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(t_lo, t_hi, p_lo, p_hi, states, lo, hi, salt_lo, salt_hi,
             active, summary):
        prop_masks = (
            jnp.stack([p.condition(model, states) for p in props])
            if props
            else jnp.zeros((0, K), dtype=bool)
        )
        (
            t_lo, t_hi, p_lo, p_hi,
            flat, slo, shi, is_new, suspect,
            gen_rows, has_succ, ovf,
        ) = expand_insert(
            model, t_lo, t_hi, p_lo, p_hi, states, lo, hi, active,
            insert=insert, salt_lo=salt_lo, salt_hi=salt_hi,
            summary=summary if tiered else None,
            summary_cfg=s_cfg,
        )
        out_states, out_lo, out_hi, out_src, new_count = compact_new(
            flat, slo, shi, is_new
        )
        out_sus = compact_flags(suspect, is_new)
        return (
            t_lo, t_hi, p_lo, p_hi,
            out_states, out_lo, out_hi, out_src, out_sus,
            new_count, gen_rows, has_succ, ovf, prop_masks,
        )

    return step


def group_key(model) -> Hashable:
    """The key of a model's group (its compiled step and its batches): the
    model's definition (`TensorModel.definition_key()`), so that jobs of
    equal models share one group whichever instance each was submitted
    with; the instance itself where the model names no definition."""
    key = model.definition_key()
    return ("instance", id(model)) if key is None else key


class _Group:
    """Jobs sharing one model definition (and therefore one compiled
    step; `group_key`)."""

    def __init__(self, model, K, insert, store):
        self.model = model
        self.fault_count = 0  # consecutive step faults (service retry policy)
        self.props = model.properties()
        self.prop_is = {
            "always": [
                i for i, p in enumerate(self.props)
                if p.expectation == Expectation.ALWAYS
            ],
            "sometimes": [
                i for i, p in enumerate(self.props)
                if p.expectation == Expectation.SOMETIMES
            ],
            "eventually": [
                i for i, p in enumerate(self.props)
                if p.expectation == Expectation.EVENTUALLY
            ],
        }
        self.step = _build_service_step(model, K, self.props, insert, store)
        self.jobs: list[Job] = []
        self.rr = 0  # lane-grant rotation pointer

    def runnable(self) -> list:
        return [
            j for j in self.jobs
            if j.status == JobStatus.RUNNING and j.pending_lanes
        ]


class ServiceError(RuntimeError):
    """The shared device state is unusable (table overflow without a spill
    tier); every in-flight job was failed with this message. This is the
    ONLY failure class with service-wide blast radius — a step exception in
    one group raises `StepFault` instead and fails/quarantines only that
    group's jobs."""


class StepFault(RuntimeError):
    """One group's fused step failed BEFORE any shared state changed: the
    lanes it had taken were pushed back to the front of each job's
    frontier, so the step is exactly retriable. Carries the group and the
    original cause; the owning CheckService applies the per-job retry /
    poison-quarantine policy."""

    def __init__(self, group: "_Group", cause: BaseException):
        super().__init__(
            f"service step fault in group {type(group.model).__name__}: "
            f"{type(cause).__name__}: {cause}"
        )
        self.group = group
        self.cause = cause


class ServiceEngine:
    """Shared device state + step execution. Not thread-safe by itself —
    the owning CheckService serializes access."""

    # Same visited-set designs the standalone engines race.
    INSERT_VARIANTS = FrontierSearch.INSERT_VARIANTS
    # Corpus warm ladder: the ONE kind vocabulary (knobs.WARM_KINDS) and
    # the ONE preload/soundness seam (store/warm.py) — alias identity
    # pinned by knobs.check_registry, like INSERT_VARIANTS above.
    WARM_KINDS = WARM_KINDS
    WARM_SEAM = warm_seam

    def __init__(
        self,
        batch_size: int = 1024,
        table_log2: int = 20,
        insert_variant: str = "sort",
        store: str = "device",
        high_water: float = 0.85,
        low_water: Optional[float] = None,
        summary_log2: int = 20,
        telemetry: bool = True,
        telemetry_log2: int = 12,
        tracer=None,
        events=None,
        corpus_dir: Optional[str] = None,
        quotas=None,
    ):
        self.batch_size = batch_size
        if insert_variant not in self.INSERT_VARIANTS:
            raise ValueError(
                f"insert_variant must be one of "
                f"{sorted(self.INSERT_VARIANTS)}, got {insert_variant!r}"
            )
        self.insert_variant = insert_variant
        # Variant-aware handle (PallasHashTable for "pallas", so job
        # seeding probes the variant's own slot layout) + the shared
        # tiling guard — both defined once in tensor/inserts.py.
        from ..tensor.inserts import make_table

        self.table = make_table(insert_variant, table_log2)
        # Step telemetry (obs/ring.py): the scheduler is host-orchestrated,
        # so every per-step scalar the row needs is already fetched — the
        # ring adds no device work. One ring for the engine lifetime (a
        # service is a long-lived server; totals are monotonic, retention
        # keeps the last 2^telemetry_log2 step rows).
        self._ring = StepRing(1 << telemetry_log2) if telemetry else None
        self._tracer = as_tracer(tracer)
        # Flight recorder (obs/events.py): one `engine.chunk` journal event
        # per fused device step — the engine-level rung of a job's
        # cross-replica timeline (NULL_EVENTS = free when off).
        self._events = as_events(events)
        if store not in STORE_KINDS:  # knob universe: knobs.py
            raise ValueError(f"store must be one of {STORE_KINDS}, got {store!r}")
        self.store = store
        self._store = None
        self._spill_trigger = 0
        if store == "tiered":
            from ..store.tiered import TieredConfig, TieredStore

            self._store = TieredStore(
                self.table.size,
                TieredConfig(
                    high_water=high_water,
                    low_water=low_water,
                    summary_log2=summary_log2,
                ),
            )
            # One-batch headroom, exactly like FrontierSearch: eviction only
            # runs between steps, and a step can claim K*A slots. The K*A
            # bound is per GROUP model; use the max as groups appear.
            self._spill_trigger = self._store.high_slots
        # THE dispatch table (tensor/inserts.py): the step insert carries
        # the tiered store's fused Bloom probe when the variant supports it
        # (pallas); job seeding goes through self.table.insert instead.
        from ..tensor.inserts import resolve_insert

        self._insert = resolve_insert(
            insert_variant,
            summary_cfg=(
                (
                    self._store.config.summary_log2,
                    self._store.config.summary_hashes,
                )
                if self._store is not None
                else None
            ),
        )
        self._no_summary = jnp.zeros(1, dtype=jnp.uint32)
        # Cross-job warm-start corpus (store/corpus.py, ROADMAP item 4):
        # published visited sets keyed by (model definition, lowering,
        # finish policy), preloaded into the tiered store at admission.
        # The dedup mechanism IS the tiered suspect path, so the corpus
        # requires the tiered store.
        self._corpus = None
        self._corpus_keys: dict = {}
        if corpus_dir is not None:
            if self._store is None:
                raise ValueError(
                    "corpus_dir warm-start requires store='tiered' (known "
                    "states are dedup-filtered through the spill tier's "
                    "Bloom suspect path)"
                )
            from ..store.corpus import CorpusStore

            self._corpus = CorpusStore(
                corpus_dir,
                summary_log2=self._store.config.summary_log2,
                summary_hashes=self._store.config.summary_hashes,
            )
        self.hot_claims = 0
        # Calibration comparator (obs/calib.py): created lazily on the
        # first group (the prediction needs a model geometry) and
        # re-pointed as groups change; drift events journal through this
        # engine's flight recorder with the active jobs' trace ids, and
        # observation records flush into the corpus root when one exists.
        self._calib = None
        self._calib_root = corpus_dir
        self.groups: dict[Hashable, _Group] = {}
        self._group_rr: list = []
        # Robustness accounting (surfaced in stats()["faults"] and each
        # job result's detail["faults"] — obs/schema.py FAULTS_DETAIL_KEYS).
        self.fault_counters = {
            "step_faults": 0,
            "retries": 0,
            "quarantined_jobs": 0,
        }
        self.total_steps = 0
        # Tenancy plane (service/tenancy.py): the shared lane-seconds
        # ledger jobs charge after each successful step (None = free).
        self.quotas = quotas
        # Lanes the LAST fused step actually carried — the autoscaler's
        # lane-utilization signal (last_active_lanes / batch_size),
        # always available even with telemetry off.
        self.last_active_lanes = 0
        self._table_stamp = 0  # bumped per step; parent-map cache key
        self._parent_map = None
        self._parent_map_stamp = -1

    # -- admission / retirement ------------------------------------------------

    def group_of(self, job: Job) -> _Group:
        key = group_key(job.model)
        g = self.groups.get(key)
        if g is None:
            g = _Group(job.model, self.batch_size, self._insert, self._store)
            self.groups[key] = g
            self._group_rr.append(key)
            if self._store is not None:
                ka = self.batch_size * job.model.max_actions
                self._spill_trigger = min(
                    self._spill_trigger, self.table.size - ka
                )
                if self._spill_trigger <= self._store.low_slots:
                    raise ValueError(
                        "table too small for tiered spilling at this batch: "
                        f"table {self.table.size} minus one batch of claims "
                        f"({ka}) leaves no room above the low-water mark "
                        f"({self._store.low_slots} slots); raise table_log2 "
                        "or lower batch_size/low_water"
                    )
            self._configure_calib()
        return g

    def _configure_calib(self) -> None:
        """(Re)point the comparator at the widest live group geometry —
        the fused step is padded to the max (lanes, max_actions) across
        groups, which is exactly what the costmodel should price."""
        from ..obs.calib import calib_enabled

        if self._ring is None or not calib_enabled() or not self.groups:
            return
        lanes = max(g.model.lanes for g in self.groups.values())
        acts = max(g.model.max_actions for g in self.groups.values())
        if self._calib is None:
            from ..obs.calib import CalibConfig, Comparator
            from ..tensor.costmodel import ENGINE_VARIANTS

            self._calib = Comparator(
                CalibConfig(
                    # The calib source tag for the SERVICE plane's fused
                    # step — deliberately outside the four device-engine
                    # spines the knob registry names.
                    engine="service",  # srlint: knob-ok calib source label
                    variant=ENGINE_VARIANTS.get(
                        ("split", self.insert_variant), "split"
                    ),
                    lanes=lanes,
                    max_actions=acts,
                    batch=self.batch_size,
                    table_log2=self.table.size.bit_length() - 1,
                    spill=self._store is not None,
                ),
                events=self._events,
                record_root=self._calib_root,
            )
            REGISTRY.register("calib", self._calib.metrics)
        else:
            self._calib.configure(lanes, acts)

    # -- warm-start corpus -----------------------------------------------------

    @property
    def has_corpus(self) -> bool:
        return self._corpus is not None

    def corpus_stats(self) -> Optional[dict]:
        return None if self._corpus is None else self._corpus.metrics()

    def _content_key_for(self, job: Job) -> str:
        """The job's corpus content address (see `_key_and_components_for`)."""
        return self._key_and_components_for(job)[0]

    def _components_for(self, job: Job) -> dict:
        """The job's factored content-key components (corpus v2: the
        family index's near-match vocabulary)."""
        return self._key_and_components_for(job)[1]

    def _key_and_components_for(self, job: Job) -> tuple:
        """The job's corpus content address: model definition hash x the
        engine lowering/table config x the job's finish policy — exactly
        the inputs that determine a cold run's visited set and result —
        plus the same address factored into its near-match components
        (store/corpus.key_components). Cached per (model definition, or
        instance where it names none; finish signature; tenant): the jaxpr
        trace behind the definition hash costs milliseconds and
        submissions repeat.

        A non-default tenant salts the key AND the factored "def"
        component (store/corpus.py) — per-tenant corpus namespaces, so
        one tenant's published entries never warm another's runs. The
        default tenant's keys are byte-identical to pre-tenancy."""
        from ..store.corpus import (
            content_key, finish_signature, key_components,
        )
        from .tenancy import tenant_salt

        fin = finish_signature(
            job.finish_when, job.target_state_count, job.target_max_depth
        )
        salt = tenant_salt(getattr(job, "tenant", None))
        gk = group_key(job.model)
        by_instance = job.model.definition_key() is None
        sig = (gk, fin, salt)
        hit = self._corpus_keys.get(sig)
        # Same recycled-id() guard as specdelta._COMPONENT_CACHE for a key
        # by instance: the cached key only serves if the weakly-held model
        # is the SAME object — a stale hit after id reuse would preload the
        # wrong corpus. A definition names its model by value.
        if hit is not None and (not by_instance or hit[0]() is job.model):
            return hit[1], hit[2]
        cfg = self._store.config
        lowering = {
            "batch_size": self.batch_size,
            "table_log2": self.table.size.bit_length() - 1,
            "insert_variant": self.insert_variant,
            "store": self.store,
            "summary_log2": cfg.summary_log2,
            "summary_hashes": cfg.summary_hashes,
            "finish": fin,
        }
        key = content_key(job.model, lowering, tenant=salt)
        comp = key_components(job.model, lowering, tenant=salt)
        if not by_instance:
            self._corpus_keys[sig] = (None, key, comp)
            return key, comp
        try:
            self._corpus_keys[sig] = (weakref.ref(job.model), key, comp)
        except TypeError:
            pass  # weakref-less exotic model: re-derive next time
        return key, comp

    def prefetch_warm(self, job: Job) -> None:
        """The OFF-LOCK half of warm-start (ROADMAP item 4 leftover):
        compute the job's content key and read+decode the corpus entry
        npz WITHOUT the service lock held — CheckService.submit calls this
        from the client thread before it ever takes the lock, so a slow
        corpus read can never stall an unrelated job's poll. Only
        immutable engine config and the (internally locked) CorpusStore
        are touched; the decoded entry parks on the job and the
        under-lock `_maybe_warm` consumes it at admission without I/O."""
        if self._corpus is None or job.warm is not None:
            return
        if job.content_key is None:
            job.content_key = self._content_key_for(job)
        job.warm_checked = True
        entry, kind = self._warm_lookup(job)
        job.warm_entry = entry
        job.warm_entry_kind = kind
        if entry is not None and entry.complete:
            # Dedup-first semantics: seed the canonical verdict cache HERE,
            # still on the client thread — inserting a 2^16-entry packed
            # table under the service lock would stall unrelated polls, the
            # same invariant the publish side honors (publish_payload).
            # Verdict bits are class-addressed, so preloading before the
            # job is admitted (or even if it never is) cannot be wrong.
            job.verdict_preloads = self._corpus.preload_verdicts(entry)

    def _warm_lookup(self, job: Job):
        """The corpus-v2 warm ladder, best rung first (knobs.WARM_KINDS;
        soundness rules in store/warm.py): (1) "exact" — a complete entry
        under this job's own content key (key identity IS the gate: the
        key already encodes batch + finish); (2) "partial" — this key's
        own partial entry, continuable; (3) "near" — a family entry with
        the same definition hash and a different table packing, replayed
        when complete (same batch + finish) or continued when partial;
        (4) "delta" — the Spec-CI rung (store/specdelta.py): a family
        entry under a DIFFERENT definition hash of the same spec
        geometry, salvaged when the factored component digests prove the
        edit was properties-only or boundary-only (expand/init edits
        refuse — counted, cold, never wrong).
        Returns (entry, kind) or (None, None) — every miss, gate decline,
        corrupt entry, or injected `corpus.load` fault means cold."""
        from ..store.corpus import finish_signature

        entry = self._corpus.lookup(job.content_key)
        if entry is not None and entry.complete:
            return entry, "exact"
        props = list(job.model.properties())
        entry = self._corpus.lookup_partial(job.content_key)
        if entry is not None and warm_seam.can_continue(
            entry, self.batch_size, job.finish_when, props,
            job.target_state_count, job.target_max_depth,
        ):
            return entry, "partial"
        comp = self._components_for(job)
        entry = self._corpus.lookup_near(comp, exclude=(job.content_key,))
        if entry is not None:
            if entry.complete and warm_seam.can_replay(
                entry,
                self.batch_size,
                finish_signature(
                    job.finish_when, job.target_state_count,
                    job.target_max_depth,
                ),
            ):
                return entry, "near"
            if not entry.complete and warm_seam.can_continue(
                entry, self.batch_size, job.finish_when, props,
                job.target_state_count, job.target_max_depth,
            ):
                return entry, "partial"
        if comp.get("core"):
            entry, kind = self._delta_lookup(job, comp)
            if entry is not None:
                return entry, kind
        return None, None

    def _delta_lookup(self, job: Job, comp: dict):
        """The Spec-CI "delta" rung: walk the spec index (entries sharing
        this job's spec GEOMETRY — class/lanes/max_actions — under a
        different definition hash), classify each candidate's edit from
        the factored component digests, and salvage the best-supported
        one (store/warm.salvage_delta → store/specdelta). Candidates are
        ordered largest-visited-set-first so the salvage that saves the
        most work is tried first; unsalvageable classes and declined
        salvages are counted as `delta_refusals` (the CI driver's "this
        edit is provably cold" signal). A salvaged PARTIAL (boundary
        widening) marks the job no-publish: its traversal-order
        statistics are not cold-bit-identical (specdelta docstring)."""
        new_comps = comp.get("comps")
        if not isinstance(new_comps, dict):
            return None, None
        from ..store import specdelta

        refusals = 0
        members = [
            m for m in self._corpus.spec_members(comp["core"])
            if m.get("def") != comp.get("def")
            and m.get("complete")
            and int(m.get("batch_size", -1)) == self.batch_size
        ]
        members.sort(key=lambda m: int(m.get("states", 0)), reverse=True)
        for m in members[:8]:
            # Classify from the INDEX row first: a cheap digest diff
            # avoids decoding candidate npz files that can never serve
            # (pre-delta rows without a component vector land here too —
            # classified unsalvageable, never misclassified).
            cls = specdelta.classify(new_comps, m.get("comps"))
            if cls not in ("properties-only", "boundary-only"):
                refusals += 1
                continue
            entry = self._corpus.lookup(m.get("key"))
            if entry is None:
                continue  # corrupt/GC'd npz: not an edit-class refusal
            cls, served = warm_seam.salvage_delta(
                entry, job.model, new_comps, self.batch_size,
                job.finish_when, job.target_state_count,
                job.target_max_depth,
            )
            if served is None:
                refusals += 1
                continue
            job.delta_class = cls
            if not served.complete:
                job.partial_kind = "delta"
                job.no_publish = True
            self._corpus.note_delta_hit(
                specdelta.component_reuse(new_comps, m.get("comps"))
            )
            if refusals:
                self._corpus.note_delta_refusal(refusals)
            return served, "delta"
        if refusals:
            self._corpus.note_delta_refusal(refusals)
        return None, None

    def _maybe_warm(self, job: Job) -> None:
        """Corpus preload at admission. On a replayable (complete) hit,
        the published visited set lands in the spill tier + Bloom summary
        RE-SALTED with this job's salt (so co-resident jobs never see
        each other's preload) and the publisher's result metadata is kept
        on the job for the completion-time replay. A continuable PARTIAL
        hit parks the entry on `job.partial_entry` instead — `admit`
        converts it into a resume payload and takes the journal-reseed
        path. The entry itself was prefetched OFF the service lock
        (`prefetch_warm`); only the device/host preload — engine state —
        happens here. Every failure mode — miss, corrupt entry, injected
        `corpus.load` fault — degrades to a cold run."""
        if self._corpus is None:
            return
        if job.content_key is None:
            job.content_key = self._content_key_for(job)
        if job.warm is not None:
            return  # already preloaded (re-admission path)
        prefetched = job.warm_checked
        entry, kind = job.warm_entry, job.warm_entry_kind
        job.warm_entry = None
        job.warm_entry_kind = None
        if entry is None and not job.warm_checked:
            # No prefetch reached this admission (direct engine use): one
            # inline ladder walk. A prefetch that MISSED (or was degraded
            # by an injected corpus.load fault) is never retried here —
            # the chaos plane's "fault => cold run" contract stands.
            entry, kind = self._warm_lookup(job)
            job.warm_checked = True
        if entry is None:
            return
        if not entry.complete:
            job.partial_entry = entry
            return
        with self._tracer.span(
            "corpus.preload", cat="store", job=job.id, trace=job.trace,
            states=entry.states,
        ):
            n = self._store.preload(
                entry.fps,
                entry.parents,
                salt_lo=job.salt_lo,
                salt_hi=job.salt_hi,
            )
        self._corpus.note_preload(n)
        job.warm = entry.meta
        job.warm_kind = kind or "exact"
        job.warm_states = n
        # Dedup-first semantics: the verdict table was preloaded OFF-LOCK
        # by prefetch_warm; only the rare no-prefetch admissions (direct
        # engine use, crash-resume on a survivor) seed it here — single-job
        # paths where holding the lock over the insert loop stalls nobody.
        # Gate on whether a prefetch RAN, not the preload COUNT: a prefetch
        # that found every fingerprint already cached legitimately
        # returns 0.
        if not prefetched:
            job.verdict_preloads = self._corpus.preload_verdicts(entry)
        # Pin the SERVED entry against corpus GC while this job depends on
        # it (released at retire) — for the near rung that is the family
        # entry's key, not this job's own.
        self._corpus.pin(entry.key)
        job.corpus_pinned = True
        job.corpus_pin_key = entry.key
        self._events.emit(
            "job.warm_start", job=job.id, trace=job.trace, states=n,
            key=job.content_key[:16], kind=job.warm_kind,
        )

    def prepare_publish(self, job: Job) -> Optional[tuple]:
        """The UNDER-LOCK half of a corpus publish: apply the gate and
        snapshot the journal into packed arrays + metadata. A COMPLETE
        exhaustive cold run (never early-exited, timed out, or cancelled
        — only then is the journal the full reachable set) publishes a
        complete entry; every OTHER terminal outcome with a non-empty
        journal — early exit, timeout, cancellation, budget cap — plus
        the preemption snapshot publishes a PARTIAL entry (corpus v2):
        what the job visited, and (when the cut is a clean step boundary,
        i.e. the frontier is still pending) the frontier snapshot a
        successor continues from. Discovery early-exits drop their
        frontier (the triggering batch's successors were discarded, so
        the snapshot would not be a true FIFO prefix) and publish
        coverage-only. Returns the payload for `publish_payload`, or
        None when the job must not publish. Cheap (memory concatenation)
        by design: the npz write and the Bloom rehash — the slow parts —
        happen off-lock. MUST run before `retire` (retire drops the
        frontier this snapshots)."""
        if (
            self._corpus is None
            or job.content_key is None
            or job.warm is not None
            or job.no_publish
            or job.journal is None
            or not job.journal
            or job.quarantined
            or job.error is not None
            or job.status == JobStatus.ERROR
        ):
            return None
        if getattr(job, "_spill_path", None) is not None:
            # Parked with a live frontier spill: the preemption cut
            # already published this exact prefix WITH its frontier; a
            # shutdown cancel here would overwrite that entry with a
            # frontier-less (continuation-blind) one.
            return None
        complete = (
            job.status == JobStatus.DONE
            and not job.early_exit
            and not job.timed_out
            and job.pending_lanes == 0
        )
        frontier = None
        if not complete and job.pending_lanes:
            # A pending frontier means the cut is a clean step boundary
            # (steps fully account their successors before the scheduler
            # loop returns) — a sound continuation prefix.
            frontier = job._frontier_arrays()
            frontier = {
                "states": frontier["q_states"],
                "lo": frontier["q_lo"],
                "hi": frontier["q_hi"],
                "ebits": frontier["q_ebits"],
                "depths": frontier["q_depths"],
            }
        j_lo = np.concatenate([c[0] for c in job.journal])
        j_hi = np.concatenate([c[1] for c in job.journal])
        jp_lo = np.concatenate([c[2] for c in job.journal])
        jp_hi = np.concatenate([c[3] for c in job.journal])
        # Spec-CI plane (store/specdelta.py): the journaled STATE rows +
        # pop depths, row-parallel with the fp journal. Only a COMPLETE
        # entry carries it (the salvage proofs are exhaustion arguments);
        # a poisoned or misaligned plane is simply dropped — the entry is
        # then delta-incapable but otherwise identical.
        j_states = j_depths = None
        if complete and job.state_journal:
            j_states = np.concatenate([c[0] for c in job.state_journal])
            j_depths = np.concatenate([c[1] for c in job.state_journal])
            if len(j_states) != len(j_lo) or len(j_depths) != len(j_lo):
                j_states = j_depths = None
        return (
            job.content_key,
            pack_fp(j_lo, j_hi),
            pack_fp(jp_lo, jp_hi),
            {
                "state_count": job.state_count,
                "unique_count": job.unique_count,
                "max_depth": job.max_depth,
                "discoveries": dict(job.discoveries),
            },
            complete,
            frontier,
            self._components_for(job),
            j_states,
            j_depths,
            job.model,
        )

    def publish_payload(self, payload: tuple) -> bool:
        """The OFF-LOCK half: Bloom rehash + crash-atomic npz write
        (ROADMAP item 4 leftover — a slow publish must not stall an
        unrelated job's poll against the service lock). The CorpusStore
        is internally thread-safe; never raises. Dedup-first semantics:
        the packed canonical verdict table rides along on COMPLETE
        entries, snapshotted HERE (off the service lock — walking a
        2^16-entry cache under it would stall unrelated polls); verdict
        bits are class-addressed, so over-inclusion is harmless and a
        repeat register-model submission in a fresh process warm-starts
        its consistency properties, not just its visited set."""
        (
            key, fps, parents, meta, complete, frontier, components,
            j_states, j_depths, model,
        ) = payload
        sem_fps = sem_verdicts = None
        if complete:
            from ..semantics.batch import export_verdicts

            sem_fps, sem_verdicts = export_verdicts()
        j_bound = None
        if j_states is not None:
            # Spec-CI boundary plane: evaluate within_boundary over the
            # journaled states HERE, off the service lock (a batched jax
            # eval over the full visited set is exactly the slow work
            # prepare_publish defers). Best-effort like the npz write.
            try:
                from ..store import specdelta

                j_bound = specdelta.eval_boundary(model, j_states)
            except Exception:
                j_states = j_depths = None
        return self._corpus.publish(
            key, fps, parents, meta,
            sem_fps=sem_fps, sem_verdicts=sem_verdicts,
            complete=complete, frontier=frontier, components=components,
            journal_states=j_states, journal_depths=j_depths,
            journal_bound=j_bound,
        )

    def admit(self, job: Job) -> Optional[Job]:
        """Seed a job's init states into the shared table (salted) and hand
        its frontier to the scheduler. Returns the job if it finished
        immediately (vacuous finish policy / empty space), else None. A job
        carrying a `resume` payload (fleet requeue after a replica death)
        is re-seeded from its journal instead of its init states."""
        if job.resume is not None:
            return self._admit_resumed(job)
        g = self.group_of(job)
        model = job.model
        props = g.props
        P = len(props)
        init, init_lo, init_hi, n_raw = seed_init(model)
        n0 = len(init)
        job.state_count = n_raw  # host checkers count pre-dedup (bfs.rs:54)

        if job.finish_when.matches(props, set()) or not props:
            # Vacuously-true finish policy: stop before exploring anything
            # (the resident engine's immediate early-out).
            job.unique_count = n0
            job.max_depth = 1 if n0 else 0
            job.early_exit = True
            return job

        # Warm-start: preload a published visited set for this content key
        # into the spill tier + Bloom summary BEFORE seeding, so the very
        # first expansion's successors already dedup-filter against it.
        self._maybe_warm(job)
        if job.partial_entry is not None:
            # Partial rung (corpus v2): the entry's visited prefix +
            # frontier snapshot IS a resume payload — take the fleet
            # journal-reseed path, which restores the table, counters,
            # discoveries, and pop order bit-identically, then continues
            # the search naturally under THIS job's finish policy.
            return self._admit_partial(job)

        K = self.batch_size
        slo, shi = salt_fp(init_lo, init_hi, job.salt_lo, job.salt_hi)
        for b0 in range(0, max(n0, 1), K):
            sl = slice(b0, min(b0 + K, n0))
            n = sl.stop - sl.start
            lo_pad = np.zeros(K, dtype=np.uint32)
            hi_pad = np.zeros(K, dtype=np.uint32)
            lo_pad[:n] = slo[sl]
            hi_pad[:n] = shi[sl]
            res = self.table.insert(
                jnp.asarray(lo_pad),
                jnp.asarray(hi_pad),
                jnp.zeros(K, dtype=jnp.uint32),
                jnp.zeros(K, dtype=jnp.uint32),
                jnp.asarray(np.arange(K) < n),
            )
            if bool(res.overflow):
                self._fail_all("shared hash table full; raise table_log2")
                raise ServiceError("shared hash table full; raise table_log2")
            n_new = int(np.asarray(res.is_new).sum())
            job.unique_count += n_new
            self.hot_claims += n_new
        self._table_stamp += 1

        ebits0 = np.zeros((n0, P), dtype=bool)
        for i in g.prop_is["eventually"]:
            ebits0[:, i] = True
        job.push(
            init, init_lo, init_hi, ebits0,
            np.ones(n0, dtype=np.uint32),
        )
        if self._corpus is not None:
            # Spec-CI plane: journal the init STATE rows (depth 1) in the
            # same order as the fp rows — specdelta replays property
            # conditions against them at delta-salvage time.
            job.journal_append(
                init_lo, init_hi,
                np.zeros(n0, np.uint32), np.zeros(n0, np.uint32),
                states=init, depths=np.ones(n0, np.uint32),
            )
        else:
            job.journal_append(
                init_lo, init_hi,
                np.zeros(n0, np.uint32), np.zeros(n0, np.uint32),
            )
        g.jobs.append(job)
        if job.pending_lanes == 0:
            return job  # empty reachable space: complete immediately
        return None

    def _admit_partial(self, job: Job) -> Optional[Job]:
        """Warm-from-partial admission (corpus v2): convert the parked
        partial entry into a `JobResume` payload and run the journal-
        reseed admission. The prefix's (fp, parent) pairs land in the
        shared table re-salted with THIS job's salt, the frontier snapshot
        restores at its exact pop order, and the job's journal continues
        accumulating — so a natural DONE later publishes the COMPLETE
        visited set and supersedes the partial entry it grew from."""
        entry = job.partial_entry
        job.partial_entry = None
        j_lo, j_hi = warm_seam.split_fps(entry.fps)
        jp_lo, jp_hi = warm_seam.split_fps(entry.parents)
        f = entry.frontier
        chunks = []
        if f is not None and f["lo"].size:
            # One chunk carrying the whole snapshot: Job.take flattens
            # chunks FIFO and depth is a per-row array, so splitting by
            # depth run is unnecessary.
            chunks.append(
                (
                    np.asarray(f["states"], np.uint32),
                    np.asarray(f["lo"], np.uint32),
                    np.asarray(f["hi"], np.uint32),
                    np.asarray(f["ebits"], bool),
                    np.asarray(f["depths"], np.uint32),
                )
            )
        meta = entry.meta
        job.resume = JobResume(
            chunks=chunks,
            journal=(j_lo, j_hi, jp_lo, jp_hi),
            state_count=meta["state_count"],
            unique_count=meta["unique_count"],
            max_depth=meta["max_depth"],
            discoveries=dict(meta.get("discoveries", {})),
        )
        job.warm_kind = job.partial_kind
        job.warm_states = entry.states
        self._corpus.note_partial_preload()
        self._corpus.note_preload(entry.states)
        # Pin the SERVED entry (its own key — the near-partial rung serves
        # a different family member's partial) until retire.
        self._corpus.pin(entry.key)
        job.corpus_pinned = True
        job.corpus_pin_key = entry.key
        self._events.emit(
            "job.warm_start", job=job.id, trace=job.trace,
            states=entry.states, key=job.content_key[:16],
            kind=job.partial_kind,
        )
        return self._admit_resumed(job)

    def _admit_resumed(self, job: Job) -> Optional[Job]:
        """Fleet requeue admission: re-seed the job's ENTIRE visited set
        (the checkpointed journal, re-salted with THIS job's salt, parent
        chains intact) into the shared table, then restore the pending
        frontier at its exact pop order. From here the normal step path
        continues the search bit-identically to an uninterrupted run — the
        restored table deduplicates exactly what the dead replica's did,
        and restored discoveries are never re-scanned."""
        g = self.group_of(job)
        # A requeued job re-checks the corpus on its NEW replica: the
        # shared corpus directory means the survivor warm-starts the
        # not-yet-explored remainder exactly like a fresh submission.
        self._maybe_warm(job)
        rz = job.resume
        if rz.was_warm and job.warm is None:
            # The checkpoint came from a WARM run, but THIS replica could
            # not re-warm (entry corrupt/missing, injected corpus.load
            # fault). A warm run's journal/frontier cover only the
            # re-expanded slice — the corpus dedup dropped every known
            # subtree — so draining the payload cold would finish DONE
            # with silently wrong counts. Restart the job fresh instead:
            # slower, never wrong.
            self._tracer.instant(
                "corpus.resume_restart", cat="store", job=job.id,
                trace=job.trace,
            )
            job.resume = None
            return self.admit(job)
        job.state_count = rz.state_count
        job.max_depth = rz.max_depth
        job.discoveries = dict(rz.discoveries)
        K = self.batch_size
        j_lo, j_hi, jp_lo, jp_hi = (np.asarray(a) for a in rz.journal)
        n_j = len(j_lo)
        slo, shi = salt_fp(j_lo, j_hi, job.salt_lo, job.salt_hi)
        # Parent 0 is the root sentinel: it must survive salting as 0 or
        # reconstruct_path's chain walk would never terminate. Real parent
        # fingerprints never have lo == 0 (the sentinel contract).
        root = (jp_lo == 0) & (jp_hi == 0)
        plo, phi = salt_fp(jp_lo, jp_hi, job.salt_lo, job.salt_hi)
        plo = np.where(root, np.uint32(0), plo).astype(np.uint32)
        phi = np.where(root, np.uint32(0), phi).astype(np.uint32)
        for b0 in range(0, n_j, K):
            sl = slice(b0, min(b0 + K, n_j))
            n = sl.stop - sl.start
            lo_pad = np.zeros(K, dtype=np.uint32)
            hi_pad = np.zeros(K, dtype=np.uint32)
            plo_pad = np.zeros(K, dtype=np.uint32)
            phi_pad = np.zeros(K, dtype=np.uint32)
            lo_pad[:n] = slo[sl]
            hi_pad[:n] = shi[sl]
            plo_pad[:n] = plo[sl]
            phi_pad[:n] = phi[sl]
            res = self.table.insert(
                jnp.asarray(lo_pad),
                jnp.asarray(hi_pad),
                jnp.asarray(plo_pad),
                jnp.asarray(phi_pad),
                jnp.asarray(np.arange(K) < n),
            )
            if bool(res.overflow):
                self._fail_all("shared hash table full; raise table_log2")
                raise ServiceError("shared hash table full; raise table_log2")
            self.hot_claims += int(np.asarray(res.is_new).sum())
        self._table_stamp += 1
        # Counters continue from the checkpoint (the journal rows are
        # distinct by construction, so the insert claims agree).
        job.unique_count = rz.unique_count
        job.journal = [(j_lo, j_hi, jp_lo, jp_hi)] if n_j else []
        # The resume payload carries no state rows: the Spec-CI plane for
        # the restored prefix is unavailable, so the eventual publish is
        # delta-incapable (valid, just never salvageable by specdelta).
        job.state_journal = None
        for chunk in rz.chunks:
            job.push(*chunk)
        job.resume = None
        props = g.props
        if not props or job.finish_when.matches(
            props, set(job.discoveries)
        ):
            job.early_exit = True
            return job  # finish policy was already satisfied at crash time
        g.jobs.append(job)
        if job.pending_lanes == 0:
            return job  # frontier was already exhausted at checkpoint time
        return None

    def retire(self, job: Job) -> None:
        g = self.groups.get(group_key(job.model))
        if g is not None and job in g.jobs:
            g.jobs.remove(job)
        job.drop_frontier()
        self._job_semantics_finalize(job)
        # Empty groups are kept: their compiled step is the expensive part,
        # and a later job of the same model definition reuses it.

    def _job_semantics_finalize(self, job: Job) -> None:
        """Per-job-retire semantics housekeeping: release the job's corpus
        GC pin and bound the process-global verdict caches (the legacy lru
        memos pin FULL tester histories; semantics.maintain_caches trims
        the canonical plane and clears an oversized memo, counted through
        the "semantics" REGISTRY source) — a fleet replica serving
        thousands of register jobs stops growing without bound."""
        if job.corpus_pinned and self._corpus is not None:
            # The near/partial rungs pin the SERVED entry's key, which may
            # differ from this job's own content key.
            self._corpus.unpin(job.corpus_pin_key or job.content_key)
            job.corpus_pinned = False
        from ..semantics import maintain_caches

        maintain_caches()

    def runnable_groups(self) -> list:
        return [
            self.groups[k] for k in self._group_rr if self.groups[k].runnable()
        ]

    def next_group(self) -> Optional[_Group]:
        """Round-robin over groups with runnable work."""
        n = len(self._group_rr)
        for _ in range(n):
            key = self._group_rr.pop(0)
            self._group_rr.append(key)
            g = self.groups[key]
            if g.runnable():
                return g
        return None

    # -- lane grants -----------------------------------------------------------

    def _grants(self, jobs: list, K: int) -> list:
        """TWO-LEVEL fair-share waterfill of K lanes (tenancy plane):
        level 1 waterfills across the TENANTS present (each tenant's
        demand = its jobs' pending lanes summed), level 2 waterfills each
        tenant's allocation across that tenant's jobs — so a tenant with
        one job and a tenant with a hundred get equal device share, and
        within a tenant small jobs finish their frontier while big jobs
        absorb the slack. With a single tenant present (every pre-tenancy
        caller) level 1 degenerates to handing K straight to level 2,
        which IS the old jobs-only waterfill — grants bit-identical."""
        pend = [j.pending_lanes for j in jobs]
        tenants: list = []
        for j in jobs:
            t = getattr(j, "tenant", "default")
            if t not in tenants:
                tenants.append(t)
        if len(tenants) <= 1:
            return self._waterfill(pend, K)
        demand = [
            sum(
                p for j, p in zip(jobs, pend)
                if getattr(j, "tenant", "default") == t
            )
            for t in tenants
        ]
        t_alloc = self._waterfill(demand, K)
        grants = [0] * len(jobs)
        for t, alloc in zip(tenants, t_alloc):
            idxs = [
                i for i, j in enumerate(jobs)
                if getattr(j, "tenant", "default") == t
            ]
            sub = self._waterfill([pend[i] for i in idxs], alloc)
            for i, g in zip(idxs, sub):
                grants[i] = g
        return grants

    @staticmethod
    def _waterfill(pend: list, K: int) -> list:
        """One waterfill pass: each round gives every still-hungry entry
        an equal share (>= 1 lane) until K is exhausted or demand is."""
        grants = [0] * len(pend)
        left = K
        while left > 0:
            live = [i for i in range(len(pend)) if pend[i] > grants[i]]
            if not live:
                break
            share = max(left // len(live), 1)
            for i in live:
                t = min(share, pend[i] - grants[i], left)
                grants[i] += t
                left -= t
                if left == 0:
                    break
        return grants

    # -- one fused step --------------------------------------------------------

    def step_group(self, group: _Group, only: Optional[list] = None) -> list:
        """Assemble one batch from the group's runnable jobs, dispatch the
        fused step, and do the per-job bookkeeping. Returns jobs finished by
        this step (result built; caller signals their events).

        `only` restricts the batch to specific jobs — the isolation probe
        the CheckService uses to find the poison job after a group's step
        has faulted past its retry budget.

        A step exception (injected `service.step` fault, or a real dispatch
        error) is converted to `StepFault` AFTER pushing every taken lane
        back to the FRONT of its job's frontier and reversing the per-job
        bookkeeping — so retrying the step re-packs the identical lanes in
        the identical order and per-job results stay bit-identical."""
        model = group.model
        props = group.props
        prop_is = group.prop_is
        K = self.batch_size
        A = model.max_actions
        P = len(props)

        jobs = group.runnable()
        if only is not None:
            jobs = [j for j in jobs if j in only]
        if not jobs:
            return []
        # Rotate the grant order so no job is permanently first in line.
        group.rr %= len(jobs)
        rotation = jobs[group.rr:] + jobs[: group.rr]
        group.rr += 1
        grants = self._grants(rotation, K)

        st = np.zeros((K, model.lanes), dtype=np.uint32)
        lo = np.zeros(K, dtype=np.uint32)
        hi = np.zeros(K, dtype=np.uint32)
        salt_lo = np.zeros(K, dtype=np.uint32)
        salt_hi = np.zeros(K, dtype=np.uint32)
        depth = np.zeros(K, dtype=np.uint32)
        ebits = np.zeros((K, P), dtype=bool)
        eval_mask = np.zeros(K, dtype=bool)
        segments = []  # (job, start, end)
        m = 0
        for job, grant in zip(rotation, grants):
            if grant == 0:
                continue
            s_states, s_lo, s_hi, s_eb, s_dp = job.take(grant)
            n = len(s_lo)
            seg = slice(m, m + n)
            st[seg] = s_states
            lo[seg] = s_lo
            hi[seg] = s_hi
            ebits[seg] = s_eb
            depth[seg] = s_dp
            salt_lo[seg] = job.salt_lo
            salt_hi[seg] = job.salt_hi
            # target_max_depth: lanes at the cutoff are popped but neither
            # evaluated nor expanded (ref: bfs.rs:219-224) — and still raise
            # max_depth, exactly like FrontierSearch's skipped chunks.
            tmd = job.target_max_depth
            eval_mask[seg] = True if tmd is None else (s_dp < tmd)
            job.max_depth = max(job.max_depth, int(s_dp.max()) if n else 0)
            job.metrics.device_steps += 1
            job.metrics.lanes_held += n
            job.steps_since_admit += 1
            segments.append((job, m, m + n))
            m += n

        t_step0 = time.monotonic()
        try:
            # Chaos-plane boundary (faults/plan.py): fires BEFORE the
            # dispatch — rules can target a specific job via `job=<id>`
            # matching against this batch's job list (the poison-job
            # scenario).
            maybe_fault(
                "service.step",
                job=[j.id for j, _s, _e in segments],
                group=type(model).__name__,
            )
            with self._tracer.span(
                "service.step", cat="service", jobs=len(jobs), lanes=m
            ):
                (
                    t_lo, t_hi, p_lo, p_hi,
                    out_states, out_lo, out_hi, out_src, out_sus,
                    new_count, gen_rows, has_succ, overflow, prop_masks,
                ) = group.step(
                    self.table.t_lo, self.table.t_hi,
                    self.table.p_lo, self.table.p_hi,
                    jnp.asarray(st), jnp.asarray(lo), jnp.asarray(hi),
                    jnp.asarray(salt_lo), jnp.asarray(salt_hi),
                    jnp.asarray(eval_mask),
                    self._store.device_summary()
                    if self._store is not None
                    else self._no_summary,
                )
                self.table.t_lo, self.table.t_hi = t_lo, t_hi
                self.table.p_lo, self.table.p_hi = p_lo, p_hi
                self.total_steps += 1
                self._table_stamp += 1
                if bool(overflow):  # first host sync of the step
                    msg = (
                        "shared hash table full; raise table_log2 "
                        "(or store='tiered')"
                    )
                    self._fail_all(msg)
                    raise ServiceError(msg)
            # A successful step resets the group's CONSECUTIVE-fault
            # streak — without this the retry budget erodes over a
            # long-lived service until one transient fault skips straight
            # to solo-probe quarantine.
            group.fault_count = 0
        except ServiceError:
            raise  # shared-state failure: service-wide by design
        except Exception as e:  # noqa: BLE001 — group-scoped by design
            # Exactly-retriable unwind: the taken lanes go back to the
            # FRONT of each job's frontier (pop order preserved) and the
            # per-job bookkeeping above is reversed.
            for job, s, e2 in segments:
                job.push_front(
                    st[s:e2], lo[s:e2], hi[s:e2], ebits[s:e2], depth[s:e2]
                )
                job.metrics.device_steps -= 1
                job.metrics.lanes_held -= e2 - s
                job.steps_since_admit -= 1
            self.fault_counters["step_faults"] += 1
            self._tracer.instant(
                "service.step_fault", cat="service",
                group=type(model).__name__, error=type(e).__name__,
            )
            raise StepFault(group, e) from e
        step_us = (time.monotonic() - t_step0) * 1e6
        self.last_active_lanes = m
        # Tenancy billing: lane-seconds = lanes held x step wall time,
        # charged AFTER the step succeeded (the exactly-retriable unwind
        # above never reaches here, so a faulted step cannot double-bill).
        lane_s = step_us / 1e6
        for job, s, e2 in segments:
            share = (e2 - s) * lane_s
            job.metrics.lane_seconds += share
            if self.quotas is not None and job.tenant != "default":
                self.quotas.charge(job.tenant, share)

        masks = np.asarray(prop_masks)
        gen_rows = np.asarray(gen_rows)
        has_succ = np.asarray(has_succ)
        nc = int(new_count)
        finished: list[Job] = []
        early: set[int] = set()

        # -- per-job discovery scan + early exit (FrontierSearch order) --------
        for job, s, e in segments:
            ev = eval_mask[s:e]
            for i in prop_is["always"]:
                if props[i].name in job.discoveries:
                    continue
                viol = ev & ~masks[i][s:e]
                if viol.any():
                    j = int(np.argmax(viol))
                    job.discoveries[props[i].name] = int(
                        pack_fp(lo[s + j], hi[s + j])
                    )
            for i in prop_is["sometimes"]:
                if props[i].name in job.discoveries:
                    continue
                sat = ev & masks[i][s:e]
                if sat.any():
                    j = int(np.argmax(sat))
                    job.discoveries[props[i].name] = int(
                        pack_fp(lo[s + j], hi[s + j])
                    )
            if prop_is["eventually"]:
                for i in prop_is["eventually"]:
                    ebits[s:e, i] &= ~masks[i][s:e]
                term = ev & ~has_succ[s:e]
                for i in prop_is["eventually"]:
                    if props[i].name in job.discoveries:
                        continue
                    bad = term & ebits[s:e, i]
                    if bad.any():
                        j = int(np.argmax(bad))
                        job.discoveries[props[i].name] = int(
                            pack_fp(lo[s + j], hi[s + j])
                        )
            if (props and len(job.discoveries) == len(props)) or (
                job.finish_when.matches(props, set(job.discoveries))
            ):
                # Early exit discards this batch's count/successor
                # contributions for THIS job only (frontier.py does the
                # same for the whole search).
                job.early_exit = True
                early.add(job.id)
                job.drop_frontier()
                finished.append(job)
                continue
            job.state_count += int(gen_rows[s:e].sum())

        # -- successors: attribute to jobs, resolve suspects, append -----------
        self.hot_claims += nc  # device slot claims (incl. suspects)
        sus_n = 0
        lane_job = np.full(K, -1, dtype=np.int64)
        for idx, (job, s, e) in enumerate(segments):
            lane_job[s:e] = idx
        if nc:
            o_states = np.asarray(out_states[:nc])
            o_lo = np.asarray(out_lo[:nc])
            o_hi = np.asarray(out_hi[:nc])
            parents = np.asarray(out_src[:nc]) // A
            keep = np.ones(nc, dtype=bool)
            if self._store is not None:
                sus = np.asarray(out_sus[:nc])
                sus_n = int(sus.sum())
                if sus.any():
                    self._tracer.instant(
                        "tiered.suspect_resolve", cat="store", suspects=sus_n
                    )
                    k_lo, k_hi = salt_fp(
                        o_lo[sus], o_hi[sus],
                        salt_lo[parents[sus]], salt_hi[parents[sus]],
                    )
                    dup = self._store.resolve_suspects(k_lo, k_hi)
                    keep[np.nonzero(sus)[0][dup]] = False
                    sus_jobs = lane_job[parents[sus]]
                    for idx, (job, _s, _e) in enumerate(segments):
                        mine = sus_jobs == idx
                        job.metrics.suspects_checked += int(mine.sum())
                        job.metrics.suspects_dup += int(dup[mine].sum())
            owner = lane_job[parents]
            for idx, (job, _s, _e) in enumerate(segments):
                if job.id in early:
                    continue
                rows = np.nonzero((owner == idx) & keep)[0]
                n_j = len(rows)
                if n_j == 0:
                    continue
                job.unique_count += n_j
                pr = parents[rows]
                job.push(
                    o_states[rows], o_lo[rows], o_hi[rows],
                    ebits[pr] if P else np.zeros((n_j, 0), dtype=bool),
                    depth[pr] + 1,
                )
                # Fleet requeue journal: the claimed (fp, parent fp) pairs,
                # unsalted — all four arrays are already host-side. With a
                # corpus attached, the Spec-CI plane also records the
                # claimed STATE rows + pop depths (row-parallel with the
                # fp rows by construction — same `rows` index).
                if self._corpus is not None:
                    job.journal_append(
                        o_lo[rows], o_hi[rows], lo[pr], hi[pr],
                        states=o_states[rows], depths=depth[pr] + 1,
                    )
                else:
                    job.journal_append(
                        o_lo[rows], o_hi[rows], lo[pr], hi[pr]
                    )

        # -- spill eviction (tiered) -------------------------------------------
        if self._store is not None and self.hot_claims >= self._spill_trigger:
            with self._tracer.span("tiered.evict", cat="store"):
                tl, th, pl, ph, n_ev = self._store.evict(
                    self.table.t_lo, self.table.t_hi,
                    self.table.p_lo, self.table.p_hi,
                    self.hot_claims,
                )
            if n_ev == 0:
                msg = (
                    "tiered store could not free any bucket (every bucket "
                    "full and pinned); raise table_log2 or lower high_water"
                )
                self._fail_all(msg)
                raise ServiceError(msg)
            self.table.t_lo, self.table.t_hi = tl, th
            self.table.p_lo, self.table.p_hi = pl, ph
            self.hot_claims -= n_ev

        # -- flight-recorder chunk event (scalars already host-side) -----------
        if self._events.enabled:
            self._events.emit(
                "engine.chunk",
                jobs=[j.id for j, _s, _e in segments],
                traces=[j.trace for j, _s, _e in segments if j.trace],
                step=self.total_steps,
                lanes=m,
                claimed=nc,
            )

        # -- step telemetry row (every scalar above is already host-side) ------
        if self._ring is not None:
            self._ring.append(
                active=m,
                generated=int(gen_rows.sum()),
                claimed=nc,
                queue_len=sum(
                    j.pending_lanes
                    for g in self.groups.values()
                    for j in g.jobs
                ),
                table_claims=self.hot_claims,
                suspects=sus_n,
                depth=int(depth[:m].max()) if m else 0,
                step_us=step_us,
            )
            if self._calib is not None:
                # Same already-fetched scalars, joined against the
                # costmodel at chunk granularity; active traces ride onto
                # any drift event so the timeline can name the jobs.
                self._calib.observe(
                    self._ring.steps,
                    step_us,
                    self._ring.generated_total,
                    traces=[
                        j.trace for j, _s, _e in segments if j.trace
                    ] or None,
                )

        # -- per-job finish checks ---------------------------------------------
        for job, _s, _e in segments:
            if job.id in early:
                continue
            if (
                job.target_state_count is not None
                and job.state_count >= job.target_state_count
            ):
                # Budget-cap cut: unlike the discovery early-exit above,
                # this check runs AFTER successor attribution, so the
                # pending frontier IS a sound continuation prefix — keep
                # it for the partial-publish snapshot (retire drops it).
                job.early_exit = True
                finished.append(job)
            elif job.pending_lanes == 0:
                finished.append(job)
        return finished

    # -- results / failure -----------------------------------------------------

    def build_result(self, job: Job) -> SearchResult:
        if (
            job.warm is not None
            and job.status == JobStatus.DONE
            and job.pending_lanes == 0
            and not job.early_exit
            and not job.timed_out
        ):
            # Warm-start replay: the run itself only re-expanded the init
            # frontier (everything else dedup-filtered against the
            # preloaded corpus), so the result bookkeeping comes from the
            # publisher's cold run — which, for this content key, is
            # bit-identical to what THIS submission's cold run would have
            # produced. Discovery fingerprints replay onto `job` (not just
            # the result) so `discovery_paths` walks the preloaded salted
            # parent chains.
            w = job.warm
            job.state_count = w["state_count"]
            job.unique_count = w["unique_count"]
            job.max_depth = w["max_depth"]
            job.discoveries = dict(w["discoveries"])
        detail = dict(self.store_stats() or {})
        detail["service"] = job.metrics.to_dict(job.unique_count)
        if self._corpus is not None and job.content_key is not None:
            detail["corpus"] = {
                "warm_start": job.warm is not None or job.warm_kind is not None,
                "preloaded_states": job.warm_states,
                "verdict_preloads": job.verdict_preloads,
                "published": job.published,
                "key": job.content_key[:16],
            }
            if job.warm_kind is not None:
                detail["corpus"]["warm_kind"] = job.warm_kind
            if job.delta_class is not None:
                detail["corpus"]["delta_class"] = job.delta_class
        if any(self.fault_counters.values()):
            # Engine-wide recovery counters (documented schema:
            # obs/schema.py FAULTS_DETAIL_KEYS) — present only once a
            # fault actually happened, so fault-free results stay
            # byte-identical to before.
            detail["faults"] = dict(self.fault_counters)
        t = self.telemetry_summary()
        if t is not None:
            # Engine-wide step digest (the shared batches this job rode in),
            # not a per-job slice — per-job shares live under "service".
            detail["telemetry"] = t
        c = self.calib_detail()
        if c is not None:
            # Engine-wide measured-vs-predicted join, same scope as the
            # telemetry digest above (obs/schema.py CALIB_DETAIL_KEYS).
            detail["calib"] = c
            self._calib.flush_records()
        if job.tenant != "default":
            # Tenancy accounting sub-dict (obs/schema.py
            # TENANT_DETAIL_KEYS) — default-tenant results stay
            # byte-identical to the pre-tenancy goldens.
            detail["tenant"] = {
                "name": job.tenant,
                "lane_seconds": round(job.metrics.lane_seconds, 6),
            }
        if job.timed_out:
            detail["timed_out"] = True
        if job.trace:
            detail["trace"] = job.trace
        ref = job.metrics.admitted_at or job.metrics.submitted_at
        return SearchResult(
            state_count=job.state_count,
            unique_state_count=job.unique_count,
            max_depth=job.max_depth,
            discoveries=dict(job.discoveries),
            complete=(
                job.pending_lanes == 0
                and not job.early_exit
                and not job.timed_out
                and job.status != JobStatus.CANCELLED
            ),
            duration=(job.metrics.finished_at or time.monotonic()) - ref,
            steps=job.metrics.device_steps,
            detail=detail,
        )

    def _fail_all(self, msg: str) -> None:
        """Service-wide failure: ONLY for unusable shared device state
        (table overflow without a spill tier). Per-group step exceptions
        take the `StepFault` → retry → quarantine path instead — see
        `_fail_group` and CheckService._handle_step_fault."""
        for g in self.groups.values():
            self._fail_group(g, msg)

    def _fail_group(self, group: _Group, msg: str) -> None:
        """Fail one group's jobs without touching any other group — the
        blast-radius fix: a poison model (or a fault localized to one
        group's step) must not kill unrelated jobs sharing the service."""
        for job in list(group.jobs):
            job.status = JobStatus.ERROR
            job.error = msg
            job.metrics.finished_at = time.monotonic()
            job.drop_frontier()
            self._job_semantics_finalize(job)
            self._events.emit(
                "job.error", job=job.id, trace=job.trace, error=msg
            )
            job.event.set()
        group.jobs.clear()

    def store_stats(self) -> Optional[dict]:
        if self._store is None:
            return None
        return self._store.stats(self.hot_claims)

    def calib_detail(self) -> Optional[dict]:
        """The comparator's `detail["calib"]` sub-dict, or None before the
        first closed chunk (also the `/.status` and fleet-row surface)."""
        if self._calib is None:
            return None
        self._calib.finish()
        if not self._calib.chunks:
            return None
        return self._calib.detail()

    def lane_util(self) -> float:
        """Fraction of the batch the LAST fused step filled — the
        autoscaler's utilization signal (0.0 before any step)."""
        return self.last_active_lanes / max(self.batch_size, 1)

    def telemetry_summary(self) -> Optional[dict]:
        """Engine-wide step-telemetry digest (obs/ring.py; None with
        telemetry off) — surfaced in `/.status`, `/metrics`, and every
        job result's detail (the owning CheckService is the registry
        provider; it folds this into its stats())."""
        if self._ring is None:
            return None
        return self._ring.summary(self.table.size, self.batch_size)

    # -- path reconstruction ---------------------------------------------------

    def parent_map(self) -> dict:
        """Salted {key: parent} of the shared table (+ spill tier), cached
        per table version."""
        if self._parent_map_stamp != self._table_stamp:
            pm = self.table.dump()
            if self._store is not None:
                pm.update(self._store.parent_map())
            self._parent_map = pm
            self._parent_map_stamp = self._table_stamp
        return self._parent_map

    def reconstruct_path(self, job: Job, fp: int):
        """Walk the SALTED parent chain for a job's (unsalted) discovery
        fingerprint, unsalt it, and re-execute the model along it — the
        engines' TLC-style reconstruction, made job-aware. A parent written
        by another job can never appear in the chain: every parent pointer
        stored for a job's state is that job's own salted key."""
        pm = self.parent_map()
        lo32, hi32 = unpack_fp(fp)
        klo, khi = salt_fp(
            np.uint32(lo32), np.uint32(hi32), job.salt_lo, job.salt_hi
        )
        cur = int(pack_fp(klo, khi))
        chain = []
        while cur:
            lo32, hi32 = unpack_fp(cur)
            ulo, uhi = salt_fp(
                np.uint32(lo32), np.uint32(hi32), job.salt_lo, job.salt_hi
            )
            chain.append(int(pack_fp(ulo, uhi)))
            cur = pm.get(cur, 0)
        chain.reverse()
        return replay_fp_chain(job.model, chain)
