"""Fully device-resident frontier search: the ENTIRE breadth-first check runs
as one `lax.while_loop` inside one `jit` dispatch.

Motivation: the host-orchestrated loop (frontier.py) pays a host↔device round
trip per step. Here the frontier queue itself lives in HBM; each
loop iteration pops a batch (a contiguous dynamic slice — the queue never
wraps, see below), expands it with the model kernel, fingerprints + dedups +
inserts into the visited table, evaluates property masks, and appends fresh
states to the queue tail — no host involvement until the search finishes.

Everything on device is 32-bit (u32 fingerprint pairs, u32-pair generated
counters): TPUs emulate 64-bit integer ops, so the round-1 u64 design paid
emulation tax on every hot op.

Capacity argument (also why the queue needs no ring wraparound): every unique
state is enqueued exactly once, so a queue with as many rows as the hash
table has slots can never fill before the table overflows.

Early-exit parity with the reference checkers: the loop stops when every
property has a discovery (src/checker/bfs.rs:278-280), when the configured
`HasDiscoveries` policy matches (encoded as required/any bitmask pairs), when
`target_state_count` is reached, or when the queue drains.
"""

from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.discovery import HasDiscoveries
from ..core.model import Expectation
from ..knobs import PHASED_VARIANTS, TABLE_LAYOUTS, WARM_KINDS
from ..faults.plan import maybe_fault
from ..store import warm as warm_seam
from ..obs import N_COLS
from .frontier import (
    SearchResult,
    append_new,
    append_new_dus,
    count_add,
    count_ge,
    expand_insert,
    pop_batch,
    resolve_append,
    record_discovery as _record,
)
from .inserts import resolve_insert
from .model import TensorModel
# The abort codes, `_finish_masks` and `_regrow` are imported from here by
# callers outside the package too.
from .search_host import (  # noqa: F401
    ABORT_QUEUE,
    ABORT_TABLE,
    EXIT_SERVICE,
    SearchHost,
    _abort_reason,
    _compact_queue,
    _finish_masks,
    _fit_rows,
    _inject_rows,
    _regrow,
    _resolve_chunking,
    _SUSPECTS,
    summary_fields,
    to_host,
)


#: Static arguments of the whole-search program: required mask, any mask
#: and the step cap (one executable per value, as jit keys them).
_SEARCH_STATIC = (3, 4, 7)


class _Carry(NamedTuple):
    t_lo: jnp.ndarray  # uint32[S] visited-table key halves
    t_hi: jnp.ndarray  # uint32[S]
    p_lo: jnp.ndarray  # uint32[S] parent halves
    p_hi: jnp.ndarray  # uint32[S]
    q_states: jnp.ndarray  # uint32[Q, L]
    q_lo: jnp.ndarray  # uint32[Q]
    q_hi: jnp.ndarray  # uint32[Q]
    q_ebits: jnp.ndarray  # uint32[Q]
    q_depth: jnp.ndarray  # uint32[Q]
    head: jnp.ndarray  # int32
    tail: jnp.ndarray  # int32
    gen_lo: jnp.ndarray  # uint32 generated-count pair
    gen_hi: jnp.ndarray  # uint32
    unique_count: jnp.ndarray  # int32
    max_depth: jnp.ndarray  # uint32
    discovered: jnp.ndarray  # uint32 bitmask
    disc_lo: jnp.ndarray  # uint32[P]
    disc_hi: jnp.ndarray  # uint32[P]
    overflow: jnp.ndarray  # uint32 abort code (0 ok; ABORT_*|EXIT_SERVICE)
    steps: jnp.ndarray  # int32
    # -- tiered store (store="tiered"; zero-sized placeholders otherwise) ------
    hot_claims: jnp.ndarray  # int32: occupied device-table slots
    s_states: jnp.ndarray  # uint32[SQ, L] suspect buffer (Bloom-positive claims
    s_lo: jnp.ndarray  # uint32[SQ]       awaiting exact host resolution)
    s_hi: jnp.ndarray  # uint32[SQ]
    s_ebits: jnp.ndarray  # uint32[SQ]
    s_depth: jnp.ndarray  # uint32[SQ]
    s_tail: jnp.ndarray  # int32
    summary: jnp.ndarray  # uint32[W] Bloom words (read-only in-loop)
    # -- step telemetry (obs/ring.py; zero-row placeholder when disabled) ------
    tm_rows: jnp.ndarray  # uint32[TMR, N_COLS] in-carry metrics ring


class ResidentSearch(SearchHost):
    """One-dispatch whole-search engine for a `TensorModel`; its host side
    (result, paths, dump, checkpoint) is `SearchHost`'s one-shard case."""

    _carry_type = _Carry

    # Corpus warm ladder: the ONE kind vocabulary and the ONE preload seam
    # (store/warm.py) — aliased, never restated; knobs.check_registry()
    # pins both on every engine.
    WARM_KINDS = WARM_KINDS
    WARM_SEAM = warm_seam

    def __init__(
        self,
        model: TensorModel,
        batch_size: int = 2048,
        table_log2: int = 20,
        donate_chunks: bool = False,
        queue_log2: Optional[int] = None,
        append: Optional[str] = None,
        table_layout: str = "split",
        insert_variant: str = "sort",
        store: str = "device",
        high_water: float = 0.85,
        low_water: Optional[float] = None,
        summary_log2: int = 20,
        telemetry: bool = True,
        telemetry_log2: int = 12,
        tracer=None,
    ):
        """`donate_chunks=True` donates the carry to each chunked dispatch:
        XLA updates the tables/queue IN PLACE instead of copying the whole
        multi-GB carry per dispatch (measured ~280 s/dispatch at table 2^27
        on the CPU backend — the dominant cost of chunked long-haul runs).
        The trade: on a table/queue overflow the pre-chunk carry no longer
        exists, so the checkpoint-then-regrow recovery is unavailable —
        run big spaces with a right-sized table, or leave this off when
        overflow recovery matters more than throughput.

        `queue_log2` caps the frontier queue at 2^queue_log2 rows (default:
        table_log2, the always-sufficient bound). The queue dominates HBM
        when states are wide — 2pc-10 at table 2^27 needs 9.1 GB of queue
        for at most 61.5 M uniques (< 2^26): right-sizing it is what fits
        the workload on a 16 GB v5e. Exceeding the cap is detected as the
        same overflow signal as a full table (never a silent drop).

        `telemetry=True` (default) appends one obs.STEP_COLS metrics row
        per loop step into a device-resident ring of 2^telemetry_log2 rows
        carried through the while_loop — a ~32-byte scatter next to the
        megabytes the step already moves, with NO host involvement; the
        ring is drained in bulk at boundaries where the host has already
        synced (chunk returns, run end) and digested into
        `SearchResult.detail["telemetry"]`. `tracer` (obs.Tracer) records
        the host phases (chunk dispatch, tiered-store servicing,
        checkpoint) as Chrome trace events."""
        self.queue_log2 = table_log2 if queue_log2 is None else queue_log2
        self.donate_chunks = donate_chunks
        # Queue-append variant: XLA lays the queue out column-major (fast
        # per-lane reads for the model kernels), which makes the row-scatter
        # append pathological on TPU — the round-4 silicon profile measured
        # it at 44.7% of the paxos-3 step (2.4 GiB/s effective); the
        # compact-then-dynamic_update_slice form writes 21 contiguous
        # column runs instead (paxos-3 627k -> 1.06M states/s). The 1-core
        # CPU backend measured the OPPOSITE at 2pc-10 scale (DUS ~5x
        # slower), so the default follows the effective backend; pass
        # append="scatter"|"dus" to pin it.
        self.append = resolve_append(append, jax.default_backend())
        # table_layout="kv": interleaved 64-slot lo|hi buckets — one probe
        # gather fetches half the bytes of the split layout (see
        # hashtable._insert_impl_kv). Carry convention: t_lo holds the
        # uint32[2S] kv array and t_hi a zero-length placeholder.
        # Flag-gated pending the silicon race; checkpoint regrow is
        # split-only for now.
        if table_layout not in TABLE_LAYOUTS:  # knob universe: knobs.py
            raise ValueError(
                f"table_layout must be one of {TABLE_LAYOUTS}, "
                f"got {table_layout!r}"
            )
        self.table_layout = table_layout
        # insert_variant selects the visited-set insert design:
        #   "sort"   — full-batch sort-claim (the at-scale default);
        #   "phased" — pre-sort-claim scatter-max insert, raceable per
        #              workload — its fixed costs win on tiny frontiers
        #              (paxos-2 class; see hashtable._insert_impl_phased);
        #   "capped" — batch-monotonic path: active-compaction + fixed-size
        #              claim tiles, so per-step probe AND sort cost scale
        #              with the populated lanes instead of the full
        #              expanded batch (hashtable.make_capped_insert);
        #              composes with table_layout="kv";
        #   "capped-phased" — the same cap around the phased insert;
        #   "pallas" — the partitioned-VMEM route-then-probe kernel
        #              (tensor/pallas_hashtable.py; interpret mode on
        #              the CPU backend). Split layout only; the table must
        #              tile into (8,128) VMEM blocks, so table_log2 >= 10.
        if (
            insert_variant in PHASED_VARIANTS or insert_variant == "pallas"
        ) and table_layout == "kv":
            raise ValueError(
                f"insert_variant={insert_variant!r} supports the split "
                "table layout only"
            )
        if store == "tiered" and table_layout != "split":
            raise ValueError("store='tiered' supports the split table layout only")
        self._store = None
        # A step claims up to K*A slots (one batch's successors).
        self._init_host(
            "resident", model, batch_size, table_log2, insert_variant, store,
            (high_water, low_water, summary_log2), telemetry, telemetry_log2,
            tracer, step_claims=batch_size * model.max_actions,
            append_tile=batch_size if self.append == "dus" else 0,
        )
        self._kernel, self._seed_k, self._chunk_k = self._build()
        # The model's definition and every argument `_build` reads: engines
        # that agree on it share one search executable (Stages.executable).
        dk = model.definition_key()
        self._definition = None if dk is None else (
            dk, batch_size, table_log2, self.queue_log2, self.append,
            table_layout, insert_variant, store, high_water, low_water,
            summary_log2, telemetry, telemetry_log2, donate_chunks,
        )
        # Operand tables (lowered models): round-varying baked tables flow
        # into the kernels as ARGUMENTS instead of jaxpr constants, so
        # `set_dyn_tables` can swap their contents (same shapes) with no
        # retrace/recompile — what makes refine_check's per-round restarts
        # cheap.
        self._dyn_dev = (
            jax.device_put(model.dyn_tables())
            if hasattr(model, "dyn_tables")
            else {}
        )
        # Abort code of the last overflow (ABORT_TABLE | ABORT_QUEUE bits);
        # written into checkpoint meta so recovery grows the right resource.
        self._last_abort = 0

    @property
    def _stores(self) -> Optional[list]:
        """The spill tier as the shared host side sees it: one per shard."""
        return None if self._store is None else [self._store]

    @_stores.setter
    def _stores(self, stores: list) -> None:
        (self._store,) = stores

    def store_stats(self) -> Optional[dict]:
        """Per-tier occupancy counters (None with the plain device store) —
        surfaced in SearchResult.detail, the bench JSON, and `/.status`."""
        if self._store is None:
            return None
        hot = int(self._carry.hot_claims) if self._carry is not None else 0
        return self._store.stats(hot)

    def _insert_fn(self, summary_cfg=None):
        """Resolve through THE dispatch table (tensor/inserts.py) — the one
        name → insert-fn resolution point all three engines share.
        `summary_cfg=(summary_log2, hashes)` requests the tiered store's
        fused suspect probe where the variant has one (pallas)."""
        return resolve_insert(
            self.insert_variant, self.table_layout, summary_cfg=summary_cfg
        )

    def _build(self):
        model = self.model
        K = self.batch_size
        A = model.max_actions
        L = model.lanes
        # The DUS append writes tiles of one batch (K rows) and reads the
        # popped batch's ebits/depth columns unexpanded (fan=A: lane i's
        # parent is row i // A); the scatter append takes one per lane.
        scatter = self.append == "scatter"
        _append = (
            append_new if scatter
            else partial(append_new_dus, tile=K, fan=A)
        )
        kv = self.table_layout == "kv"
        S = 1 << self.table_log2
        tiered = self._store is not None
        if tiered:
            from ..store.summary import summary_words

            slog2 = self._store.config.summary_log2
            khash = self._store.config.summary_hashes
            W = summary_words(slog2)
            s_cfg = (slog2, khash)
        else:
            W = 1
            s_cfg = None
        # Seed inserts run against a fresh (empty-summary) table — always
        # the plain form; the step insert carries the fused Bloom probe
        # when the variant supports it (expand_insert keys on the marker).
        insert = self._insert_fn()
        insert_step = self._insert_fn(summary_cfg=s_cfg)
        SQ = self._SQ
        TMR = self._TMR
        TRIGGER = jnp.int32(self._spill_trigger) if tiered else None
        # Queue capacity: every unique state is enqueued exactly once (<= S
        # before the table overflows, and <= 2^queue_log2 when the caller
        # right-sized the queue below the table — see __init__), plus K*A
        # rows of slack so either append variant (scatter `append_new` —
        # the default; measured faster than `append_new_dus` on CPU at
        # 2pc-10 scale — or the DUS tiles: append_rows(K*A, K) == K*A)
        # stays in bounds right up to the overflow signal. Tiered runs add
        # SQ more rows: the live frontier is still bounded by 2^queue_log2
        # (uniques beyond the table spill, and the tail is host-compacted at
        # each service exit), and the extra slack guarantees the
        # suspect-injection block always fits.
        QL = 1 << self.queue_log2
        Q = QL + K * A + (SQ if tiered else 0)
        self._Q = Q
        props = self.props
        P = len(props)
        always_i = [i for i, p in enumerate(props) if p.expectation == Expectation.ALWAYS]
        sometimes_i = [i for i, p in enumerate(props) if p.expectation == Expectation.SOMETIMES]
        eventually_i = [i for i, p in enumerate(props) if p.expectation == Expectation.EVENTUALLY]
        ebits0 = np.uint32(sum(1 << i for i in eventually_i))
        all_bits = jnp.uint32((1 << P) - 1)

        def body(c: _Carry, tmd) -> _Carry:
            # -- pop a batch: contiguous dynamic slice (no wraparound) ---------
            states, lo, hi, ebits, depth, active, head = pop_batch(
                c.q_states, c.q_lo, c.q_hi, c.q_ebits, c.q_depth,
                c.head, c.tail, K,
            )

            max_depth = jnp.maximum(
                c.max_depth, jnp.max(jnp.where(active, depth, 0))
            )
            # target_max_depth: states at the cutoff are neither evaluated
            # nor expanded (ref: bfs.rs:219-224); 0 = no limit.
            active = active & ((tmd == 0) | (depth < tmd))

            # -- property evaluation (ref: bfs.rs:230-280) ---------------------
            # Named scopes here and in expand_insert attribute the step's
            # device ops to its phases in a profiler trace (HLO metadata
            # only: the program is unchanged).
            discovered = c.discovered
            disc_lo, disc_hi = c.disc_lo, c.disc_hi
            if P:
                with jax.named_scope("properties"):
                    masks = jnp.stack(
                        [p.condition(model, states) for p in props]
                    )
                    for i in always_i:
                        discovered, disc_lo, disc_hi = _record(
                            discovered, disc_lo, disc_hi, i,
                            active & ~masks[i], lo, hi,
                        )
                    for i in sometimes_i:
                        discovered, disc_lo, disc_hi = _record(
                            discovered, disc_lo, disc_hi, i,
                            active & masks[i], lo, hi,
                        )
                    for i in eventually_i:
                        ebits = jnp.where(
                            masks[i],
                            ebits & jnp.uint32(~(1 << i) & 0xFFFFFFFF),
                            ebits,
                        )

            # -- expand + fingerprint + dedup + insert (shared core) -----------
            (
                t_lo, t_hi, p_lo, p_hi,
                flat, slo, shi, is_new, suspect,
                gen_rows, has_succ, ovf,
            ) = expand_insert(
                model, c.t_lo, c.t_hi, c.p_lo, c.p_hi, states, lo, hi,
                active, insert=insert_step,
                summary=c.summary if tiered else None,
                summary_cfg=s_cfg,
            )
            gen = gen_rows.sum()

            # -- eventually counterexamples at terminal states -----------------
            if eventually_i:
                with jax.named_scope("properties"):
                    term = active & ~has_succ
                    for i in eventually_i:
                        bad = term & (
                            (ebits >> jnp.uint32(i)) & 1
                        ).astype(bool)
                        discovered, disc_lo, disc_hi = _record(
                            discovered, disc_lo, disc_hi, i, bad, lo, hi
                        )

            # -- tiered store: split claims into enqueue vs suspect ------------
            # A fresh claim whose fingerprint hits the Bloom summary of the
            # spilled set might be a revisit of an evicted state: it is
            # buffered for exact host resolution instead of enqueued (a
            # summary MISS proves novelty, so the common path never leaves
            # the device). The claim itself stays in the table either way —
            # that is what dedups further on-device probes of the same key.
            # expand_insert computes the suspect mask (fused into the Pallas
            # kernel's own partition pass when that variant is selected).
            enq = is_new & ~suspect if tiered else is_new

            # -- append new states to the queue tail (cumsum compaction) -------
            eb_rows, dp_rows = ebits, depth + 1
            if scatter:
                src_row = jnp.arange(K * A, dtype=jnp.int32) // A
                eb_rows, dp_rows = eb_rows[src_row], dp_rows[src_row]
            with jax.named_scope("append"):
                q_states, q_lo, q_hi, q_ebits, q_depth, tail = _append(
                    c.q_states, c.q_lo, c.q_hi, c.q_ebits, c.q_depth, c.tail,
                    flat, slo, shi, eb_rows, dp_rows, enq,
                )
            new_count = tail - c.tail
            hot_claims = c.hot_claims + is_new.sum().astype(jnp.int32)
            if tiered:
                with jax.named_scope("append"):
                    (
                        s_states, s_lo, s_hi, s_ebits, s_depth, s_tail,
                    ) = _append(
                        c.s_states, c.s_lo, c.s_hi, c.s_ebits, c.s_depth,
                        c.s_tail, flat, slo, shi, eb_rows, dp_rows, suspect,
                    )
                # Host-service exits (non-fatal): spill trigger crossed,
                # suspect buffer near capacity, or queue tail past the
                # compaction threshold.
                service = (
                    (hot_claims >= TRIGGER)
                    | (s_tail > SQ - K * A)
                    | (tail > QL)
                )
                q_full = jnp.bool_(False)  # the host decides queue fatality
            else:
                s_states, s_lo, s_hi = c.s_states, c.s_lo, c.s_hi
                s_ebits, s_depth, s_tail = c.s_ebits, c.s_depth, c.s_tail
                service = jnp.bool_(False)
                # tail beyond S means more uniques than table slots — the
                # table is overflowing anyway; the K*A slack above keeps the
                # DUS and the next pop's dynamic_slice in bounds right up to
                # that point.
                q_full = tail > Q - K * A

            gen_lo, gen_hi = count_add(c.gen_lo, c.gen_hi, gen)

            # -- step telemetry row (obs/ring.py STEP_COLS order) --------------
            # One tiny scatter into the in-carry ring; the host drains it in
            # bulk at chunk boundaries — zero per-step host involvement.
            if TMR:
                tm_row = jnp.stack(
                    [
                        c.steps.astype(jnp.uint32),
                        active.sum().astype(jnp.uint32),
                        gen.astype(jnp.uint32),
                        is_new.sum().astype(jnp.uint32),
                        (tail - head).astype(jnp.uint32),
                        hot_claims.astype(jnp.uint32),
                        s_tail.astype(jnp.uint32),
                        max_depth.astype(jnp.uint32),
                    ]
                )
                tm_rows = c.tm_rows.at[
                    jnp.remainder(c.steps, TMR)
                ].set(tm_row)
            else:
                tm_rows = c.tm_rows
            return _Carry(
                t_lo=t_lo,
                t_hi=t_hi,
                p_lo=p_lo,
                p_hi=p_hi,
                q_states=q_states,
                q_lo=q_lo,
                q_hi=q_hi,
                q_ebits=q_ebits,
                q_depth=q_depth,
                head=head,
                tail=tail,
                gen_lo=gen_lo,
                gen_hi=gen_hi,
                unique_count=c.unique_count + new_count,
                max_depth=max_depth,
                discovered=discovered,
                disc_lo=disc_lo,
                disc_hi=disc_hi,
                overflow=c.overflow
                | (ovf.astype(jnp.uint32) * jnp.uint32(ABORT_TABLE))
                | (q_full.astype(jnp.uint32) * jnp.uint32(ABORT_QUEUE))
                | (service.astype(jnp.uint32) * jnp.uint32(EXIT_SERVICE)),
                steps=c.steps + 1,
                hot_claims=hot_claims,
                s_states=s_states,
                s_lo=s_lo,
                s_hi=s_hi,
                s_ebits=s_ebits,
                s_depth=s_depth,
                s_tail=s_tail,
                summary=c.summary,
                tm_rows=tm_rows,
            )

        def should_continue(
            c: _Carry, req, anym, have_target, target_lo, target_hi, max_steps
        ):
            drained = c.head >= c.tail
            all_found = (P > 0) & (c.discovered == all_bits)
            policy = ((req != 0) & ((c.discovered & req) == req)) | (
                (c.discovered & anym) != 0
            )
            count_hit = have_target & count_ge(
                c.gen_lo, c.gen_hi, target_lo, target_hi
            )
            return (
                (~drained)
                & (~all_found)
                & (~policy)
                & (~count_hit)
                & (c.overflow == 0)
                & (c.steps < max_steps)
            )

        def make_carry(init_states, init_lo, init_hi, n0, seed_lo, seed_hi):
            # Tables are allocated in-trace: a fresh search per dispatch, and
            # no host-side zero-fill and host-to-device copy.
            if kv:
                t_lo = jnp.zeros(2 * S, dtype=jnp.uint32)  # the kv array
                t_hi = jnp.zeros(0, dtype=jnp.uint32)  # placeholder
            else:
                t_lo = jnp.zeros(S, dtype=jnp.uint32)
                t_hi = jnp.zeros(S, dtype=jnp.uint32)
            p_lo = jnp.zeros(S, dtype=jnp.uint32)
            p_hi = jnp.zeros(S, dtype=jnp.uint32)
            init_active = jnp.arange(K, dtype=jnp.int32) < n0
            t_lo, t_hi, p_lo, p_hi, is_new, ovf = insert(
                t_lo, t_hi, p_lo, p_hi,
                init_lo, init_hi,
                jnp.zeros(K, dtype=jnp.uint32), jnp.zeros(K, dtype=jnp.uint32),
                init_active,
            )
            q_states = jnp.zeros((Q, L), dtype=jnp.uint32)
            q_lo = jnp.zeros(Q, dtype=jnp.uint32)
            q_hi = jnp.zeros(Q, dtype=jnp.uint32)
            q_ebits = jnp.zeros(Q, dtype=jnp.uint32)
            q_depth = jnp.zeros(Q, dtype=jnp.uint32)
            slot = jnp.arange(K, dtype=jnp.int32)
            qpos = jnp.where(slot < n0, slot, Q)
            q_states = q_states.at[qpos].set(init_states, mode="drop")
            q_lo = q_lo.at[qpos].set(init_lo, mode="drop")
            q_hi = q_hi.at[qpos].set(init_hi, mode="drop")
            q_ebits = q_ebits.at[qpos].set(jnp.uint32(ebits0), mode="drop")
            q_depth = q_depth.at[qpos].set(jnp.uint32(1), mode="drop")

            return _Carry(
                t_lo=t_lo,
                t_hi=t_hi,
                p_lo=p_lo,
                p_hi=p_hi,
                q_states=q_states,
                q_lo=q_lo,
                q_hi=q_hi,
                q_ebits=q_ebits,
                q_depth=q_depth,
                head=jnp.int32(0),
                tail=n0.astype(jnp.int32),
                gen_lo=seed_lo,
                gen_hi=seed_hi,
                unique_count=is_new.sum().astype(jnp.int32),
                max_depth=jnp.uint32(0),
                discovered=jnp.uint32(0),
                disc_lo=jnp.zeros(max(P, 1), dtype=jnp.uint32),
                disc_hi=jnp.zeros(max(P, 1), dtype=jnp.uint32),
                overflow=ovf.astype(jnp.uint32) * jnp.uint32(ABORT_TABLE),
                steps=jnp.int32(0),
                hot_claims=is_new.sum().astype(jnp.int32),
                s_states=jnp.zeros((SQ, L), dtype=jnp.uint32),
                s_lo=jnp.zeros(SQ, dtype=jnp.uint32),
                s_hi=jnp.zeros(SQ, dtype=jnp.uint32),
                s_ebits=jnp.zeros(SQ, dtype=jnp.uint32),
                s_depth=jnp.zeros(SQ, dtype=jnp.uint32),
                s_tail=jnp.int32(0),
                summary=jnp.zeros(W, dtype=jnp.uint32),
                tm_rows=jnp.zeros((TMR, N_COLS), dtype=jnp.uint32),
            )

        def summary_of(carry: _Carry, stop):
            # Pack every host-facing scalar into ONE small vector so the host
            # reads the whole result in a single device transfer (each fetch
            # is a device-to-host round trip). Layout:
            # [0..9] as before, [10] hot_claims, [11] s_tail, then
            # disc_lo/disc_hi.
            return jnp.concatenate(
                [
                    jnp.stack(
                        [
                            carry.gen_lo,
                            carry.gen_hi,
                            carry.unique_count.astype(jnp.uint32),
                            carry.max_depth,
                            carry.discovered,
                            carry.head.astype(jnp.uint32),
                            carry.tail.astype(jnp.uint32),
                            carry.overflow.astype(jnp.uint32),
                            carry.steps.astype(jnp.uint32),
                            stop.astype(jnp.uint32),
                            carry.hot_claims.astype(jnp.uint32),
                            carry.s_tail.astype(jnp.uint32),
                        ]
                    ),
                    carry.disc_lo,
                    carry.disc_hi,
                ]
            )

        @partial(jax.jit, static_argnums=_SEARCH_STATIC)
        def search(
            init_states,  # uint32[K, L] padded
            init_lo,  # uint32[K]
            init_hi,  # uint32[K]
            required_mask: int,
            any_mask: int,
            target_lo,  # uint32 scalar pair (0, 0 = none)
            target_hi,
            max_steps: int,
            n0,  # int32: number of active seed rows
            seed_lo,  # uint32 pair: pre-dedup init count (host count parity)
            seed_hi,
            target_max_depth,  # uint32 (0 = no limit)
            dyn={},  # operand tables for lowered models (see __init__)
        ):
            model._dyn = dyn
            try:
                req = jnp.uint32(required_mask)
                anym = jnp.uint32(any_mask)
                have_target = (target_lo | target_hi) != 0
                carry = make_carry(
                    init_states, init_lo, init_hi, n0, seed_lo, seed_hi
                )
                carry = jax.lax.while_loop(
                    lambda c: should_continue(
                        c, req, anym, have_target, target_lo, target_hi,
                        max_steps,
                    ),
                    lambda c: body(c, target_max_depth),
                    carry,
                )
                summary = summary_of(carry, jnp.bool_(True))
            finally:
                model._dyn = None
            return (
                carry.t_lo, carry.t_hi, carry.p_lo, carry.p_hi, summary,
                carry.tm_rows,
            )

        @jax.jit
        def seed_k(init_states, init_lo, init_hi, n0, seed_lo, seed_hi):
            return make_carry(init_states, init_lo, init_hi, n0, seed_lo, seed_hi)

        # NOTE: NOT donated by default — the host keeps the pre-chunk carry
        # alive so a table/queue overflow can revert to the last sound chunk
        # boundary (checkpoint-then-raise instead of discarding the run).
        # `donate_chunks=True` flips this trade (see __init__).
        def chunk_k(
            carry: _Carry,
            req,  # uint32 dynamic (one compiled chunk kernel per model/shape)
            anym,
            target_lo,
            target_hi,
            target_max_depth,
            budget,  # int32: max loop steps THIS dispatch
            max_steps,  # int32: global step cap
            dyn={},  # operand tables for lowered models (see __init__)
        ):
            model._dyn = dyn
            try:
                have_target = (target_lo | target_hi) != 0
                start = carry.steps

                def cond(c: _Carry):
                    return should_continue(
                        c, req, anym, have_target, target_lo, target_hi,
                        max_steps,
                    ) & (c.steps < start + budget)

                carry = jax.lax.while_loop(
                    cond, lambda c: body(c, target_max_depth), carry
                )
                stop = ~should_continue(
                    carry, req, anym, have_target, target_lo, target_hi,
                    max_steps,
                )
                out = carry, summary_of(carry, stop)
            finally:
                model._dyn = None
            return out

        chunk_k = (
            partial(jax.jit, donate_argnums=(0,))(chunk_k)
            if self.donate_chunks
            else jax.jit(chunk_k)
        )
        return search, seed_k, chunk_k

    # -- static analysis -------------------------------------------------------

    def audit_step(self):
        """(chunk_fn, abstract_operands, host_slots) for the jaxpr auditor
        (analysis/auditor.py). The carry shapes come from eval_shape over
        the engine's own seed kernel — abstract only, no device work. The
        chunked dispatch re-uploads nothing per step (host_slots empty):
        the auditor's while-body extraction reports the per-step cost."""
        K, L = self.batch_size, self.model.lanes
        sds = jax.ShapeDtypeStruct
        u32 = lambda *s: sds(s, jnp.uint32)  # noqa: E731
        carry = jax.eval_shape(
            self._seed_k,
            u32(K, L), u32(K), u32(K), sds((), jnp.int32), u32(), u32(),
        )
        dyn = jax.tree.map(
            lambda x: sds(x.shape, x.dtype), self._dyn_dev
        )
        args = (
            carry, u32(), u32(), u32(), u32(), u32(),
            sds((), jnp.int32), sds((), jnp.int32), dyn,
        )
        return self._chunk_k, args, ()

    # -- host entry ------------------------------------------------------------

    def warm_start(self, entry, kind: Optional[str] = None) -> int:
        """Preload a published corpus entry before the first run() — the
        resident engine's leg of the ONE warm-start seam (store/warm.py;
        knobs.WARM_KINDS), closing the gap where this engine started cold
        on every job.

        A COMPLETE entry replays: the prefix lands in the spill tier and
        the Bloom summary, the summary is patched into the seeded carry
        (make_carry builds an empty one), so the init frontier's children
        all resolve as spilled duplicates at the stop-drain and the run
        collapses to one expansion wave; the result then replays the
        publisher's bookkeeping. A PARTIAL entry (corpus v2) CONTINUES:
        the frontier snapshot is packed into a host-built carry (the
        load_checkpoint recipe against an empty hot table — the visited
        prefix dedups through the preloaded spill tier), counters and
        discoveries restore from the entry's meta, and run() finishes the
        remainder. The caller owns key discipline (`warm.can_replay` /
        `warm.can_continue`, and `warm.salvage_delta` for the Spec-CI
        "delta" rung — pass the salvaged entry it returns with
        kind="delta"); a replay must use the publisher's finish policy.
        Returns the state count preloaded."""
        if self._store is None:
            raise ValueError(
                "warm_start requires store='tiered' (known states are "
                "dedup-filtered through the spill tier's Bloom suspect "
                "path)"
            )
        if self._carry is not None:
            raise ValueError("warm_start must run before the first run()")
        n = warm_seam.preload_store(self._store, entry)
        self._warm_states = n
        if getattr(entry, "complete", True):
            self._warm = dict(entry.meta)
            self._warm_kind = kind or "exact"
            # The seeded carry's summary is patched in run() — the preload
            # above already rebuilt self._store.summary_np.
            self._warm_summary_pending = True
            return n
        if entry.frontier is None:
            raise ValueError(
                "partial corpus entry has no frontier snapshot (coverage-"
                "only); a continuation needs the publisher's cut frontier"
            )
        self._warm_kind = kind if kind == "delta" else "partial"
        meta = entry.meta
        f = entry.frontier
        nf = int(np.asarray(f["lo"]).size)
        if nf > (1 << self.queue_log2):
            raise ValueError(
                f"partial entry's frontier ({nf} rows) exceeds the queue "
                f"(queue_log2={self.queue_log2}); raise queue_log2"
            )
        model = self.model
        P = len(self.props)
        Q, L = self._Q, model.lanes
        q_states = np.zeros((Q, L), np.uint32)
        q_lo = np.zeros(Q, np.uint32)
        q_hi = np.zeros(Q, np.uint32)
        q_ebits = np.zeros(Q, np.uint32)
        q_depth = np.zeros(Q, np.uint32)
        q_states[:nf] = np.asarray(f["states"], np.uint32)
        q_lo[:nf] = np.asarray(f["lo"], np.uint32)
        q_hi[:nf] = np.asarray(f["hi"], np.uint32)
        q_ebits[:nf] = warm_seam.pack_ebits(np.asarray(f["ebits"]))
        q_depth[:nf] = np.asarray(f["depths"], np.uint32)
        disc = meta.get("discoveries", {})
        discovered = 0
        disc_lo = np.zeros(max(P, 1), np.uint32)
        disc_hi = np.zeros(max(P, 1), np.uint32)
        for i, p in enumerate(self.props):
            if p.name in disc:
                discovered |= 1 << i
                fp = int(disc[p.name])
                disc_lo[i] = np.uint32(fp & 0xFFFFFFFF)
                disc_hi[i] = np.uint32(fp >> 32)
        S = 1 << self.table_log2
        sc = int(meta["state_count"])
        fields = dict(
            t_lo=np.zeros(S, np.uint32),
            t_hi=np.zeros(S, np.uint32),
            p_lo=np.zeros(S, np.uint32),
            p_hi=np.zeros(S, np.uint32),
            q_states=q_states, q_lo=q_lo, q_hi=q_hi,
            q_ebits=q_ebits, q_depth=q_depth,
            head=np.int32(0), tail=np.int32(nf),
            gen_lo=np.uint32(sc & 0xFFFFFFFF),
            gen_hi=np.uint32(sc >> 32),
            unique_count=np.int32(meta["unique_count"]),
            max_depth=np.uint32(meta["max_depth"]),
            discovered=np.uint32(discovered),
            disc_lo=disc_lo, disc_hi=disc_hi,
            overflow=np.uint32(0), steps=np.int32(0),
            hot_claims=np.int32(0),
            s_states=np.zeros((self._SQ, L), np.uint32),
            s_lo=np.zeros(self._SQ, np.uint32),
            s_hi=np.zeros(self._SQ, np.uint32),
            s_ebits=np.zeros(self._SQ, np.uint32),
            s_depth=np.zeros(self._SQ, np.uint32),
            s_tail=np.int32(0),
            summary=self._store.summary_np,
            tm_rows=np.zeros((self._TMR, N_COLS), np.uint32),
        )
        self._carry = _Carry(
            **{k: jax.device_put(jnp.asarray(v)) for k, v in fields.items()}
        )
        return n

    def run(
        self,
        finish_when: HasDiscoveries = HasDiscoveries.ALL,
        target_state_count: Optional[int] = None,
        target_max_depth: Optional[int] = None,
        timeout: Optional[float] = None,
        max_steps: int = 1 << 30,
        budget: Optional[int] = None,
        progress: Optional[callable] = None,
    ) -> SearchResult:
        """Run (or resume) the search.

        Without `budget`, the whole search is ONE device dispatch (fastest;
        no suspension possible). With `budget`, the search runs in chunks of
        at most `budget` loop steps per dispatch, which enables:
        - `progress(state_count, unique_count, max_depth)` between chunks,
        - `timeout` (polled between chunks, so it overshoots by <=1 chunk),
        - `checkpoint()` / resume (a later `run()` continues the carry), and
        - recoverable overflow: the carry reverts to the last chunk boundary
          so `checkpoint()` + `load_checkpoint(..., table_log2=bigger)` can
          continue the run instead of discarding it.
        """
        # Tiered runs are always chunked: the host must regain control for
        # spill eviction and suspect resolution (the ISSUE's "exit to host
        # on high-water instead of aborting").
        if self._store is not None and budget is None and timeout is None:
            budget = 1 << 20
        chunked, budget = _resolve_chunking(
            budget, timeout, progress, self._carry
        )
        start = time.monotonic()
        self._begin_run()
        dev, n0, n_raw = self._seed_rows()
        vacuous = self._vacuous_result(finish_when, n0, n_raw, start)
        if vacuous is not None:
            return vacuous

        required_mask, any_mask = _finish_masks(finish_when, self.props)
        target = int(target_state_count or 0)
        t_lo32 = jnp.uint32(target & 0xFFFFFFFF)
        t_hi32 = jnp.uint32(target >> 32)
        tmd = jnp.uint32(target_max_depth or 0)

        timed_out = False
        if not chunked:
            # Chaos-plane boundary: a simulated OOM/XLA fault lands before
            # the whole-search dispatch (faults/plan.py).
            maybe_fault("engine.step", engine="resident")
            args = (
                *dev,
                required_mask,
                any_mask,
                t_lo32,
                t_hi32,
                max_steps,
                jnp.int32(n0),
                jnp.uint32(n_raw & 0xFFFFFFFF),
                jnp.uint32(n_raw >> 32),
                tmd,
                self._dyn_dev,
            )
            with self._tracer.span("resident.search", cat="engine") as span:
                exe, cached = self._search_executable(args)
                span.set(cached=int(cached))
                with self._stages.stage("execute"):
                    t_lo, t_hi, p_lo, p_hi, summary, tm_rows = exe(*(
                        a for i, a in enumerate(args)
                        if i not in _SEARCH_STATIC
                    ))
                    # ONE device->host transfer for the entire result.
                    summary = np.asarray(summary)
            # Whole-search dispatch: one bulk drain at the end (the ring
            # holds the LAST 2^telemetry_log2 steps; earlier rows count as
            # dropped). The window is the execute stage alone: no tracing,
            # lowering or executable load.
            self._drain_ring(tm_rows, int(summary[8]),
                             self._stages.phases["execute_s"] * 1e6)
            # On overflow the failed run's tables are unsound AND a previous
            # run's snapshot must not silently serve paths for states this
            # run discovered — invalidate (matches the sharded engine).
            self._last_tables = (
                (t_lo, t_hi, p_lo, p_hi) if not summary[7] else None
            )
        else:
            if self._carry is None:
                self._carry = self._seed_k(
                    *dev,
                    jnp.int32(n0),
                    jnp.uint32(n_raw & 0xFFFFFFFF),
                    jnp.uint32(n_raw >> 32),
                )
                if self._warm_summary_pending:
                    # Warm replay: make_carry built an empty Bloom summary;
                    # patch in the preloaded one (warm_start already rebuilt
                    # the store's words) so the very first expansion's
                    # children dedup-filter against the corpus prefix.
                    self._warm_summary_pending = False
                    self._carry = self._carry._replace(
                        summary=jax.device_put(
                            jnp.asarray(self._store.summary_np)
                        )
                    )
            req = jnp.uint32(required_mask)
            anym = jnp.uint32(any_mask)
            if self.donate_chunks:
                # Donating self._carry deletes the buffers a previous run's
                # _last_tables may alias; drop the alias now so a later
                # reconstruct_path gets a clear "no tables" error instead of
                # jax's "Array has been deleted".
                self._last_tables = None
            while True:
                # Chaos-plane boundary: faults land BEFORE the dispatch, so
                # a faulted chunk never half-updates the retained carry.
                maybe_fault("engine.step", engine="resident")
                t_chunk0 = time.monotonic()
                with self._tracer.span("resident.chunk", cat="engine"):
                    carry, summary = self._chunk_k(
                        self._carry,
                        req,
                        anym,
                        t_lo32,
                        t_hi32,
                        tmd,
                        jnp.int32(budget),
                        jnp.int32(max_steps),
                        self._dyn_dev,
                    )
                    summary = np.asarray(summary)  # one small transfer/chunk
                self._drain_ring(carry.tm_rows, int(summary[8]),
                                 (time.monotonic() - t_chunk0) * 1e6)
                code = int(summary[7])
                if code & EXIT_SERVICE and not (
                    code & (ABORT_TABLE | ABORT_QUEUE)
                ):
                    # Non-fatal host-service exit (tiered store): drain the
                    # suspect buffer, evict past-high-water buckets, compact
                    # the queue, clear the flag, resume the same carry.
                    self._carry = carry
                    self._service()
                    continue
                if code:  # fatal overflow (abort code)
                    self._last_abort = code & (ABORT_TABLE | ABORT_QUEUE)
                    reason = _abort_reason(self._last_abort)
                    if self.donate_chunks:
                        # The pre-chunk carry was donated into the dispatch;
                        # there is no sound state to recover.
                        self._carry = None
                        raise RuntimeError(
                            f"hash table or queue full — {reason}; "
                            "donate_chunks=True sacrificed the recovery "
                            "carry — rerun with the larger size (or "
                            "donate_chunks=False for checkpoint-then-regrow "
                            "recovery)"
                        )
                    # Revert to the pre-chunk carry so checkpoint() +
                    # load_checkpoint(table_log2=bigger) can resume exactly
                    # from the last sound chunk boundary — and point the
                    # reconstruction snapshot at that same boundary so
                    # paths reflect THIS run, not a stale prior one.
                    self._last_tables = self._carry_tables()
                    self._parent_map = None
                    raise RuntimeError(
                        f"hash table or queue full — {reason}; the search "
                        "carry was kept at the last chunk boundary — "
                        "checkpoint(path) then "
                        "ResidentSearch.load_checkpoint(model, path, ...) "
                        "with the named size raised to continue without "
                        "losing the run (the abort reason is preserved in "
                        "the checkpoint and load_checkpoint enforces the "
                        "growth)"
                    )
                self._carry = carry
                # Chaos-plane boundary: simulated preemption mid-run —
                # raised at a chunk boundary where the carry is sound, the
                # same place a real TPU preemption would surface when the
                # host regains control.
                maybe_fault("engine.chunk", engine="resident")
                if progress is not None:
                    gl, gh, uc, md = (int(x) for x in summary[:4])
                    progress(gl | (gh << 32), uc, md)
                if summary[9]:  # stop: search finished (or hit max_steps)
                    if self._store is not None and int(summary[11]) > 0:
                        # The queue drained with suspects still buffered:
                        # resolve them — confirmed-new rows reopen the
                        # frontier; the next chunk re-evaluates the stop
                        # with an empty buffer, so this cannot loop.
                        self._service()
                        continue
                    break
                if timeout is not None and time.monotonic() - start > timeout:
                    timed_out = True
                    break
            self._last_tables = self._carry_tables()

        overflow = int(summary[7])
        if overflow:
            self._last_abort = overflow
            raise RuntimeError(
                f"hash table or queue full — {_abort_reason(overflow)}"
            )
        return self._result(
            start, chunked,
            **summary_fields(summary[None], len(self.props), timed_out),
        )

    def _search_executable(self, args: tuple):
        """`(compiled, cached)`: the whole-search program (`jit_search`) for
        `args`, built in stages (`Stages.executable`); kept per static
        arguments, operand-table shapes and the seed rows' device, which is
        what jit's own cache would key on here, and shared across engines of
        one definition and configuration."""
        dyn = args[-1]
        key = (
            tuple(args[i] for i in _SEARCH_STATIC),
            jax.tree.structure(dyn),
            tuple((x.shape, x.dtype) for x in jax.tree.leaves(dyn)),
            args[0].sharding,
        )
        return self._stages.executable(
            self._kernel, args, key, self._definition
        )

    def _service(self) -> None:
        """Host half of the tiered store, run between chunked dispatches on
        an EXIT_SERVICE (or a drained queue with buffered suspects):

        1. compact the frontier queue (live rows shift to the front — with
           spilling, total uniques exceed the table, so the append-only
           tail would otherwise grow without bound);
        2. drain the suspect buffer: exact membership against the host
           spill store; confirmed duplicates are dropped, Bloom false
           positives are injected at the queue tail and counted unique;
        3. evict past-high-water occupancy: cold non-full buckets move to
           the spill tier and the Bloom summary absorbs their keys.

        The carry is rebuilt with the service bit cleared; the caller
        resumes the same while_loop."""
        # Chaos-plane boundary: the whole host half is retriable from the
        # suspended carry (no host/device state mutated yet) — before this
        # boundary the tiered service raises below were failure surfaces
        # the chaos plane could not reach (found by srlint SR004).
        maybe_fault("store.service", engine="resident")
        c = self._carry
        L = self.model.lanes
        SQ = self._SQ
        head, tail = int(c.head), int(c.tail)
        s_tail = int(c.s_tail)
        hot = int(c.hot_claims)
        unique = int(c.unique_count)
        q_states, q_lo, q_hi = c.q_states, c.q_lo, c.q_hi
        q_ebits, q_depth = c.q_ebits, c.q_depth

        if head > 0:
            with self._tracer.span("tiered.queue_compact", cat="store"):
                q_states, q_lo, q_hi, q_ebits, q_depth = _compact_queue(
                    q_states, q_lo, q_hi, q_ebits, q_depth, jnp.int32(head)
                )
            tail -= head
            head = 0
            self._q_compacted = True
        if tail > (1 << self.queue_log2):
            # The LIVE frontier exceeds the queue even compacted — a real
            # capacity wall, recoverable exactly like the device-store
            # queue abort (the carry is sound; checkpoint + regrow).
            self._carry = c._replace(
                q_states=q_states, q_lo=q_lo, q_hi=q_hi,
                q_ebits=q_ebits, q_depth=q_depth,
                head=jnp.int32(head), tail=jnp.int32(tail),
                overflow=jnp.uint32(0),
            )
            self._last_abort = ABORT_QUEUE
            raise RuntimeError(
                f"frontier queue full — {_abort_reason(ABORT_QUEUE)}; the "
                "live frontier exceeds the compacted queue — checkpoint() "
                "then load_checkpoint with a larger queue_log2 to continue"
            )

        if s_tail > 0:
            self._tracer.instant(
                "tiered.suspect_resolve", cat="store", suspects=s_tail
            )
            sus_lo = np.asarray(c.s_lo[:s_tail])
            sus_hi = np.asarray(c.s_hi[:s_tail])
            dup = self._store.resolve_suspects(sus_lo, sus_hi)
            keep = ~dup
            n_conf = int(keep.sum())
            if n_conf:
                blk_states = np.zeros((SQ, L), dtype=np.uint32)
                blk_lo = np.zeros(SQ, dtype=np.uint32)
                blk_hi = np.zeros(SQ, dtype=np.uint32)
                blk_eb = np.zeros(SQ, dtype=np.uint32)
                blk_dp = np.zeros(SQ, dtype=np.uint32)
                blk_states[:n_conf] = np.asarray(c.s_states[:s_tail])[keep]
                blk_lo[:n_conf] = sus_lo[keep]
                blk_hi[:n_conf] = sus_hi[keep]
                blk_eb[:n_conf] = np.asarray(c.s_ebits[:s_tail])[keep]
                blk_dp[:n_conf] = np.asarray(c.s_depth[:s_tail])[keep]
                q_states, q_lo, q_hi, q_ebits, q_depth = _inject_rows(
                    q_states, q_lo, q_hi, q_ebits, q_depth,
                    jnp.int32(tail),
                    jnp.asarray(blk_states), jnp.asarray(blk_lo),
                    jnp.asarray(blk_hi), jnp.asarray(blk_eb),
                    jnp.asarray(blk_dp),
                )
                tail += n_conf
                unique += n_conf

        t_lo, t_hi, p_lo, p_hi = c.t_lo, c.t_hi, c.p_lo, c.p_hi
        if hot >= self._spill_trigger:
            with self._tracer.span("tiered.evict", cat="store"):
                t_lo, t_hi, p_lo, p_hi, n_ev = self._store.evict(
                    t_lo, t_hi, p_lo, p_hi, hot
                )
            if n_ev == 0:
                raise RuntimeError(
                    "tiered store could not free any bucket (every bucket "
                    "is full and pinned); raise table_log2 or lower "
                    "high_water"
                )
            hot -= n_ev

        self._carry = c._replace(
            t_lo=t_lo, t_hi=t_hi, p_lo=p_lo, p_hi=p_hi,
            q_states=q_states, q_lo=q_lo, q_hi=q_hi,
            q_ebits=q_ebits, q_depth=q_depth,
            head=jnp.int32(head), tail=jnp.int32(tail),
            unique_count=jnp.int32(unique),
            hot_claims=jnp.int32(hot),
            s_tail=jnp.int32(0),
            # A FRESH upload, never the store's cached device array: with
            # donate_chunks the next dispatch donates (deletes) whatever
            # sits in the carry, and a later no-eviction service would
            # otherwise re-install the same deleted buffer. The words are
            # tiny; one upload per (rare) service event is free.
            summary=jnp.asarray(self._store.summary_np),
            overflow=jnp.uint32(0),
        )

    def set_dyn_tables(self, tables: dict) -> None:
        """Swap the lowered model's operand tables. Same pytree keys and
        shapes reuse the already-compiled kernels untouched (no retrace);
        `refine_check` calls this between rounds after `extend()`."""
        self._dyn_dev = jax.device_put(tables)

    def reset(self) -> None:
        super().reset()
        self._last_abort = 0  # a fresh run owes nothing to an old overflow

    # -- the engine's side of SearchHost ---------------------------------------

    def _seed_operands(self, st, lo, hi, n0):
        # Device-side once, so repeat runs skip the host-to-device copies.
        return jax.device_put((st, lo, hi))

    def _queue_rows(self, evaluated_only: bool, start: int) -> list:
        # Rows [start, end) sliced on device: one transfer of what is read.
        end = int(self._carry.head if evaluated_only else self._carry.tail)
        return [np.asarray(self._carry.q_states[start:end])]

    def _checkpoint_arrays(self, path: str) -> dict:
        if self.table_layout != "split":
            # load_checkpoint refuses kv checkpoints (regrow is split-only);
            # fail at SAVE time rather than handing back a file that can
            # never be restored.
            raise NotImplementedError(
                "checkpointing is split-layout-only for now; use "
                "table_layout='split' (default) for checkpoint/resume runs"
            )
        with self._tracer.span("checkpoint", cat="engine", path=path):
            return to_host(self._carry._asdict())

    def _checkpoint_meta(self) -> dict:
        return {
            "queue_log2": self.queue_log2,
            "table_layout": self.table_layout,
            # Why the run aborted (0 = clean suspension): lets
            # load_checkpoint refuse a resume that would hit the same wall
            # again.
            "abort_reason": self._last_abort,
        }

    def _set_carry(self, fields: dict) -> None:
        self._carry = _Carry(
            **{f: jax.device_put(jnp.asarray(v[0])) for f, v in fields.items()}
        )

    @classmethod
    def load_checkpoint(
        cls,
        model: TensorModel,
        path: str,
        batch_size: Optional[int] = None,
        table_log2: Optional[int] = None,
        donate_chunks: bool = False,
        queue_log2: Optional[int] = None,
    ) -> "ResidentSearch":
        """Rebuild a suspended search from a `checkpoint` file. Passing a
        larger `table_log2` re-hashes the visited set into the bigger table
        (the recovery path for an overflow abort); the queue is padded to the
        matching capacity. The next `run()` continues where the dump left
        off. The CRC footer is verified; a corrupt current generation falls
        back to `path + ".prev"` instead of raising."""
        data, meta = cls._read_checkpoint(model, path)
        if meta.get("table_layout", "split") != "split":
            raise NotImplementedError(
                "checkpoint resume is split-layout-only for now; rerun the "
                "search with table_layout='split' (default) if you need "
                "checkpoint/regrow"
            )
        kw = cls._resume_kwargs(meta, batch_size, table_log2)
        log2 = kw["table_log2"]
        meta_q = meta.get("queue_log2", meta["table_log2"])
        if queue_log2 is None:
            # Default-sized checkpoints (queue == table) keep following the
            # table through a regrow — the overflow-recovery path needs the
            # bigger queue. An explicitly right-sized queue is preserved.
            queue_log2 = log2 if meta_q == meta["table_log2"] else meta_q
        # Enforce that the resource the aborted run actually ran out of
        # (preserved in meta by checkpoint()) grew — a same-size resume
        # would hit the identical wall and lose the recovery attempt.
        abort = int(meta.get("abort_reason", 0))
        if abort & ABORT_TABLE and log2 <= meta["table_log2"]:
            raise ValueError(
                "this checkpoint was taken after a hash-table overflow "
                f"(table_log2={meta['table_log2']}); pass a larger "
                "table_log2 to load_checkpoint to regrow the table"
            )
        if abort & ABORT_QUEUE and queue_log2 <= meta_q:
            raise ValueError(
                "this checkpoint was taken after a frontier-queue overflow "
                f"(queue_log2={meta_q}); pass a larger queue_log2 to "
                "load_checkpoint to regrow the queue"
            )
        rs = cls(
            model, donate_chunks=donate_chunks, queue_log2=queue_log2, **kw
        )
        fields = rs._restore(data, meta)
        ka = rs.batch_size * model.max_actions
        # The suspect buffer is sized by batch_size x max_actions: a resume
        # with a different batch size renormalizes it like the queue (live
        # rows [0, s_tail) are preserved; shrinking past them is refused).
        if meta.get("store"):
            s_tail_live = int(fields["s_tail"][0])
            if s_tail_live > rs._SQ - ka:
                raise ValueError(
                    "batch_size too small for the checkpointed suspect "
                    f"buffer ({s_tail_live} live suspects); resume with the "
                    "original batch_size"
                )
            _fit_rows(fields, _SUSPECTS, rs._SQ)
        # Clear the abort code on resume: a chunked checkpoint sits at a
        # sound boundary (code 0) already, but a SEED-insert overflow leaves
        # its code in the carry itself — and the guards above have just
        # enforced that whatever resource aborted has grown, so carrying the
        # old code forward would only re-abort the recovered run on its
        # first step.
        fields["overflow"] = np.zeros_like(fields["overflow"])
        # Live queue rows sit at [0, tail); the guard makes the queue's
        # normalization a pure extension — silently dropping frontier rows
        # would corrupt the resumed search.
        ckpt_tail = int(fields["tail"][0])
        if ckpt_tail > rs._Q - ka:
            raise ValueError(
                f"queue_log2={rs.queue_log2} gives {rs._Q} rows but the "
                f"checkpointed frontier tail is {ckpt_tail}; the queue "
                "cannot shrink below the live frontier"
            )
        rs._set_carry(fields)
        return rs
