"""Multi-chip frontier search: fingerprint-sharded visited set + ICI
all-to-all successor exchange.

This is the TPU-native replacement for the reference's work-stealing job
market (ref: src/job_market.rs:149-176): instead of idle threads stealing
slices of a shared deque, every chip owns a fingerprint range
(`owner(fp) == axis_index`) and each expansion step ends with one
`lax.all_to_all` that routes every generated successor to its owner chip.
Termination detection replaces the market's `open_count` quiescence protocol
(ref: src/job_market.rs:109-127) with a `psum` of per-chip queue occupancy;
discovery early-exit (`HasDiscoveries`, ref: src/has_discoveries.rs:5-42)
becomes an all-gather + OR of per-chip discovery bitmasks. The whole search —
queue pop, property masks, expansion, shuffle, insert — runs as ONE
`lax.while_loop` inside ONE `shard_map`-over-`Mesh` dispatch, so multi-host
meshes ride ICI/DCN with zero host round-trips mid-search.

Everything is 32-bit on device (u32 fingerprint pairs; u32-pair counters) —
TPUs emulate 64-bit integer ops, so the round-1 u64 design paid emulation tax
on every hot op.

Sharding invariants:
- `owner(fp) = fp.lo % n_chips` while the per-chip table bucket uses
  `fp.hi % n_buckets` (tensor/hashtable.py), so sharding does not skew table
  occupancy even when both are powers of two.
- Each unique state is inserted/enqueued on exactly one chip, so per-chip
  `state_count`/`unique_count` sum to the global totals, and the per-chip
  queue can never hold more rows than the per-chip table has slots (the same
  capacity argument as the single-chip resident engine).
- The all-to-all send buffer reserves `dest_capacity` rows per destination.
  The default is 2x the per-destination MEAN (min 64 extra rows, rounded up
  to full 128-lane tiles, capped at the absolute bound batch_size *
  max_actions): owners are `fp.lo % N` on splitmix-mixed fingerprints, so
  per-destination counts are binomial and a 2x-mean buffer overflows with
  probability ~exp(-mean/3) per step — astronomically rare at engine batch
  sizes, and DETECTED (route_ovf -> RuntimeError naming dest_capacity)
  rather than silent when a model defeats the hash. The absolute bound is
  available by passing dest_capacity=batch_size*max_actions explicitly; the
  round-4 default reserved that bound per destination, which made every
  all-to-all, insert, and append run on N x the real traffic — measured as
  a 5.4x sharding overhead on the 8-device virtual mesh.
- Routing positions come from per-destination cumsums (static unroll over the
  N destinations), not a sort: the received batch may contain duplicates and
  the hash-table insert resolves them (phase-3 arena).
"""

from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..faults.plan import maybe_fault
from ..knobs import WARM_KINDS
from ..obs import N_COLS
from ..store import warm as warm_seam
from ..core.discovery import HasDiscoveries
from ..core.model import Expectation
from ..tensor.frontier import (
    SearchResult,
    append_new,
    append_new_dus,
    append_rows,
    resolve_append,
    count_add,
    count_ge,
    pop_batch,
    record_discovery as _record_impl,
    state_fingerprint,
)
from ..tensor.inserts import resolve_insert
from ..tensor.model import TensorModel
from ..tensor.search_host import (
    ABORT_QUEUE,
    ABORT_TABLE,
    EXIT_SERVICE,
    SearchHost,
    _compact_queue,
    _finish_masks,
    _inject_rows,
    _resolve_chunking,
    summary_fields,
    to_host,
)

# Per-shard service kernels: the single-device queue compaction / suspect
# injection, vmapped over the shard axis so one dispatch services every
# shard without gathering the [N, Q, L] queues to host (see _service).
_compact_queue_sharded = None
_inject_rows_sharded = None


def _service_kernels():
    global _compact_queue_sharded, _inject_rows_sharded
    if _compact_queue_sharded is None:
        _compact_queue_sharded = jax.jit(jax.vmap(_compact_queue))
        _inject_rows_sharded = jax.jit(jax.vmap(_inject_rows))
    return _compact_queue_sharded, _inject_rows_sharded

# Sharded-only abort bit (on top of the resident engine's codes): the
# all-to-all send buffer's per-destination capacity overflowed — wants a
# fresh run with a larger dest_capacity, not a table regrow.
ABORT_ROUTE = 8


def make_mesh(n_devices: Optional[int] = None, axis: str = "d") -> Mesh:
    """A 1-D device mesh over the first `n_devices` visible devices.

    Multi-host: under `jax.distributed.initialize()`, `jax.devices()` is the
    GLOBAL device list, so the same call assembles a cross-host mesh and the
    search's all_to_all/psum ride ICI within a slice and DCN across hosts —
    no code changes in the engine (the reference's multi-machine story is
    manual spawn-per-host; here it is one flag on the launcher)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices but only {len(devices)} "
                "are visible (set --xla_force_host_platform_device_count "
                "for virtual CPU meshes)"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


class _Carry(NamedTuple):
    t_lo: jnp.ndarray  # uint32[S]   per-chip table shard
    t_hi: jnp.ndarray  # uint32[S]
    p_lo: jnp.ndarray  # uint32[S]
    p_hi: jnp.ndarray  # uint32[S]
    q_states: jnp.ndarray  # uint32[Q, L]  per-chip frontier queue
    q_lo: jnp.ndarray  # uint32[Q]
    q_hi: jnp.ndarray  # uint32[Q]
    q_ebits: jnp.ndarray  # uint32[Q]
    q_depth: jnp.ndarray  # uint32[Q]
    head: jnp.ndarray  # int32
    tail: jnp.ndarray  # int32
    gen_lo: jnp.ndarray  # uint32 GLOBAL generated-count pair (identical on all chips)
    gen_hi: jnp.ndarray  # uint32
    unique_count: jnp.ndarray  # int32 (local; host sums shards)
    max_depth: jnp.ndarray  # uint32 (local)
    discovered: jnp.ndarray  # uint32 global OR of discovery bits
    disc_lo: jnp.ndarray  # uint32[P] locally-witnessed discovery fps
    disc_hi: jnp.ndarray  # uint32[P]
    cont: jnp.ndarray  # bool global continue flag
    overflow: jnp.ndarray  # uint32 abort code (ABORT_*|EXIT_SERVICE bits)
    steps: jnp.ndarray  # int32
    # -- tiered store (store="tiered"; zero-sized placeholders otherwise) ------
    hot_claims: jnp.ndarray  # int32: occupied local-table slots
    s_states: jnp.ndarray  # uint32[SQ, L] per-shard suspect buffer
    s_lo: jnp.ndarray  # uint32[SQ]
    s_hi: jnp.ndarray  # uint32[SQ]
    s_ebits: jnp.ndarray  # uint32[SQ]
    s_depth: jnp.ndarray  # uint32[SQ]
    s_tail: jnp.ndarray  # int32
    summary: jnp.ndarray  # uint32[W] per-shard Bloom words (read-only in-loop)
    # -- step telemetry (obs/ring.py; zero-row placeholder when disabled) ------
    tm_rows: jnp.ndarray  # uint32[TMR, N_COLS] per-shard in-carry metrics ring


class ShardedSearch(SearchHost):
    """Whole-search multi-chip engine for a `TensorModel` over a 1-D mesh;
    its host side (result, paths, dump, checkpoint) is `SearchHost`'s over
    the chip axis."""

    _CKPT_SHARD_AXIS = True
    _carry_type = _Carry

    # Warm-knob registry pins (knobs.check_registry): the kind vocabulary
    # and the mechanics both alias the ONE seam, never a local copy.
    WARM_KINDS = WARM_KINDS
    WARM_SEAM = warm_seam

    def __init__(
        self,
        model: TensorModel,
        mesh: Optional[Mesh] = None,
        batch_size: int = 1024,
        table_log2: int = 18,
        dest_capacity: Optional[int] = None,
        donate_chunks: bool = False,
        append: Optional[str] = None,
        insert_variant: str = "sort",
        store: str = "device",
        high_water: float = 0.85,
        low_water: Optional[float] = None,
        summary_log2: int = 20,
        telemetry: bool = True,
        telemetry_log2: int = 12,
        tracer=None,
    ):
        """`donate_chunks=True` donates the per-shard carry to each chunked
        dispatch so XLA updates the sharded tables/queues in place instead
        of copying them per dispatch (same trade as the resident engine:
        overflow loses the recovery carry — see ResidentSearch.__init__).
        `append` picks the queue-append variant exactly as on
        ResidentSearch (backend-informed default; "scatter" or "dus").
        `store="tiered"` gives each shard its own spill tier: a RANK-LOCAL
        host fingerprint store plus a per-shard device Bloom summary, with
        the same water-mark semantics as the single-device engines — every
        shard spills the states it owns, so the fingerprint→owner map and
        the all-to-all routing are untouched (single-process meshes only:
        servicing needs every shard addressable).

        `telemetry=True` (default) gives each SHARD a device-resident ring
        of 2^telemetry_log2 obs.STEP_COLS rows in the while_loop carry,
        drained in bulk at chunk boundaries (steps are globally synced, so
        per-step rows align across shards — the drain sums extensive
        columns and tracks per-shard claims for the imbalance digest in
        `SearchResult.detail["telemetry"]`). `tracer` records host phases
        as Chrome trace events."""
        self.donate_chunks = donate_chunks
        self.mesh = mesh if mesh is not None else make_mesh()
        (self.axis,) = self.mesh.axis_names
        self.n_chips = self.mesh.devices.size
        self.append = resolve_append(
            append, self.mesh.devices.flat[0].platform
        )
        if store == "tiered" and jax.process_count() > 1:
            raise NotImplementedError(
                "store='tiered' on the sharded engine requires a "
                "single-process mesh (the host service must address every "
                "shard's carry)"
            )
        # Per-destination all-to-all capacity (see module docstring): default
        # 2x the binomial mean + 64 slack, tile-rounded, capped at the
        # absolute bound K*A. Overflow is detected and surfaced as a
        # RuntimeError, never a silent drop.
        ka = batch_size * model.max_actions
        mean = -(-ka // self.n_chips)  # ceil
        self.dest_capacity = (
            dest_capacity
            if dest_capacity is not None
            else min(ka, -(-(2 * mean + 64) // 128) * 128)
        )
        self._stores = None  # rank-local TieredStore per shard (tiered only)
        # insert_variant: the same visited-set designs the single-device
        # engines race (the per-shard table layout is always split here).
        # A shard's per-step claims are bounded by the all-to-all receive
        # width N*C. The calibration comparator prices ONE shard's lockstep
        # step (per-shard batch/table — every shard dispatches the same
        # program).
        self._init_host(
            "sharded", model, batch_size, table_log2, insert_variant, store,
            (high_water, low_water, summary_log2), telemetry, telemetry_log2,
            tracer, step_claims=self.n_chips * self.dest_capacity,
            append_tile=batch_size if self.append == "dus" else 0,
        )
        # The exchange counters; `_exchange_mark` is the (steps, generated)
        # point up to which the exchange is counted.
        self._counters.update(exchange_rows_total=0, exchange_slots_total=0)
        self._exchange_mark = None
        # The model's definition and every argument `_build` reads, the
        # mesh's devices included: engines that agree on it share one
        # search executable (Stages.executable).
        dk = model.definition_key()
        self._definition = None if dk is None else (
            dk, batch_size, table_log2, self.dest_capacity, self.append,
            insert_variant, store, high_water, low_water, summary_log2,
            telemetry, telemetry_log2, donate_chunks, self.axis,
            tuple(d.id for d in self.mesh.devices.flat),
        )
        self._kernel, self._seed_k, self._chunk_k = self._build()

    def store_stats(self) -> Optional[dict]:
        """Aggregated per-tier counters across shards (None with the plain
        device store); `per_shard_spilled` exposes the rank-local split."""
        if self._stores is None:
            return None
        hot = (
            [int(x) for x in np.asarray(self._carry.hot_claims)]
            if self._carry is not None
            else [0] * self.n_chips
        )
        per = [s.stats(h) for s, h in zip(self._stores, hot)]
        return {
            "store": "tiered",
            "hot_fill": round(max(p["hot_fill"] for p in per), 4),
            "spilled_states": sum(p["spilled_states"] for p in per),
            "spill_events": sum(p["spill_events"] for p in per),
            "suspects_checked": sum(p["suspects_checked"] for p in per),
            "suspects_dup": sum(p["suspects_dup"] for p in per),
            "per_shard_spilled": [p["spilled_states"] for p in per],
        }

    def _build(self):
        model = self.model
        mesh = self.mesh
        ax = self.axis
        N = self.n_chips
        K = self.batch_size
        A = model.max_actions
        L = model.lanes
        S = 1 << self.table_log2
        C = self.dest_capacity
        tiered = self._stores is not None
        if tiered:
            from ..store.summary import maybe_contains, summary_words

            slog2 = self._stores[0].config.summary_log2
            khash = self._stores[0].config.summary_hashes
            W = summary_words(slog2)
            TRIGGER = jnp.int32(self._spill_trigger)
            s_cfg = (slog2, khash)
        else:
            W = 1
            s_cfg = None
        # THE dispatch table (tensor/inserts.py): seed inserts stay plain
        # (fresh shard, empty summary); the in-loop insert carries the
        # fused Bloom probe when the variant supports it (pallas).
        _insert = resolve_insert(self.insert_variant)
        _insert_step = resolve_insert(self.insert_variant, summary_cfg=s_cfg)
        _fused = getattr(_insert_step, "fused_summary", False)
        # The DUS append writes tiles of T = K rows (a chip's batch) out of
        # the N*C received lanes, so it can write NC_T = N*C rounded up to
        # whole tiles past the tail.
        _append = (
            append_new if self.append == "scatter"
            else partial(append_new_dus, tile=K)
        )
        NC_T = append_rows(N * C, K)
        SQ = self._SQ
        TMR = self._TMR
        # NC_T rows of slack beyond the per-shard table size: the append
        # writes up to NC_T rows, and the DUS variant's contract requires
        # the start never to clamp (append_new_dus docstring) — without the
        # slack a near-full queue would silently overwrite live rows.
        # Tiered runs add SQ more rows of slack for the host's
        # suspect-injection block (the live frontier stays bounded by S:
        # the tail is host-compacted at every service exit).
        Q = S + NC_T + (SQ if tiered else 0)
        self._Q = Q
        props = self.props
        P_ = len(props)
        always_i = [i for i, p in enumerate(props) if p.expectation == Expectation.ALWAYS]
        sometimes_i = [i for i, p in enumerate(props) if p.expectation == Expectation.SOMETIMES]
        eventually_i = [i for i, p in enumerate(props) if p.expectation == Expectation.EVENTUALLY]
        ebits0 = np.uint32(sum(1 << i for i in eventually_i))
        all_bits = jnp.uint32((1 << P_) - 1)

        def owner_of(lo, _hi):
            # lo selects the chip; hi selects the in-table bucket — keeping
            # the two independent avoids occupancy skew (module docstring).
            return (lo % jnp.uint32(N)).astype(jnp.int32)

        def continue_expr(
            g_pending, g_overflow, discovered, gen_lo, gen_hi, steps,
            required_mask, any_mask, target_lo, target_hi, max_steps,
        ):
            # The ONE definition of "keep searching" — used by the in-loop
            # body and by chunk-entry recomputation so fresh and resumed runs
            # can never drift on termination semantics.
            all_found = (P_ > 0) & (discovered == all_bits)
            policy = (
                (required_mask != 0)
                & ((discovered & required_mask) == required_mask)
            ) | ((discovered & any_mask) != 0)
            have_target = (target_lo | target_hi) != 0
            count_hit = have_target & count_ge(
                gen_lo, gen_hi, target_lo, target_hi
            )
            return (
                (g_pending > 0)
                & ~all_found
                & ~policy
                & ~count_hit
                & ~g_overflow
                & (steps < max_steps)
            )

        _record = _record_impl

        def seed_carry(
            init_states,  # uint32[K, L] replicated
            init_lo,  # uint32[K] replicated
            init_hi,  # uint32[K] replicated
            init_active,  # bool[K] replicated
            target_lo,  # uint32 replicated (pair; 0,0 = none)
            target_hi,
            seed_lo,  # uint32 replicated: pre-dedup init count pair
            seed_hi,
            max_steps,  # int32 replicated
        ) -> _Carry:
            me = jax.lax.axis_index(ax)

            # -- seed: each chip keeps only the init states it owns ------------
            mine = init_active & (owner_of(init_lo, init_hi) == me)
            t_lo = jnp.zeros(S, dtype=jnp.uint32)
            t_hi = jnp.zeros(S, dtype=jnp.uint32)
            p_lo = jnp.zeros(S, dtype=jnp.uint32)
            p_hi = jnp.zeros(S, dtype=jnp.uint32)
            zero_k = jnp.zeros(K, dtype=jnp.uint32)
            t_lo, t_hi, p_lo, p_hi, is_new0, ovf0 = _insert(
                t_lo, t_hi, p_lo, p_hi, init_lo, init_hi, zero_k, zero_k, mine
            )
            n0 = mine.sum().astype(jnp.int32)
            pos_all = jnp.cumsum(mine.astype(jnp.int32)) - 1
            qpos = jnp.where(mine, pos_all, Q)
            q_states = (
                jnp.zeros((Q, L), dtype=jnp.uint32)
                .at[qpos].set(init_states, mode="drop")
            )
            q_lo = jnp.zeros(Q, dtype=jnp.uint32).at[qpos].set(init_lo, mode="drop")
            q_hi = jnp.zeros(Q, dtype=jnp.uint32).at[qpos].set(init_hi, mode="drop")
            q_ebits = (
                jnp.zeros(Q, dtype=jnp.uint32)
                .at[qpos].set(jnp.uint32(ebits0), mode="drop")
            )
            q_depth = (
                jnp.zeros(Q, dtype=jnp.uint32)
                .at[qpos].set(jnp.uint32(1), mode="drop")
            )

            # The seed counter pair is global (identical on every chip).
            # Stop conditions that can already hold at seed time (empty init
            # set, target <= seed count, max_steps == 0, seed overflow) must
            # prevent the first expansion step, matching the resident
            # engine's check-cond-before-first-body semantics.
            have_target0 = (target_lo | target_hi) != 0
            cont0 = (
                (jax.lax.psum(n0, ax) > 0)
                & ~(have_target0 & count_ge(seed_lo, seed_hi, target_lo, target_hi))
                & ~(jax.lax.psum(ovf0.astype(jnp.int32), ax) > 0)
                & (max_steps > 0)
            )
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
                tail=n0,
                gen_lo=seed_lo,
                gen_hi=seed_hi,
                unique_count=is_new0.sum().astype(jnp.int32),
                max_depth=jnp.uint32(0),
                discovered=jnp.uint32(0),
                disc_lo=jnp.zeros(max(P_, 1), dtype=jnp.uint32),
                disc_hi=jnp.zeros(max(P_, 1), dtype=jnp.uint32),
                cont=cont0,
                overflow=ovf0.astype(jnp.uint32) * jnp.uint32(ABORT_TABLE),
                steps=jnp.int32(0),
                hot_claims=is_new0.sum().astype(jnp.int32),
                s_states=jnp.zeros((SQ, L), dtype=jnp.uint32),
                s_lo=jnp.zeros(SQ, dtype=jnp.uint32),
                s_hi=jnp.zeros(SQ, dtype=jnp.uint32),
                s_ebits=jnp.zeros(SQ, dtype=jnp.uint32),
                s_depth=jnp.zeros(SQ, dtype=jnp.uint32),
                s_tail=jnp.int32(0),
                summary=jnp.zeros(W, dtype=jnp.uint32),
                tm_rows=jnp.zeros((TMR, N_COLS), dtype=jnp.uint32),
            )

        def make_body(
            required_mask, any_mask, target_lo, target_hi, max_steps,
            target_max_depth,
        ):
            def body(c: _Carry) -> _Carry:
                # -- pop a local batch (contiguous; queue never wraps) ---------
                states, lo, hi, ebits, depth, active, head = pop_batch(
                    c.q_states, c.q_lo, c.q_hi, c.q_ebits, c.q_depth,
                    c.head, c.tail, K,
                )
                max_depth = jnp.maximum(
                    c.max_depth, jnp.max(jnp.where(active, depth, 0))
                )
                # target_max_depth: states at the cutoff are neither evaluated
                # nor expanded (ref: bfs.rs:219-224); 0 = no limit.
                active = active & (
                    (target_max_depth == 0) | (depth < target_max_depth)
                )

                # -- property masks on popped states (bfs.rs:230-280) ----------
                # Named scopes attribute the step's device ops to its phases
                # in a profiler trace, with the resident body's names plus
                # `exchange` and `sync` (HLO metadata only: the program is
                # unchanged).
                discovered = c.discovered
                disc_lo, disc_hi = c.disc_lo, c.disc_hi
                if P_:
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

                # -- expand locally --------------------------------------------
                with jax.named_scope("expand"):
                    succs, valid = model.expand(states)
                    valid = valid & active[:, None]
                    flat = succs.reshape(K * A, L)
                    validf = valid.reshape(-1) & model.within_boundary(flat)
                    gen = validf.sum().astype(jnp.int32)
                    has_succ = validf.reshape(K, A).any(axis=1)

                # -- eventually counterexamples at terminal states --------------
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

                with jax.named_scope("fingerprint"):
                    slo, shi = state_fingerprint(model, flat)

                # -- route successors to owner chips (cumsum per destination) --
                with jax.named_scope("exchange"):
                    owner = jnp.where(validf, owner_of(slo, shi), N)
                    idx_in_seg = jnp.zeros(K * A, dtype=jnp.int32)
                    for d in range(N):  # static unroll
                        sel = owner == d
                        idx_in_seg = jnp.where(
                            sel, jnp.cumsum(sel.astype(jnp.int32)) - 1,
                            idx_in_seg,
                        )
                    live = owner < N
                    route_ovf = jnp.any(live & (idx_in_seg >= C))
                    dest = jnp.where(
                        live & (idx_in_seg < C), owner * C + idx_in_seg, N * C
                    )
                    parent_lo = jnp.repeat(lo, A)
                    parent_hi = jnp.repeat(hi, A)
                    ebits_rep = jnp.repeat(ebits, A)
                    depth_rep = jnp.repeat(depth + 1, A)

                    # ONE packed send buffer [N*C, L+7]: state lanes then
                    # (lo, hi, parent_lo, parent_hi, ebits, depth,
                    # valid-as-u32) — one zero-fill, one row scatter, ONE
                    # all_to_all instead of eight of each (per-collective
                    # launch overhead was a visible slice of the
                    # virtual-mesh step after the dest_capacity cut; on
                    # ICI, one large message also beats eight small ones).
                    packed = jnp.concatenate(
                        [
                            flat,
                            jnp.stack(
                                [
                                    slo, shi, parent_lo, parent_hi,
                                    ebits_rep, depth_rep,
                                    live.astype(jnp.uint32),
                                ],
                                axis=1,
                            ),
                        ],
                        axis=1,
                    )
                    s_packed = (
                        jnp.zeros((N * C, L + 7), dtype=jnp.uint32)
                        .at[dest].set(packed, mode="drop")
                    )
                    r_packed = jax.lax.all_to_all(
                        s_packed.reshape(N, C, L + 7), ax, 0, 0
                    ).reshape(N * C, L + 7)
                    r_states = r_packed[:, :L]
                    r_lo = r_packed[:, L]
                    r_hi = r_packed[:, L + 1]
                    r_plo = r_packed[:, L + 2]
                    r_phi = r_packed[:, L + 3]
                    r_ebits = r_packed[:, L + 4]
                    r_depth = r_packed[:, L + 5]
                    r_valid = r_packed[:, L + 6].astype(bool)

                # -- insert into the local shard (handles duplicates) ----------
                # Tiered: a Bloom-positive fresh claim is buffered for exact
                # host resolution against this shard's rank-local spill
                # store; a miss proves novelty on-device. The suspect probe
                # fuses into the Pallas kernel's partition pass when that
                # variant is selected (same protocol as the other engines).
                with jax.named_scope("insert"):
                    if tiered and _fused:
                        (
                            t_lo2, t_hi2, p_lo2, p_hi2, is_new, suspect,
                            ins_ovf,
                        ) = _insert_step(
                            c.t_lo, c.t_hi, c.p_lo, c.p_hi,
                            r_lo, r_hi, r_plo, r_phi, r_valid,
                            c.summary,
                        )
                    else:
                        (
                            t_lo2, t_hi2, p_lo2, p_hi2, is_new, ins_ovf,
                        ) = _insert_step(
                            c.t_lo, c.t_hi, c.p_lo, c.p_hi,
                            r_lo, r_hi, r_plo, r_phi, r_valid,
                        )
                        suspect = (
                            is_new
                            & maybe_contains(
                                c.summary, r_lo, r_hi, slog2, khash
                            )
                            if tiered
                            else None
                        )
                enq = is_new & ~suspect if tiered else is_new
                # -- append fresh states to the local queue (cumsum) -----------
                with jax.named_scope("append"):
                    q_states, q_lo, q_hi, q_ebits, q_depth, tail = _append(
                        c.q_states, c.q_lo, c.q_hi, c.q_ebits, c.q_depth,
                        c.tail,
                        r_states, r_lo, r_hi, r_ebits, r_depth, enq,
                    )
                new_count = tail - c.tail
                hot_claims = c.hot_claims + is_new.sum().astype(jnp.int32)
                if tiered:
                    with jax.named_scope("append"):
                        (
                            s_states, s_lo, s_hi, s_ebits, s_depth, s_tail,
                        ) = _append(
                            c.s_states, c.s_lo, c.s_hi, c.s_ebits, c.s_depth,
                            c.s_tail,
                            r_states, r_lo, r_hi, r_ebits, r_depth, suspect,
                        )
                    service = (
                        (hot_claims >= TRIGGER)
                        | (s_tail > SQ - NC_T)
                        | (tail > S)
                    )
                    q_fatal = jnp.bool_(False)  # host decides after compaction
                else:
                    s_states, s_lo, s_hi = c.s_states, c.s_lo, c.s_hi
                    s_ebits, s_depth, s_tail = c.s_ebits, c.s_depth, c.s_tail
                    service = jnp.bool_(False)
                    # Queue-full guard: the NC_T append slack keeps both
                    # append variants in bounds, and pop_batch's K-row
                    # dynamic_slice must never clamp either (dest_capacity
                    # may be set below K), so the bound is the stricter of
                    # the two.
                    q_fatal = tail > Q - max(NC_T, K)

                unique_count = c.unique_count + new_count
                overflow = (
                    c.overflow
                    | (route_ovf.astype(jnp.uint32) * jnp.uint32(ABORT_ROUTE))
                    | (ins_ovf.astype(jnp.uint32) * jnp.uint32(ABORT_TABLE))
                    | (q_fatal.astype(jnp.uint32) * jnp.uint32(ABORT_QUEUE))
                    | (service.astype(jnp.uint32) * jnp.uint32(EXIT_SERVICE))
                )

                # -- global sync: discovery OR, counters, termination ----------
                with jax.named_scope("sync"):
                    gathered = jax.lax.all_gather(discovered, ax)
                    discovered = gathered[0]
                    for i in range(1, N):  # static unroll: global OR
                        discovered = discovered | gathered[i]
                    g_gen_step = jax.lax.psum(gen, ax)  # < 2^31 per step
                    gen_lo, gen_hi = count_add(
                        c.gen_lo, c.gen_hi, g_gen_step.astype(jnp.uint32)
                    )
                    g_pending = jax.lax.psum(tail - head, ax)
                    g_overflow = (
                        jax.lax.psum(overflow.astype(jnp.int32), ax) > 0
                    )
                    steps = c.steps + 1
                    cont = continue_expr(
                        g_pending, g_overflow, discovered, gen_lo, gen_hi,
                        steps, required_mask, any_mask, target_lo, target_hi,
                        max_steps,
                    )

                # -- per-shard step telemetry row (obs/ring.py STEP_COLS) ------
                # Steps are globally synced, so row i holds shard-local
                # values for the SAME global step on every shard; the host
                # drain aligns and aggregates them.
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
                    t_lo=t_lo2,
                    t_hi=t_hi2,
                    p_lo=p_lo2,
                    p_hi=p_hi2,
                    q_states=q_states,
                    q_lo=q_lo,
                    q_hi=q_hi,
                    q_ebits=q_ebits,
                    q_depth=q_depth,
                    head=head,
                    tail=tail,
                    gen_lo=gen_lo,
                    gen_hi=gen_hi,
                    unique_count=unique_count,
                    max_depth=max_depth,
                    discovered=discovered,
                    disc_lo=disc_lo,
                    disc_hi=disc_hi,
                    cont=cont,
                    overflow=overflow,
                    steps=steps,
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

            return body

        def recompute_cont(c: _Carry, required_mask, any_mask, target_lo,
                           target_hi, max_steps):
            # Re-derive the global continue flag from the carry's state so a
            # resumed chunk honors the CURRENT run options (finish policy,
            # target, step cap) rather than whatever stopped the prior run.
            g_pending = jax.lax.psum(c.tail - c.head, ax)
            g_overflow = jax.lax.psum(c.overflow.astype(jnp.int32), ax) > 0
            return continue_expr(
                g_pending, g_overflow, c.discovered, c.gen_lo, c.gen_hi,
                c.steps, required_mask, any_mask, target_lo, target_hi,
                max_steps,
            )

        def shard(x):
            return x.reshape(1, *jnp.shape(x))

        def sharded_search(
            init_states, init_lo, init_hi, init_active,
            target_lo, target_hi, seed_lo, seed_hi,
            required_mask, any_mask, max_steps, target_max_depth,
        ):
            carry = seed_carry(
                init_states, init_lo, init_hi, init_active,
                target_lo, target_hi, seed_lo, seed_hi, max_steps,
            )
            body = make_body(
                required_mask, any_mask, target_lo, target_hi, max_steps,
                target_max_depth,
            )
            carry = jax.lax.while_loop(lambda c: c.cont, body, carry)

            return (
                shard(carry.t_lo),
                shard(carry.t_hi),
                shard(carry.p_lo),
                shard(carry.p_hi),
                shard(carry.gen_lo),
                shard(carry.gen_hi),
                shard(carry.unique_count),
                shard(carry.max_depth),
                shard(carry.discovered),
                shard(carry.disc_lo),
                shard(carry.disc_hi),
                shard(carry.head >= carry.tail),
                shard(carry.overflow),
                shard(carry.steps),
                shard(carry.tm_rows),
            )

        def sharded_seed(
            init_states, init_lo, init_hi, init_active,
            target_lo, target_hi, seed_lo, seed_hi, max_steps,
        ):
            carry = seed_carry(
                init_states, init_lo, init_hi, init_active,
                target_lo, target_hi, seed_lo, seed_hi, max_steps,
            )
            return jax.tree.map(lambda x: jnp.asarray(x)[None], carry)

        def sharded_chunk(
            carry: _Carry,  # per-chip view: leading dim 1 on every leaf
            required_mask, any_mask, target_lo, target_hi,
            target_max_depth, budget, max_steps,
        ):
            c = jax.tree.map(lambda x: x[0], carry)
            c = c._replace(
                cont=recompute_cont(
                    c, required_mask, any_mask, target_lo, target_hi,
                    max_steps,
                )
            )
            body = make_body(
                required_mask, any_mask, target_lo, target_hi, max_steps,
                target_max_depth,
            )
            start = c.steps
            c = jax.lax.while_loop(
                lambda c: c.cont & (c.steps < start + budget), body, c
            )
            summary = jnp.concatenate(
                [
                    jnp.stack(
                        [
                            c.gen_lo,
                            c.gen_hi,
                            c.unique_count.astype(jnp.uint32),
                            c.max_depth,
                            c.discovered,
                            c.head.astype(jnp.uint32),
                            c.tail.astype(jnp.uint32),
                            c.overflow.astype(jnp.uint32),
                            c.steps.astype(jnp.uint32),
                            (~c.cont).astype(jnp.uint32),
                            c.hot_claims.astype(jnp.uint32),
                            c.s_tail.astype(jnp.uint32),
                        ]
                    ),
                    c.disc_lo,
                    c.disc_hi,
                ]
            )
            out = jax.tree.map(lambda x: jnp.asarray(x)[None], c)
            return out, shard(summary)

        sharded = jax.shard_map(
            sharded_search,
            mesh=mesh,
            in_specs=(P(),) * 12,
            out_specs=P(ax),
            check_vma=False,
        )
        seed_sm = jax.shard_map(
            sharded_seed,
            mesh=mesh,
            in_specs=(P(),) * 9,
            out_specs=P(ax),
            check_vma=False,
        )
        # NOTE: NOT donated by default — the host keeps the pre-chunk carry
        # alive so an overflow reverts to the last sound chunk boundary
        # (checkpoint-then-raise instead of discarding the run).
        # `donate_chunks=True` flips the trade (see __init__).
        chunk_sm = jax.shard_map(
            sharded_chunk,
            mesh=mesh,
            in_specs=(P(ax),) + (P(),) * 7,
            out_specs=(P(ax), P(ax)),
            check_vma=False,
        )
        chunk_jit = (
            jax.jit(chunk_sm, donate_argnums=(0,))
            if self.donate_chunks
            else jax.jit(chunk_sm)
        )
        return jax.jit(sharded), jax.jit(seed_sm), chunk_jit

    # -- static analysis -------------------------------------------------------

    def audit_step(self):
        """(chunk_fn, abstract_operands, host_slots) for the jaxpr auditor
        (analysis/auditor.py). Carry shapes via eval_shape over the
        engine's own shard_map'd seed kernel — abstract only; the mesh
        must exist (conftest forces 8 host devices on CPU) but no device
        executes anything."""
        K, L = self.batch_size, self.model.lanes
        sds = jax.ShapeDtypeStruct
        u32 = lambda *s: sds(s, jnp.uint32)  # noqa: E731
        carry = jax.eval_shape(
            self._seed_k,
            u32(K, L), u32(K), u32(K), sds((K,), jnp.bool_),
            u32(), u32(), u32(), u32(), sds((), jnp.int32),
        )
        args = (
            carry, u32(), u32(), u32(), u32(), u32(),
            sds((), jnp.int32), sds((), jnp.int32),
        )
        return self._chunk_k, args, ()

    # -- host entry ------------------------------------------------------------

    def warm_start(self, entry, kind: Optional[str] = None) -> int:
        """Seed this search from a published `CorpusEntry` (store/warm.py).

        The entry's visited set is split by the fingerprint→owner map
        (`lo % n_chips` — the same routing the all-to-all uses) and each
        shard's slice preloads that shard's rank-local spill tier; the
        entry's serialized Bloom summary OR-s into every shard (a sound
        superset — shards only probe states they own).

        Complete entries replay: the run drains its re-expanded seed
        against the preloaded set and the published result is restored
        verbatim (caller gates on `warm.can_replay`). Partial entries
        continue: the frontier snapshot is routed to its owner shards as
        each shard's live queue and the run picks up mid-search (caller
        gates on `warm.can_continue`). The Spec-CI rung rides the same
        two paths: gate through `warm.salvage_delta` and pass its
        salvaged entry here with kind="delta". Returns states
        preloaded."""
        if self._stores is None:
            raise ValueError(
                "warm_start requires store='tiered' (the preloaded set "
                "lives in the per-shard spill tiers)"
            )
        if self._carry is not None:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError(
                "cannot warm-start a suspended search; reset() first"
            )
        lo, _hi = warm_seam.split_fps(entry.fps)
        owners = lo % np.uint32(self.n_chips)
        n = 0
        for i, s in enumerate(self._stores):
            n += warm_seam.preload_store(s, entry, mask=(owners == i))
        self._warm_states = n
        if getattr(entry, "complete", True):
            self._warm = dict(entry.meta)
            self._warm_kind = kind or "exact"
            self._warm_summary_pending = True
            return n
        if getattr(entry, "frontier", None) is None:
            raise ValueError(
                "partial corpus entry has no frontier snapshot (coverage-"
                "only entries cannot seed a continuation)"
            )
        self._warm_kind = kind if kind == "delta" else "partial"
        self._seed_partial_carry(entry)
        return n

    def _seed_partial_carry(self, entry) -> None:
        """Host-build the suspended per-shard carry for a partial-entry
        continuation (the `load_checkpoint` recipe, sourced from a corpus
        frontier snapshot instead of a checkpoint archive)."""
        N_ = self.n_chips
        Q = self._Q
        S = 1 << self.table_log2
        L = self.model.lanes
        P_ = max(len(self.props), 1)
        f = entry.frontier
        st = np.asarray(f["states"], dtype=np.uint32)
        f_lo = np.asarray(f["lo"], dtype=np.uint32)
        f_hi = np.asarray(f["hi"], dtype=np.uint32)
        eb = warm_seam.pack_ebits(np.asarray(f["ebits"], dtype=bool))
        dp = np.asarray(f["depths"], dtype=np.uint32)
        owners = (f_lo % np.uint32(N_)).astype(np.int64)
        meta = entry.meta
        q_states = np.zeros((N_, Q, L), dtype=np.uint32)
        q_lo = np.zeros((N_, Q), dtype=np.uint32)
        q_hi = np.zeros((N_, Q), dtype=np.uint32)
        q_ebits = np.zeros((N_, Q), dtype=np.uint32)
        q_depth = np.zeros((N_, Q), dtype=np.uint32)
        tail = np.zeros(N_, dtype=np.int32)
        for i in range(N_):
            rows = np.flatnonzero(owners == i)  # FIFO order preserved
            m = rows.size
            if m > Q - self.batch_size:
                raise ValueError(
                    "frontier snapshot too large for a shard's queue "
                    f"(shard {i}: {m} rows, capacity {Q}); raise "
                    "table_log2"
                )
            q_states[i, :m] = st[rows]
            q_lo[i, :m] = f_lo[rows]
            q_hi[i, :m] = f_hi[rows]
            q_ebits[i, :m] = eb[rows]
            q_depth[i, :m] = dp[rows]
            tail[i] = m
        sc = int(meta.get("state_count", 0))
        disc_mask = 0
        disc_lo = np.zeros((N_, P_), dtype=np.uint32)
        disc_hi = np.zeros((N_, P_), dtype=np.uint32)
        names = [p.name for p in self.props]
        for name, fp in dict(meta.get("discoveries", {})).items():
            if name in names:
                j = names.index(name)
                disc_mask |= 1 << j
                w_lo = np.uint32(int(fp) & 0xFFFFFFFF)
                disc_lo[int(w_lo) % N_, j] = w_lo
                disc_hi[int(w_lo) % N_, j] = np.uint32(int(fp) >> 32)
        # unique/max_depth are per-shard locals the result sums/maxes; the
        # prefix totals ride on shard 0 so the reduction lands on the
        # published counts plus whatever the continuation adds.
        unique = np.zeros(N_, dtype=np.int32)
        unique[0] = int(meta.get("unique_count", 0))
        fields = {
            "t_lo": np.zeros((N_, S), np.uint32),
            "t_hi": np.zeros((N_, S), np.uint32),
            "p_lo": np.zeros((N_, S), np.uint32),
            "p_hi": np.zeros((N_, S), np.uint32),
            "q_states": q_states,
            "q_lo": q_lo,
            "q_hi": q_hi,
            "q_ebits": q_ebits,
            "q_depth": q_depth,
            "head": np.zeros(N_, np.int32),
            "tail": tail,
            "gen_lo": np.full(N_, sc & 0xFFFFFFFF, np.uint32),
            "gen_hi": np.full(N_, sc >> 32, np.uint32),
            "unique_count": unique,
            "max_depth": np.full(
                N_, int(meta.get("max_depth", 0)), np.uint32
            ),
            "discovered": np.full(N_, disc_mask, np.uint32),
            "disc_lo": disc_lo,
            "disc_hi": disc_hi,
            "cont": np.full(N_, bool(tail.sum() > 0)),
            "overflow": np.zeros(N_, np.uint32),
            "steps": np.zeros(N_, np.int32),
            "hot_claims": np.zeros(N_, np.int32),
            "s_states": np.zeros((N_, self._SQ, L), np.uint32),
            "s_lo": np.zeros((N_, self._SQ), np.uint32),
            "s_hi": np.zeros((N_, self._SQ), np.uint32),
            "s_ebits": np.zeros((N_, self._SQ), np.uint32),
            "s_depth": np.zeros((N_, self._SQ), np.uint32),
            "s_tail": np.zeros(N_, np.int32),
            "summary": np.stack([s.summary_np for s in self._stores]),
            "tm_rows": np.zeros((N_, self._TMR, N_COLS), np.uint32),
        }
        self._set_carry(fields)
        self._exchange_mark = (0, sc)
        # Queue rows no longer cover every unique state (the prefix lives
        # in the spill tiers) — dump_states must decline.
        self._q_compacted = True

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
        """Run (or resume) the multi-chip search. Without `budget` the whole
        search is ONE shard_map dispatch. With `budget`, it runs in chunks of
        at most `budget` globally-synced loop steps per dispatch — enabling
        `progress`, `timeout` (polled between chunks), `checkpoint()`/resume,
        and recoverable overflow (the carry reverts to the last chunk
        boundary; see `load_checkpoint(table_log2=...)`)."""
        # Tiered runs are always chunked: the host must regain control for
        # spill eviction and suspect resolution.
        if self._stores is not None and budget is None and timeout is None:
            budget = 1 << 20
        chunked, budget = _resolve_chunking(
            budget, timeout, progress, self._carry
        )
        start = time.monotonic()
        self._begin_run()
        (st, lo, hi, active), n0, n_raw = self._seed_rows()
        vacuous = self._vacuous_result(finish_when, n0, n_raw, start)
        if vacuous is not None:
            return vacuous

        required_mask, any_mask = _finish_masks(finish_when, self.props)
        target = int(target_state_count or 0)
        t32 = (jnp.uint32(target & 0xFFFFFFFF), jnp.uint32(target >> 32))
        seed32 = (
            jnp.uint32(n_raw & 0xFFFFFFFF),
            jnp.uint32(n_raw >> 32),
        )

        if not chunked:
            # Chaos-plane boundary (faults/plan.py): faults land before the
            # dispatch, never mid-update.
            maybe_fault("engine.step", engine="sharded")
            # Every operand is traced (the depth bound and the finish masks
            # too), so one executable serves every run of this engine.
            args = (
                jnp.asarray(st),
                jnp.asarray(lo),
                jnp.asarray(hi),
                jnp.asarray(active),
                *t32,
                *seed32,
                jnp.uint32(required_mask),
                jnp.uint32(any_mask),
                jnp.int32(max_steps),
                jnp.uint32(target_max_depth or 0),
            )
            with self._tracer.span("sharded.search", cat="engine") as span:
                exe, cached = self._stages.executable(
                    self._kernel, args,
                    tuple((a.shape, a.dtype) for a in args),
                    self._definition,
                )
                span.set(cached=int(cached))
                with self._stages.stage("execute"):
                    t_lo, t_hi, p_lo, p_hi, *outs = exe(*args)
                    # ONE gather for the small outputs (one DCN round-trip
                    # on multi-host meshes instead of one per array); the
                    # tables stay on the chips until a path needs them.
                    (
                        gen_lo, gen_hi, unique_counts, max_depths,
                        discovered, disc_lo, disc_hi, drained, overflow,
                        steps, tm_rows,
                    ) = to_host(tuple(outs))
            # Whole-search dispatch: one bulk drain of every shard's ring.
            # The window is the execute stage alone: no tracing, lowering or
            # executable load.
            self._drain_ring(tm_rows, int(steps.max()),
                             self._stages.phases["execute_s"] * 1e6)
            if bool(overflow.any()):
                # A previous run's snapshot must not silently serve paths
                # for states this failed run discovered.
                self._last_tables = None
                raise RuntimeError(
                    "sharded search overflow: raise table_log2 or "
                    "dest_capacity (or run with budget=... for a recoverable "
                    "checkpoint-then-raise)"
                )
            self._last_tables = (t_lo, t_hi, p_lo, p_hi)
            counts = dict(
                gen=int(gen_lo[0]) | (int(gen_hi[0]) << 32),
                unique=unique_counts,
                max_depth=max_depths,
                steps=steps,
                discovered=int(discovered[0]),
                disc_lo=disc_lo,  # [N, P]
                disc_hi=disc_hi,
                complete=bool(drained.all()),
            )
            self._exchange_mark = (0, n_raw)
            self._count_exchange(int(steps.max()), counts["gen"])
        else:
            if self._carry is None:
                self._carry = self._seed_k(
                    jnp.asarray(st),
                    jnp.asarray(lo),
                    jnp.asarray(hi),
                    jnp.asarray(active),
                    *t32,
                    *seed32,
                    jnp.int32(max_steps),
                )
                self._exchange_mark = (0, n_raw)
                if self._warm_summary_pending:
                    # Complete-entry replay: seed_carry built empty Bloom
                    # words; swap in each shard's preloaded summary so the
                    # re-expanded seed dedups against the published set.
                    from jax.sharding import NamedSharding

                    self._carry = self._carry._replace(
                        summary=jax.device_put(
                            jnp.asarray(
                                np.stack(
                                    [s.summary_np for s in self._stores]
                                )
                            ),
                            NamedSharding(self.mesh, P(self.axis)),
                        )
                    )
                    self._warm_summary_pending = False
            req = jnp.uint32(required_mask)
            anym = jnp.uint32(any_mask)
            tmd = jnp.uint32(target_max_depth or 0)
            timed_out = False
            while True:
                # Chaos-plane boundary: pre-dispatch, so a faulted chunk
                # never half-updates the retained carry.
                maybe_fault("engine.step", engine="sharded")
                t_chunk0 = time.monotonic()
                with self._tracer.span("sharded.chunk", cat="engine"):
                    carry, summary = self._chunk_k(
                        self._carry, req, anym, *t32, tmd,
                        jnp.int32(budget), jnp.int32(max_steps),
                    )
                    s = to_host(summary)  # [N, 12 + 2*max(P,1)] — one transfer
                self._drain_ring(carry.tm_rows, int(s[:, 8].max()),
                                 (time.monotonic() - t_chunk0) * 1e6)
                codes = s[:, 7].astype(np.uint32)
                fatal = (codes & (ABORT_TABLE | ABORT_QUEUE | ABORT_ROUTE)).any()
                if not fatal:  # the chunk's carry is kept: count its steps
                    self._count_exchange(
                        int(s[:, 8].max()), int(s[0, 0]) | (int(s[0, 1]) << 32)
                    )
                if (codes & EXIT_SERVICE).any() and not fatal:
                    # Non-fatal tiered-store service: every shard drains its
                    # suspect buffer / evicts / compacts, then the loop
                    # resumes the same carry.
                    self._carry = carry
                    self._service()
                    continue
                if codes.any():  # fatal overflow on any chip
                    if self.donate_chunks:
                        self._carry = None  # donated into the dispatch
                        self._last_tables = None  # a prior run's snapshot
                        # must not serve paths for states found in this one
                        raise RuntimeError(
                            "sharded search overflow; donate_chunks=True "
                            "sacrificed the recovery carry — rerun with a "
                            "larger table_log2 or dest_capacity (or "
                            "donate_chunks=False for checkpoint-then-regrow "
                            "recovery)"
                        )
                    # Non-donated: the carry was kept at the last sound
                    # chunk boundary for checkpoint+regrow. Refresh the
                    # table snapshot to that boundary so reconstruct_path
                    # serves THIS run's states (not a stale prior run's).
                    self._last_tables = to_host(self._carry_tables())
                    self._parent_map = None
                    raise RuntimeError(
                        "sharded search overflow; the carry was kept at the "
                        "last chunk boundary — checkpoint(path) then "
                        "ShardedSearch.load_checkpoint(model, path, "
                        "table_log2=<bigger>) to continue without losing "
                        "the run (a routing overflow instead wants a fresh "
                        "run with a larger dest_capacity)"
                    )
                self._carry = carry
                # Chaos-plane boundary: simulated preemption at a chunk
                # boundary (the carry is sound here).
                maybe_fault("engine.chunk", engine="sharded")
                if progress is not None:
                    progress(
                        int(s[0, 0]) | (int(s[0, 1]) << 32),
                        int(s[:, 2].sum()),
                        int(s[:, 3].max()),
                    )
                if s[0, 9]:  # stop flag (globally synced)
                    if self._stores is not None and s[:, 11].any():
                        # Queues drained with suspects still buffered on
                        # some shard: resolve them — confirmed-new rows
                        # reopen the frontier; the next chunk re-evaluates
                        # the stop with empty buffers (cannot loop).
                        self._service()
                        continue
                    break
                if timeout is not None:
                    # Multi-process: every rank must take the SAME branch or
                    # the next collective deadlocks (ranks' host clocks and
                    # startup delays differ). Rank 0's verdict is broadcast;
                    # single-process keeps the plain clock check.
                    timed = time.monotonic() - start > timeout
                    if jax.process_count() > 1:
                        from jax.experimental import multihost_utils

                        timed = bool(
                            multihost_utils.broadcast_one_to_all(
                                np.asarray(timed)
                            )
                        )
                    if timed:
                        timed_out = True
                        break
            self._last_tables = to_host(self._carry_tables())
            counts = summary_fields(s, len(self.props), timed_out)
        return self._result(
            start, chunked,
            # fp-sharding balance evidence (task: per-chip spread).
            extra={"per_chip_unique": [int(x) for x in counts["unique"]]},
            **counts,
        )

    def metrics(self) -> dict:
        return {"n_chips": self.n_chips, **super().metrics()}

    def _count_exchange(self, steps: int, generated: int) -> None:
        """Add the exchange since `_exchange_mark` to the counters: every
        generated successor is one routed row (`exchange_rows_total`), and
        every step each of the N chips sends N x dest_capacity slots
        (`exchange_slots_total`); their ratio is the send buffers' fill."""
        if self._exchange_mark is not None:
            s0, g0 = self._exchange_mark
            self._counters["exchange_rows_total"] += generated - g0
            self._counters["exchange_slots_total"] += (
                (steps - s0) * self.n_chips ** 2 * self.dest_capacity
            )
        self._exchange_mark = (steps, generated)

    def _service(self) -> None:
        """Host half of the tiered store for the sharded engine, with
        WINDOWED per-shard transfers (like the single-device path) instead
        of the full-carry gather it used to pay per event:

        - queue compaction runs ON DEVICE (the single-device compaction
          kernel vmapped over the shard axis) — the [N, Q, L] queues never
          cross to host;
        - only each shard's LIVE suspect rows ([s_tail] slices) transfer
          for exact resolution, and confirmed-new rows are injected back
          with the vmapped device-side injection kernel;
        - eviction uses `TieredStore.evict` on per-shard table slices —
          per-bucket counts + evictable-bucket gathers (the device-side
          pre-filter), not whole tables.

        Single-process meshes only (enforced in __init__)."""
        c = self._carry
        N = self.n_chips
        S = 1 << self.table_log2
        SQ = self._SQ
        L = self.model.lanes
        compact_v, inject_v = _service_kernels()
        # Tiny per-shard scalar vectors — the only unconditional transfers.
        head = np.asarray(c.head).astype(np.int32)
        tail = np.asarray(c.tail).astype(np.int32).copy()
        s_tail = np.asarray(c.s_tail)
        hot = np.asarray(c.hot_claims).astype(np.int32).copy()
        unique = np.asarray(c.unique_count).astype(np.int32).copy()

        q = (c.q_states, c.q_lo, c.q_hi, c.q_ebits, c.q_depth)
        if (head > 0).any():
            q = compact_v(*q, jnp.asarray(head))
            tail = tail - head
            head = np.zeros_like(head)
            self._q_compacted = True
        if (tail > S).any():
            i = int(np.argmax(tail > S))
            self._carry = self._replace_carry(
                c, q, head, tail, s_tail, hot, unique, None, None
            )
            raise RuntimeError(
                f"sharded tiered store: shard {i}'s live frontier "
                f"({int(tail[i])} rows) exceeds the compacted queue — raise "
                "table_log2 (the per-shard queue is table-sized)"
            )

        # Suspect resolution: transfer only the live rows of shards that
        # actually buffered suspects.
        n_confs = np.zeros(N, dtype=np.int32)
        if s_tail.any():
            self._tracer.instant(
                "tiered.suspect_resolve", cat="store",
                suspects=int(s_tail.sum()),
            )
            blk_states = np.zeros((N, SQ, L), dtype=np.uint32)
            blk = {
                k: np.zeros((N, SQ), dtype=np.uint32)
                for k in ("lo", "hi", "eb", "dp")
            }
            for i in range(N):
                st_i = int(s_tail[i])
                if st_i == 0:
                    continue
                # Chaos-plane boundary: one shard's transfer failing must
                # not corrupt the others (the supervisor restores the whole
                # carry from the last checkpoint on fault).
                maybe_fault("shard.transfer", shard=i, phase="resolve")
                sus_lo = np.asarray(c.s_lo[i, :st_i])
                sus_hi = np.asarray(c.s_hi[i, :st_i])
                dup = self._stores[i].resolve_suspects(sus_lo, sus_hi)
                keep = ~dup
                n_conf = int(keep.sum())
                if n_conf:
                    blk_states[i, :n_conf] = np.asarray(
                        c.s_states[i, :st_i]
                    )[keep]
                    blk["lo"][i, :n_conf] = sus_lo[keep]
                    blk["hi"][i, :n_conf] = sus_hi[keep]
                    blk["eb"][i, :n_conf] = np.asarray(
                        c.s_ebits[i, :st_i]
                    )[keep]
                    blk["dp"][i, :n_conf] = np.asarray(
                        c.s_depth[i, :st_i]
                    )[keep]
                    n_confs[i] = n_conf
            if n_confs.any():
                q = inject_v(
                    *q, jnp.asarray(tail),
                    jnp.asarray(blk_states), jnp.asarray(blk["lo"]),
                    jnp.asarray(blk["hi"]), jnp.asarray(blk["eb"]),
                    jnp.asarray(blk["dp"]),
                )
                tail = tail + n_confs
                unique = unique + n_confs

        # Eviction: windowed device-slice transfers per over-water shard.
        tables = None
        if (hot >= self._spill_trigger).any():
            self._tracer.instant("tiered.evict", cat="store")
            parts = {k: [] for k in ("t_lo", "t_hi", "p_lo", "p_hi")}
            for i in range(N):
                tl, th = c.t_lo[i], c.t_hi[i]
                pl, ph = c.p_lo[i], c.p_hi[i]
                if hot[i] >= self._spill_trigger:
                    maybe_fault("shard.transfer", shard=i, phase="evict")
                    tl, th, pl, ph, n_ev = self._stores[i].evict(
                        tl, th, pl, ph, int(hot[i])
                    )
                    if n_ev == 0:
                        raise RuntimeError(
                            f"sharded tiered store: shard {i} could not "
                            "free any bucket (every bucket full and "
                            "pinned); raise table_log2 or lower high_water"
                        )
                    hot[i] -= n_ev
                parts["t_lo"].append(tl)
                parts["t_hi"].append(th)
                parts["p_lo"].append(pl)
                parts["p_hi"].append(ph)
            tables = {k: jnp.stack(v) for k, v in parts.items()}

        summary = np.stack([s.summary_np for s in self._stores])
        self._carry = self._replace_carry(
            c, q, head, tail, np.zeros(N, np.int32), hot, unique, tables,
            summary,
        )

    def _replace_carry(
        self, c, q, head, tail, s_tail, hot, unique, tables, summary
    ) -> "_Carry":
        """Push serviced fields back with shard placement; untouched leaves
        keep their existing buffers."""
        from jax.sharding import NamedSharding

        sh = NamedSharding(self.mesh, P(self.axis))
        put = lambda x: jax.device_put(jnp.asarray(x), sh)  # noqa: E731
        upd = dict(
            q_states=put(q[0]), q_lo=put(q[1]), q_hi=put(q[2]),
            q_ebits=put(q[3]), q_depth=put(q[4]),
            head=put(head.astype(np.int32)),
            tail=put(tail.astype(np.int32)),
            s_tail=put(s_tail.astype(np.int32)),
            hot_claims=put(hot.astype(np.int32)),
            unique_count=put(unique.astype(np.int32)),
            overflow=put(np.zeros(self.n_chips, np.uint32)),
        )
        if tables is not None:
            upd.update({k: put(v) for k, v in tables.items()})
        if summary is not None:
            upd["summary"] = put(summary)
        return c._replace(**upd)

    def reset(self) -> None:
        super().reset()
        self._exchange_mark = None
        self._warm = None
        self._warm_states = 0
        self._warm_kind = None
        self._warm_summary_pending = False

    # -- the engine's side of SearchHost ---------------------------------------
    # The restore mesh must have the checkpoint's chip count (the fp->owner
    # map depends on it).

    def _seed_operands(self, st, lo, hi, n0):
        # Host arrays, replicated to every chip at each dispatch.
        return st, lo, hi, np.arange(self.batch_size) < n0

    def _queue_rows(self, evaluated_only: bool, start: int) -> list:
        q, ends = to_host((
            self._carry.q_states,  # [N, Q, L]
            self._carry.head if evaluated_only else self._carry.tail,
        ))
        return [q[i, start : int(ends[i])] for i in range(self.n_chips)]

    def _checkpoint_arrays(self, path: str) -> Optional[dict]:
        # Multi-process runs: EVERY rank must call checkpoint() (the carry
        # gather is a collective), but only process 0 writes the file — N
        # ranks writing the same path on a shared filesystem would corrupt
        # the archive. For resume, `path` must be readable by every rank
        # (shared storage).
        arrays = to_host(self._carry._asdict())
        return arrays if jax.process_index() == 0 else None

    def _checkpoint_meta(self) -> dict:
        return {"n_chips": self.n_chips, "dest_capacity": self.dest_capacity}

    def _set_carry(self, fields: dict) -> None:
        from jax.sharding import NamedSharding

        sh = NamedSharding(self.mesh, P(self.axis))
        self._carry = _Carry(
            **{f: jax.device_put(jnp.asarray(v), sh) for f, v in fields.items()}
        )

    @classmethod
    def load_checkpoint(
        cls,
        model: TensorModel,
        path: str,
        mesh: Optional[Mesh] = None,
        batch_size: Optional[int] = None,
        table_log2: Optional[int] = None,
        donate_chunks: bool = False,
    ) -> "ShardedSearch":
        """Rebuild a suspended sharded search. A larger `table_log2` re-hashes
        every shard's visited set into a bigger per-chip table (the recovery
        path for an overflow abort). The next `run()` continues exactly."""
        data, meta = cls._read_checkpoint(model, path)
        ss = cls(
            model,
            mesh=mesh,
            dest_capacity=meta["dest_capacity"],
            donate_chunks=donate_chunks,
            **cls._resume_kwargs(meta, batch_size, table_log2),
        )
        if ss.n_chips != meta["n_chips"]:
            raise ValueError(
                f"checkpoint was taken on {meta['n_chips']} chips; restoring "
                f"on {ss.n_chips} is not supported (the fingerprint→owner "
                "map depends on the chip count)"
            )
        fields = ss._restore(data, meta)
        # The per-shard queue guard was enforced with the CHECKPOINT's
        # batch size; a larger K here could let pop_batch's dynamic_slice
        # clamp past a shard's restored tail.
        max_tail = int(np.max(fields["tail"]))
        if max_tail > ss._Q - ss.batch_size:
            raise ValueError(
                "batch_size too large for the restored queue occupancy "
                f"(max per-shard tail={max_tail}, capacity={ss._Q}); "
                "use a smaller batch_size or a larger table_log2"
            )
        ss._set_carry(fields)
        ss._exchange_mark = (
            int(np.asarray(fields["steps"]).max()),
            int(fields["gen_lo"][0]) | (int(fields["gen_hi"][0]) << 32),
        )
        return ss
