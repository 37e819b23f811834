"""Device-resident visited set: a bucketed open-addressing hash table over
HBM, keyed by (lo, hi) uint32 fingerprint pairs.

Replaces the reference's sharded concurrent `DashMap<Fingerprint,
Option<Fingerprint>>` (ref: src/checker/bfs.rs:29-30): key identity is the
full 64-bit fingerprint (as two u32 lanes — see tensor/fingerprint.py for why
pairs), values are parent fingerprints for path reconstruction.

TPU-shaped design: random HBM access is the enemy (a probe loop touching one
slot at a time serializes; it measured ~270 ms per 128k-insert batch on a
v5e). So slots are grouped into BUCKETS of 128 contiguous u32s — one row
gather fetches a whole 512-byte bucket — and a round inspects 128 slots at
once. The bucket width IS the TPU lane count on purpose: a (S/128, 128)
view of the flat table is layout-identical under T(8,128) tiling, so the
per-round reshape inside the probe loop is a free bitcast. (The previous
8-wide bucket view was tile-padded 16x and MATERIALIZED every probe round —
an 8 GB HLO temp at table 2^27 that OOMed 2pc-10 on a 16 GB v5e; and a
flat 2D-index gather of 8-slot rows measured 1.3-1.8x slower than the row
gather it replaced.) A 128-slot bucket also makes chain overflow to the
next bucket vanishingly rare at any sane load factor:

1. sort the batch by (bucket, key) — duplicates become adjacent (one REP
   lane per distinct key; the rest resolve immediately), same-bucket
   claimants become contiguous,
2. gather each lane's bucket row; a rep hits if any slot matches (lo, hi),
3. otherwise reps claim DISTINCT free slots — the rank-th same-bucket rep
   takes the rank-th free lane (ranks from prefix sums over the sorted
   order) — so every table write is a race-free unique_indices scatter;
   full buckets overflow to the next bucket, wrapping modulo the table.

Safety argument: claim targets are unique by construction (distinct
(bucket, rank) pairs), a committed slot always has lo != 0 and is never
emptied, so linear bucket probing and first-non-full-bucket membership stay
correct across rounds and calls. Batches may contain duplicate
fingerprints: rep selection attributes exactly one `is_new` per distinct
new key. See `_insert_impl` for why this sort-claim form replaced the
round-1..3 phased scatter-max claim (silicon profile: ~3.9 serialized
rounds per step and sort-based non-unique scatter lowering).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

BUCKET = 128
MAX_ROUNDS = 64

# Capped insert path (see make_capped_insert): claim tiles are at least
# this many lanes (power of two — keeps tile shapes static under
# jit/while_loop); CAP_MAX_TILES bounds the serialized tile count by
# growing the tile for very large batches.
CLAIM_TILE = 4096
CAP_MAX_TILES = 64


class InsertResult(NamedTuple):
    t_lo: jnp.ndarray  # uint32[S]
    t_hi: jnp.ndarray  # uint32[S]
    p_lo: jnp.ndarray  # uint32[S]
    p_hi: jnp.ndarray  # uint32[S]
    is_new: jnp.ndarray  # bool[B] — inserted by this call (one per distinct key)
    overflow: jnp.ndarray  # bool — some lane exhausted MAX_ROUNDS


class HashTable:
    """Host-side handle; the arrays live on device."""

    def __init__(self, log2_size: int):
        self.log2_size = log2_size
        self.size = 1 << log2_size
        self.t_lo = jnp.zeros(self.size, dtype=jnp.uint32)
        self.t_hi = jnp.zeros(self.size, dtype=jnp.uint32)
        self.p_lo = jnp.zeros(self.size, dtype=jnp.uint32)
        self.p_hi = jnp.zeros(self.size, dtype=jnp.uint32)

    def insert(self, lo, hi, parent_lo, parent_hi, active) -> InsertResult:
        res = _insert(
            self.t_lo, self.t_hi, self.p_lo, self.p_hi,
            lo, hi, parent_lo, parent_hi, active,
        )
        self.t_lo, self.t_hi, self.p_lo, self.p_hi = res[:4]
        return res

    def dump(self) -> dict:
        """Host dict {fingerprint: parent_fingerprint (0 = init)} — used once
        per search for path reconstruction."""
        from .fingerprint import pack_fp

        t_lo = np.asarray(self.t_lo)
        nz = t_lo != 0
        keys = pack_fp(t_lo[nz], np.asarray(self.t_hi)[nz])
        parents = pack_fp(np.asarray(self.p_lo)[nz], np.asarray(self.p_hi)[nz])
        return dict(zip(keys.tolist(), parents.tolist()))


def _rotr(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """uint32 rotate-right by a static 0 <= k < 32 (k=0 is the identity —
    guarded because a shift by 32 is undefined in HLO)."""
    if k == 0:
        return x
    return (x >> jnp.uint32(k)) | (x << jnp.uint32(32 - k))


def _insert_impl(t_lo, t_hi, p_lo, p_hi, lo, hi, parent_lo, parent_hi, active):
    """Batched insert-if-absent. Returns InsertResult; see module docstring.

    Sort-claim design (round-4 silicon profile: the previous phased
    scatter-max claim averaged ~3.9 probe rounds per engine step — colliding
    keys raced for the SAME free slot and serialized round by round — and
    its non-unique scatters lowered to sort-based HLO; together the insert
    was 54% of the paxos-3 step). Here every round is race-free:

    1. sort lanes by (target bucket, hi, lo) — one lax.sort; identical keys
       become adjacent (first pending lane of a run is the REP; the rest
       resolve as duplicates immediately), same-bucket reps are contiguous;
    2. gather each lane's bucket row; reps hit if their key is present;
    3. reps needing a slot get a per-bucket RANK (prefix sums over the
       sorted order) and claim the rank-th free lane of their bucket row —
       distinct (bucket, rank) pairs make all claim targets UNIQUE, so all
       four table components are written with single unique_indices
       scatters: no phases, no readbacks, no arena, and exactly one is_new
       per distinct new key by construction;
    4. only reps whose bucket ran out of free lanes carry to the next round
       (off+1 — chain overflow), so the expected round count is ~1.

    Round-5 shape: the first round is HOISTED out of the while loop as a
    3-operand sort (the loop's 4-operand sort was the single largest op of
    the paxos-3 step — `while.95`, 4.75 of 12.9 ms in the round-4 silicon
    profile). In round 1 every pending lane probes bucket `hi & mask`, so
    the (bucket, hi) sort pair collapses into ONE key: `rotr(hi, log2_nb)`
    moves the bucket bits to the top — sorting by it IS sorting by
    (bucket, rest-of-hi), and it is a bijection of hi, so run detection and
    bucket recovery read the sorted operand directly (no gathers). Inactive
    lanes sort to the unique sentinel pair (0xFFFFFFFF, lo=0) — a real key
    never has lo == 0 (tensor/fingerprint.py forces lo nonzero) — which
    also keeps equal-key runs contiguous in the one tie block. The unsort
    is one iota scatter (the inverse permutation) + cheap gathers instead
    of three scatters. The while loop below only runs for bucket-overflow
    carries (per-lane probe offsets diverge there, so it keeps the general
    4-operand sort) — at sane load factors it executes ZERO iterations.

    Resolved/inactive lanes sort to a sentinel bucket past the end, which
    also keeps a key run's rep well-defined when some of its lanes are
    inactive. Claimed slots are never emptied, so linear bucket probing and
    the membership argument (a key absent from the first non-full bucket of
    its chain is absent) stay correct.
    """
    size = t_lo.shape[0]
    bucket = min(BUCKET, size)  # tiny tables (tests) shrink to one bucket
    n_buckets = size // bucket
    log2_nb = n_buckets.bit_length() - 1  # size and bucket are powers of 2
    B = lo.shape[0]
    bmask = jnp.int32(n_buckets - 1)
    b0 = (hi & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
    idx = jnp.arange(B, dtype=jnp.int32)

    def claim(t_lo, t_hi, p_lo, p_hi, is_new_in, sb, s_hi, s_lo, s_active,
              perm):
        """One race-free claim round over pre-sorted lanes (shared by the
        hoisted fast path and the overflow loop; see the kv variant for the
        same shape). Returns carry_on in ORIGINAL lane order."""
        same_prev = (
            (sb == jnp.roll(sb, 1))
            & (s_hi == jnp.roll(s_hi, 1))
            & (s_lo == jnp.roll(s_lo, 1))
        ).at[0].set(False)
        rep = s_active & ~same_prev

        rows_lo = t_lo.reshape(n_buckets, bucket)[sb]  # free bitcast view
        rows_hi = t_hi.reshape(n_buckets, bucket)[sb]
        hit = rep & jnp.any(
            (rows_lo == s_lo[:, None]) & (rows_hi == s_hi[:, None]), axis=1
        )
        need = rep & ~hit

        # Per-bucket rank of `need` lanes: exclusive prefix count within the
        # sorted bucket segment (segment base carried forward by cummax —
        # the exclusive prefix is non-decreasing, and lane 0 always starts a
        # segment, so the -1 filler never wins).
        seg_start = (sb != jnp.roll(sb, 1)).at[0].set(True)
        excl = jnp.cumsum(need.astype(jnp.int32)) - need.astype(jnp.int32)
        seg_base = jax.lax.cummax(jnp.where(seg_start, excl, jnp.int32(-1)))
        rank = excl - seg_base

        free_m = rows_lo == 0
        # Lane-wise inclusive prefix count as one MXU matmul against an
        # upper-triangular ones matrix: XLA lowers an axis-1 cumsum to
        # reduce_window (~2.7 ms/step on v5e at engine batch sizes) while
        # the [B,128]@[128,128] matmul is ~free; counts <= 128 are exact in
        # bf16 with f32 accumulation.
        tri = jnp.triu(jnp.ones((bucket, bucket), jnp.bfloat16))
        fcum = (
            jnp.dot(
                free_m.astype(jnp.bfloat16),
                tri,
                preferred_element_type=jnp.float32,
            )
            .astype(jnp.int32)
        )
        pick = free_m & (fcum == (rank + 1)[:, None])  # rank-th free lane
        can_claim = need & jnp.any(pick, axis=1)
        slot = sb * bucket + jnp.argmax(pick, axis=1).astype(jnp.int32)

        tgt = jnp.where(can_claim, slot, size)
        t_lo = t_lo.at[tgt].set(s_lo, mode="drop", unique_indices=True)
        t_hi = t_hi.at[tgt].set(s_hi, mode="drop", unique_indices=True)
        p_lo = p_lo.at[tgt].set(
            parent_lo[perm], mode="drop", unique_indices=True
        )
        p_hi = p_hi.at[tgt].set(
            parent_hi[perm], mode="drop", unique_indices=True
        )

        # Unsort via the inverse permutation: one iota scatter + gathers.
        inv_perm = jnp.zeros(B, jnp.int32).at[perm].set(
            idx, unique_indices=True
        )
        is_new = is_new_in | can_claim[inv_perm]
        carry_on = (need & ~can_claim)[inv_perm]  # full -> probe bucket +1
        return t_lo, t_hi, p_lo, p_hi, is_new, carry_on

    # -- round 1, hoisted: 3-operand sort-claim at probe offset 0 --------------
    key0 = jnp.where(active, _rotr(hi, log2_nb), jnp.uint32(0xFFFFFFFF))
    lo_m = jnp.where(active, lo, jnp.uint32(0))
    s_key0, s_lo, perm = jax.lax.sort((key0, lo_m, idx), num_keys=2)
    s_active = ~((s_key0 == jnp.uint32(0xFFFFFFFF)) & (s_lo == 0))
    s_hi = _rotr(s_key0, (32 - log2_nb) % 32)  # rotate back: bijection
    sb = (
        (s_key0 >> jnp.uint32(32 - log2_nb)).astype(jnp.int32)
        if log2_nb
        else jnp.zeros(B, jnp.int32)
    )
    t_lo, t_hi, p_lo, p_hi, is_new0, carry0 = claim(
        t_lo, t_hi, p_lo, p_hi, jnp.zeros_like(active), sb, s_hi, s_lo,
        s_active, perm,
    )
    off0 = carry0.astype(jnp.int32)

    def cond(carry):
        (_tl, _th, _pl, _ph, pending, _new, _off, rounds) = carry
        return jnp.any(pending) & (rounds < MAX_ROUNDS)

    def body(carry):
        t_lo, t_hi, p_lo, p_hi, pending, is_new, off, rounds = carry
        b = (b0 + off) & bmask
        bkey = jnp.where(pending, b, jnp.int32(n_buckets))
        sb, s_hi, s_lo, perm = jax.lax.sort(
            (bkey, hi, lo, idx), num_keys=3
        )
        s_active = sb < jnp.int32(n_buckets)
        sb_c = jnp.minimum(sb, jnp.int32(n_buckets - 1))
        t_lo, t_hi, p_lo, p_hi, is_new, carry_on = claim(
            t_lo, t_hi, p_lo, p_hi, is_new, sb_c, s_hi, s_lo, s_active, perm
        )
        off = off + carry_on.astype(jnp.int32)
        return t_lo, t_hi, p_lo, p_hi, carry_on, is_new, off, rounds + 1

    t_lo, t_hi, p_lo, p_hi, pending, is_new, _off, _rounds = (
        jax.lax.while_loop(
            cond,
            body,
            (t_lo, t_hi, p_lo, p_hi, carry0, is_new0, off0, jnp.int32(1)),
        )
    )
    return InsertResult(t_lo, t_hi, p_lo, p_hi, is_new, jnp.any(pending))


_insert = partial(jax.jit, donate_argnums=(0, 1, 2, 3))(_insert_impl)


class InsertKvResult(NamedTuple):
    t_kv: jnp.ndarray  # uint32[2S] interleaved-bucket table
    p_lo: jnp.ndarray  # uint32[S]
    p_hi: jnp.ndarray  # uint32[S]
    is_new: jnp.ndarray  # bool[B]
    overflow: jnp.ndarray  # bool


KV_BUCKET = 64  # slots per bucket; a row is 2*KV_BUCKET = 128 lanes (lo|hi)


def _insert_impl_kv(t_kv, p_lo, p_hi, lo, hi, parent_lo, parent_hi, active):
    """Interleaved-bucket variant of `_insert_impl`: the table is ONE
    uint32[2S] array whose 128-lane rows hold a 64-slot bucket as
    [lo_0..lo_63 | hi_0..hi_63], so each probe gathers HALF the bytes of
    the split layout (one [B, 128] row fetch instead of two) while the
    128-lane row keeps the (nb, 128) view a free bitcast under T(8,128)
    tiling — the same tile-padding argument that fixed the round-4 16x tax
    (module docstring). 64-slot buckets overflow to the next bucket exactly
    like 128-slot ones (vanishingly rare at sane load factors, and the
    carry loop handles it). Parents stay split (p_lo/p_hi, indexed by slot
    id) — they are only ever written here, never gathered.

    Claim logic is byte-for-byte the split fast path with bucket=64; see
    `_insert_impl` for the algorithm and safety argument. Flag-gated via
    the engines' `table_layout="kv"` until the silicon race decides a
    default (the bucket-row gathers were the second-largest slice of the insert after the sort).
    """
    size = p_lo.shape[0]  # S slots; t_kv has 2S lanes
    bucket = min(KV_BUCKET, size)
    n_buckets = size // bucket
    log2_nb = n_buckets.bit_length() - 1
    row_w = 2 * bucket
    B = lo.shape[0]
    bmask = jnp.int32(n_buckets - 1)
    b0 = (hi & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
    idx = jnp.arange(B, dtype=jnp.int32)

    def claim(t_kv, p_lo, p_hi, is_new_in, sb, s_hi, s_lo, s_active, perm):
        """One race-free claim round over pre-sorted lanes (shared by the
        hoisted fast path and the overflow loop)."""
        same_prev = (
            (sb == jnp.roll(sb, 1))
            & (s_hi == jnp.roll(s_hi, 1))
            & (s_lo == jnp.roll(s_lo, 1))
        ).at[0].set(False)
        rep = s_active & ~same_prev

        rows = t_kv.reshape(n_buckets, row_w)[sb]  # free bitcast view
        rows_lo = rows[:, :bucket]
        rows_hi = rows[:, bucket:]
        hit = rep & jnp.any(
            (rows_lo == s_lo[:, None]) & (rows_hi == s_hi[:, None]), axis=1
        )
        need = rep & ~hit

        seg_start = (sb != jnp.roll(sb, 1)).at[0].set(True)
        excl = jnp.cumsum(need.astype(jnp.int32)) - need.astype(jnp.int32)
        seg_base = jax.lax.cummax(
            jnp.where(seg_start, excl, jnp.int32(-1))
        )
        rank = excl - seg_base

        free_m = rows_lo == 0
        tri = jnp.triu(jnp.ones((bucket, bucket), jnp.bfloat16))
        fcum = (
            jnp.dot(
                free_m.astype(jnp.bfloat16), tri,
                preferred_element_type=jnp.float32,
            )
            .astype(jnp.int32)
        )
        pick = free_m & (fcum == (rank + 1)[:, None])
        can_claim = need & jnp.any(pick, axis=1)
        lane = jnp.argmax(pick, axis=1).astype(jnp.int32)

        tgt_lo = jnp.where(can_claim, sb * row_w + lane, 2 * size)
        tgt_hi = jnp.where(can_claim, sb * row_w + bucket + lane, 2 * size)
        slot = jnp.where(can_claim, sb * bucket + lane, size)
        t_kv = t_kv.at[tgt_lo].set(s_lo, mode="drop", unique_indices=True)
        t_kv = t_kv.at[tgt_hi].set(s_hi, mode="drop", unique_indices=True)
        p_lo = p_lo.at[slot].set(
            parent_lo[perm], mode="drop", unique_indices=True
        )
        p_hi = p_hi.at[slot].set(
            parent_hi[perm], mode="drop", unique_indices=True
        )

        inv_perm = jnp.zeros(B, jnp.int32).at[perm].set(
            idx, unique_indices=True
        )
        is_new = is_new_in | can_claim[inv_perm]
        carry_on = (need & ~can_claim)[inv_perm]
        return t_kv, p_lo, p_hi, is_new, carry_on

    # -- round 1, hoisted: 3-operand sort at probe offset 0 --------------------
    key0 = jnp.where(active, _rotr(hi, log2_nb), jnp.uint32(0xFFFFFFFF))
    lo_m = jnp.where(active, lo, jnp.uint32(0))
    s_key0, s_lo, perm = jax.lax.sort((key0, lo_m, idx), num_keys=2)
    s_active = ~((s_key0 == jnp.uint32(0xFFFFFFFF)) & (s_lo == 0))
    s_hi = _rotr(s_key0, (32 - log2_nb) % 32)
    sb = (
        (s_key0 >> jnp.uint32(32 - log2_nb)).astype(jnp.int32)
        if log2_nb
        else jnp.zeros(B, jnp.int32)
    )
    t_kv, p_lo, p_hi, is_new0, carry0 = claim(
        t_kv, p_lo, p_hi, jnp.zeros_like(active), sb, s_hi, s_lo,
        s_active, perm,
    )
    off0 = carry0.astype(jnp.int32)

    # -- overflow carries: generic 4-operand rounds (rare) ---------------------
    def cond(carry):
        (_kv, _pl, _ph, pending, _new, _off, rounds) = carry
        return jnp.any(pending) & (rounds < MAX_ROUNDS)

    def body(carry):
        t_kv, p_lo, p_hi, pending, is_new, off, rounds = carry
        b = (b0 + off) & bmask
        bkey = jnp.where(pending, b, jnp.int32(n_buckets))
        sb, s_hi, s_lo, perm = jax.lax.sort(
            (bkey, hi, lo, idx), num_keys=3
        )
        s_active = sb < jnp.int32(n_buckets)
        sb_c = jnp.minimum(sb, jnp.int32(n_buckets - 1))
        t_kv, p_lo, p_hi, is_new, carry_on = claim(
            t_kv, p_lo, p_hi, is_new, sb_c, s_hi, s_lo, s_active, perm
        )
        off = off + carry_on.astype(jnp.int32)
        return t_kv, p_lo, p_hi, carry_on, is_new, off, rounds + 1

    t_kv, p_lo, p_hi, pending, is_new, _off, _rounds = jax.lax.while_loop(
        cond, body, (t_kv, p_lo, p_hi, carry0, is_new0, off0, jnp.int32(1))
    )
    return InsertKvResult(t_kv, p_lo, p_hi, is_new, jnp.any(pending))


class HashTableKV:
    """Host-side handle for the interleaved-bucket table (tests + dump)."""

    def __init__(self, log2_size: int):
        self.log2_size = log2_size
        self.size = 1 << log2_size
        self.t_kv = jnp.zeros(2 * self.size, dtype=jnp.uint32)
        self.p_lo = jnp.zeros(self.size, dtype=jnp.uint32)
        self.p_hi = jnp.zeros(self.size, dtype=jnp.uint32)

    def insert(self, lo, hi, parent_lo, parent_hi, active) -> InsertKvResult:
        res = _insert_kv(
            self.t_kv, self.p_lo, self.p_hi,
            lo, hi, parent_lo, parent_hi, active,
        )
        self.t_kv, self.p_lo, self.p_hi = res[:3]
        return res

    def dump(self) -> dict:
        from .fingerprint import pack_fp

        bucket = min(KV_BUCKET, self.size)
        kv = np.asarray(self.t_kv).reshape(-1, 2 * bucket)
        t_lo = kv[:, :bucket].reshape(-1)
        t_hi = kv[:, bucket:].reshape(-1)
        nz = t_lo != 0
        keys = pack_fp(t_lo[nz], t_hi[nz])
        parents = pack_fp(
            np.asarray(self.p_lo)[nz], np.asarray(self.p_hi)[nz]
        )
        return dict(zip(keys.tolist(), parents.tolist()))


_insert_kv = partial(jax.jit, donate_argnums=(0, 1, 2))(_insert_impl_kv)


def _insert_impl_phased(
    t_lo, t_hi, p_lo, p_hi, lo, hi, parent_lo, parent_hi, active
):
    """The round-1..3 PHASED scatter-max insert, revived as a raceable
    variant: at paxos-2 scale the sort-claim insert's
    fixed sort cost dominated tiny frontiers (162k -> 94k states/s at
    b=2048 on v5e) while the phased design's ~few serialized probe rounds
    are cheap when batches are small and collisions rare. The engines race
    it per-workload via `ResidentSearch(insert_variant="phased")` /
    scripts/tpu_tune.py; the sort-claim stays the at-scale default (2.5-3.7x
    faster at paxos-3 scale — the 54%-of-step profile that retired this
    design, now with the round-5 128-lane buckets it never had).

    Claim protocol per probe round (all races resolved by scatter-max):
    phase 1 races `lo` into the bucket's first free slot (winner = max lo),
    phase 2 tie-breaks equal-lo distinct keys on `hi`, phase 3 races the
    lane index into the parent slot (still zero for a fresh claim) so
    exactly one duplicate lane wins `is_new`; real parents overwrite the
    arena residue after the loop. Losers re-probe next round; full buckets
    overflow to the next bucket.
    """
    size = t_lo.shape[0]
    bucket = min(BUCKET, size)
    n_buckets = size // bucket
    bmask = jnp.uint32(n_buckets - 1)
    b0 = hi & bmask
    lane_ix = jnp.arange(lo.shape[0], dtype=jnp.uint32) + jnp.uint32(1)

    def cond(carry):
        (_tl, _th, _pl, done, _new, _slot, _off, rounds) = carry
        return (~jnp.all(done)) & (rounds < MAX_ROUNDS)

    def body(carry):
        t_lo, t_hi, p_lo, done, is_new, slot, off, rounds = carry
        b = ((b0 + off) & bmask).astype(jnp.int32)
        rows_lo = t_lo.reshape(n_buckets, bucket)[b]  # free bitcast view
        rows_hi = t_hi.reshape(n_buckets, bucket)[b]
        hit_j = (rows_lo == lo[:, None]) & (rows_hi == hi[:, None])
        hit = (~done) & jnp.any(hit_j, axis=1)
        hit_slot = b * bucket + jnp.argmax(hit_j, axis=1).astype(jnp.int32)

        free = rows_lo == 0
        has_free = jnp.any(free, axis=1)
        cand = b * bucket + jnp.argmax(free, axis=1).astype(jnp.int32)
        attempt = (~done) & (~hit) & has_free

        tgt = jnp.where(attempt, cand, size)
        t_lo = t_lo.at[tgt].max(jnp.where(attempt, lo, 0), mode="drop")
        got_lo = attempt & (
            t_lo.at[cand].get(mode="fill", fill_value=0) == lo
        )
        tgt = jnp.where(got_lo, cand, size)
        t_hi = t_hi.at[tgt].max(jnp.where(got_lo, hi, 0), mode="drop")
        claimed = got_lo & (
            t_hi.at[cand].get(mode="fill", fill_value=0) == hi
        )
        tgt = jnp.where(claimed, cand, size)
        p_lo = p_lo.at[tgt].max(jnp.where(claimed, lane_ix, 0), mode="drop")
        winner = claimed & (
            p_lo.at[cand].get(mode="fill", fill_value=0) == lane_ix
        )

        slot = jnp.where(
            hit | claimed, jnp.where(hit, hit_slot, cand), slot
        )
        is_new = is_new | winner
        newly_done = hit | claimed
        off = jnp.where(
            (~done) & (~newly_done) & (~has_free), off + 1, off
        )
        return (
            t_lo, t_hi, p_lo, done | newly_done, is_new, slot, off,
            rounds + 1,
        )

    done0 = ~active
    zeros_i = jnp.zeros_like(lo, dtype=jnp.int32)
    t_lo, t_hi, p_lo, done, is_new, slot, _off, _rounds = (
        jax.lax.while_loop(
            cond,
            body,
            (t_lo, t_hi, p_lo, done0, jnp.zeros_like(active), zeros_i,
             zeros_i, jnp.int32(0)),
        )
    )
    ptgt = jnp.where(is_new, slot, size)
    p_lo = p_lo.at[ptgt].set(parent_lo, mode="drop")
    p_hi = p_hi.at[ptgt].set(parent_hi, mode="drop")
    return InsertResult(t_lo, t_hi, p_lo, p_hi, is_new, ~jnp.all(done))


# -- batch-monotonic capped insert ---------------------------------------------
#
# The sort-claim inserts above pay a FULL-BATCH sort per call — B·log(B)
# regardless of how many lanes actually need attention. At engine scale B
# is batch × max_actions, many of those lanes are padding (sub-full
# frontiers pop fixed-size batches) or duplicates of already-visited
# states, and the sort volume is why measured states/s FALLS with batch
# size (b=32768 was 1.6x slower than b=4096 on paxos-3 in round 4;
# same super-linear term on the CPU backend, so it is algorithmic). The
# capped path makes per-call probe AND sort cost scale with the POPULATED
# lanes instead:
#
# 1. active lanes are cumsum-compacted into a dense prefix (the
#    compact_new technique from tensor/frontier.py — O(B) elementwise, no
#    128-wide gathers, no sort);
# 2. fixed-size power-of-two CLAIM TILES of that prefix run the underlying
#    insert — tile shapes are static, so the whole thing lives happily
#    inside jit / lax.while_loop. Each tile's own bucket-row probe IS the
#    membership filter: lanes whose key is already committed resolve as
#    hits, so the duplicate-claim sort never exceeds T·log(T) per tile and
#    total tile work is ~n_active/T tiles, not B/T. Duplicates that
#    straddle tiles are resolved because a later tile's probe simply hits
#    the earlier tile's committed slot.
#
# (A variant with a SEPARATE up-front membership probe — gather all B
# home-bucket rows, then tile only the missing lanes — was measured and
# cost-modeled: the extra full-width gather re-reads rows the claim tiles
# gather again, and loses to this fused form at every candidate fraction;
# see tensor/costmodel.py.)
#
# Correctness rides entirely on the underlying insert: the wrapper only
# compacts and re-batches the active lanes, each original lane lands in
# exactly one tile, and tile order is deterministic — so per-call `is_new`
# attribution (one per distinct new key) is inherited unchanged.


def make_capped_insert(inner, n_state, result_cls, tile=CLAIM_TILE):
    """Wrap an insert impl (`inner`, taking `n_state` table arrays followed
    by lo/hi/parent_lo/parent_hi/active and returning `result_cls`) in the
    active-compaction + claim-tile structure described above."""

    def capped(*args):
        state = args[:n_state]
        lo, hi, parent_lo, parent_hi, active = args[n_state:]
        B = lo.shape[0]
        pow2_B = 1 << max(B - 1, 1).bit_length()
        # Tile size: at least CLAIM_TILE lanes, growing for huge batches so
        # the serialized tile count never exceeds CAP_MAX_TILES.
        T = min(pow2_B, max(tile, pow2_B // CAP_MAX_TILES))
        P = -(-B // T) * T  # padded prefix length: dynamic_slice never clamps

        n_act = active.sum().astype(jnp.int32)

        # Dense-prefix compaction (sort-free cumsum scatter); invalid lanes
        # land at P / map back to the out-of-range index B ("drop").
        pos_all = jnp.cumsum(active.astype(jnp.int32)) - 1
        pos = jnp.where(active, pos_all, P)
        c_lo = jnp.zeros(P, jnp.uint32).at[pos].set(lo, mode="drop")
        c_hi = jnp.zeros(P, jnp.uint32).at[pos].set(hi, mode="drop")
        c_plo = jnp.zeros(P, jnp.uint32).at[pos].set(parent_lo, mode="drop")
        c_phi = jnp.zeros(P, jnp.uint32).at[pos].set(parent_hi, mode="drop")
        c_src = jnp.full(P, B, jnp.int32).at[pos].set(
            jnp.arange(B, dtype=jnp.int32), mode="drop"
        )

        tix = jnp.arange(T, dtype=jnp.int32)
        n_tiles = (n_act + (T - 1)) // T

        def cond_f(carry):
            return carry[-1] < n_tiles

        def body_f(carry):
            st = carry[:n_state]
            is_new, ovf, t = carry[n_state:]
            start = t * T
            res = inner(
                *st,
                jax.lax.dynamic_slice(c_lo, (start,), (T,)),
                jax.lax.dynamic_slice(c_hi, (start,), (T,)),
                jax.lax.dynamic_slice(c_plo, (start,), (T,)),
                jax.lax.dynamic_slice(c_phi, (start,), (T,)),
                (start + tix) < n_act,
            )
            src = jax.lax.dynamic_slice(c_src, (start,), (T,))
            is_new = is_new.at[src].set(
                res[n_state], mode="drop", unique_indices=True
            )
            return (*res[:n_state], is_new, ovf | res[n_state + 1], t + 1)

        out = jax.lax.while_loop(
            cond_f,
            body_f,
            (*state, jnp.zeros(B, dtype=bool), jnp.bool_(False), jnp.int32(0)),
        )
        return result_cls(*out[: n_state + 2])

    return capped


_insert_impl_capped = make_capped_insert(_insert_impl, 4, InsertResult)
_insert_impl_kv_capped = make_capped_insert(_insert_impl_kv, 3, InsertKvResult)
_insert_impl_phased_capped = make_capped_insert(
    _insert_impl_phased, 4, InsertResult
)
