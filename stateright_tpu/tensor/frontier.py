"""Frontier-synchronous batched BFS on device — the TPU replacement for the
reference's hot loop (`check_block`, src/checker/bfs.rs:177-335).

One jitted step fuses, for a batch of up to `batch_size` frontier states:
property-mask evaluation, successor expansion (`TensorModel.expand`), boundary
masking, on-device fingerprinting (u32 pairs — no 64-bit emulation on TPU),
and visited-set insertion with parent tracking. Intra-batch duplicates are
resolved INSIDE the hash-table insert (phase-3 arena, tensor/hashtable.py),
so there is no per-step sort; new states are compacted with a cumsum scatter.
The host orchestrates the frontier queue, eventually-bit bookkeeping,
discovery recording, and early exit — exactly the split SURVEY.md §7
prescribes (host keeps the user-facing API and path reconstruction; the
device owns the hot loop).

Search semantics match the host BFS checker bit-for-bit where observable:
state/unique counts, boundary handling, depth cutoffs, eventually-bit false
negatives at revisits, early exit once every property has a discovery.
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..compile_cache import REPLAYS
from ..core.discovery import HasDiscoveries
from ..core.model import Expectation
from ..core.path import Path
from ..knobs import APPEND_KINDS, STORE_KINDS, WARM_KINDS
from ..faults.ckptio import fenced_savez, load_latest
from ..faults.plan import maybe_fault
from ..store import warm as warm_seam
from ..obs import NULL_TRACER, REGISTRY, StepRing, as_tracer, build_detail
from .costmodel import ENGINE_VARIANTS
from .fingerprint import device_fingerprint, pack_fp
from .hashtable import _insert_impl
from .inserts import INSERT_TABLE, make_table, resolve_insert
from .model import TensorModel


def state_fingerprint(model: "TensorModel", states: jnp.ndarray):
    """(lo, hi) fingerprint for identity purposes: the canonical (symmetry
    representative) form when the model defines one, else the state itself."""
    if model.representative is not None:
        states = model.representative(states)
    return device_fingerprint(states)


def seed_init(model: "TensorModel"):
    """Boundary-filter and fingerprint-dedup the initial states on host.

    Returns (states uint32[n0, L], lo uint32[n0], hi uint32[n0], n_raw) where
    n_raw is the PRE-dedup in-boundary count — the host checkers seed
    state_count with the raw init list length (ref: src/checker/bfs.rs:54), so
    count parity requires it.
    """
    init = np.asarray(model.init_states(), dtype=np.uint32)
    in_bounds = np.asarray(model.within_boundary(jnp.asarray(init)))
    init = init[in_bounds]
    n_raw = len(init)
    lo, hi = (np.asarray(x) for x in state_fingerprint(model, jnp.asarray(init)))
    _, first_pos = np.unique(pack_fp(lo, hi), return_index=True)
    keep = np.sort(first_pos)
    return init[keep], lo[keep], hi[keep], n_raw


# -- u64-as-u32-pair counters (device counts can exceed 2^32) ------------------


def count_add(clo, chi, x):
    """(lo, hi) += x for u32 pair counters; x is u32/i32 (< 2^32)."""
    nlo = clo + x.astype(jnp.uint32)
    return nlo, chi + (nlo < clo).astype(jnp.uint32)


def count_ge(clo, chi, tlo, thi):
    return (chi > thi) | ((chi == thi) & (clo >= tlo))


def expand_insert(
    model, t_lo, t_hi, p_lo, p_hi, states, lo, hi, active,
    insert=_insert_impl, salt_lo=None, salt_hi=None,
    summary=None, summary_cfg=None,
):
    """The traced core of one frontier step, shared by the host-orchestrated
    and device-resident engines: expand, boundary-mask, fingerprint, visited-
    set insert with parent tracking (the insert also dedups within the batch).

    Returns (t_lo, t_hi, p_lo, p_hi, flat_states, succ_lo, succ_hi, is_new,
    suspect, gen_rows, has_succ, overflow); row i of the flattened successor
    arrays came from input row i // max_actions; `gen_rows` is the
    per-input-row post-boundary pre-dedup successor count (ref:
    bfs.rs:288-291 — callers sum it for the generated-state counter; the
    check service segments it by the lane's job). `insert` swaps the
    visited-set implementation (same 9-arg signature/6-tuple result as
    hashtable._insert_impl; resolve via tensor/inserts.py) — the engines use
    it for the interleaved-kv table layout, where t_lo is the uint32[2S] kv
    array and t_hi is a zero-length placeholder.

    `salt_lo`/`salt_hi` (uint32[K] per-lane, optional) fold a per-job salt
    into every key the visited set sees — successor keys AND the parent
    pointers stored beside them — so concurrent jobs can share one table
    with zero cross-job collisions (see fingerprint.salt_fp). The RETURNED
    succ_lo/succ_hi stay unsalted: they are the state identities the host
    uses for discovery recording and queue bookkeeping, bit-identical to a
    standalone (unsalted) run.

    `summary` (+ `summary_cfg=(summary_log2, hashes)`) is the tiered
    store's Bloom summary of the spilled set: when given, the returned
    `suspect` mask marks fresh claims whose TABLE key (salted when salts
    are given — the spill tier stores table keys) hits the summary and so
    needs exact host resolution. Inserts marked `fused_summary` (the
    Pallas kernel) compute the probe inside their own partition pass; for
    every other insert the probe is the usual maybe_contains gather sweep.
    Without a summary, `suspect` is all-False.
    """
    K = states.shape[0]
    A = model.max_actions
    # Named scopes mark each phase's ops in the HLO metadata (a device
    # trace's `tf_op` path) for per-kernel attribution; they change neither
    # the program nor its fusion. XLA names a fusion by its root op, so a
    # fusion that spans two scopes counts toward one of them.
    with jax.named_scope("expand"):
        succs, valid = model.expand(states)
        valid = valid & active[:, None]
        flat = succs.reshape(K * A, model.lanes)
        validf = valid.reshape(-1) & model.within_boundary(flat)
        # Generated-state count is pre-dedup, post-boundary (ref:
        # bfs.rs:288-291).
        gen_rows = validf.reshape(K, A).sum(axis=1).astype(jnp.uint32)
        # Terminality counts deduped successors too, but not
        # boundary-excluded ones (ref: bfs.rs:287-333).
        has_succ = validf.reshape(K, A).any(axis=1)

    with jax.named_scope("fingerprint"):
        slo, shi = state_fingerprint(model, flat)
        par_lo = jnp.repeat(lo, A)
        par_hi = jnp.repeat(hi, A)
        if salt_lo is not None:
            from .fingerprint import salt_fp

            sl_rep = jnp.repeat(salt_lo, A)
            sh_rep = jnp.repeat(salt_hi, A)
            key_lo, key_hi = salt_fp(slo, shi, sl_rep, sh_rep)
            par_lo, par_hi = salt_fp(par_lo, par_hi, sl_rep, sh_rep)
        else:
            key_lo, key_hi = slo, shi
    with jax.named_scope("insert"):
        if summary is not None and getattr(insert, "fused_summary", False):
            t_lo, t_hi, p_lo, p_hi, is_new, suspect, ovf = insert(
                t_lo, t_hi, p_lo, p_hi, key_lo, key_hi, par_lo, par_hi,
                validf, summary,
            )
        else:
            t_lo, t_hi, p_lo, p_hi, is_new, ovf = insert(
                t_lo, t_hi, p_lo, p_hi, key_lo, key_hi, par_lo, par_hi,
                validf,
            )
            if summary is not None:
                from ..store.summary import maybe_contains

                slog2, khash = summary_cfg
                suspect = is_new & maybe_contains(
                    summary, key_lo, key_hi, slog2, khash
                )
            else:
                suspect = jnp.zeros_like(is_new)
    return (
        t_lo, t_hi, p_lo, p_hi,
        flat, slo, shi, is_new, suspect,
        gen_rows, has_succ, ovf,
    )


def pop_batch(q_states, q_lo, q_hi, q_ebits, q_depth, head, tail, K):
    """Pop up to K rows from the in-device frontier queue as contiguous
    dynamic slices (the queue never wraps — see the resident engine's
    capacity argument). Returns (states, lo, hi, ebits, depth, active,
    new_head). Shared by the resident and sharded engines."""
    L = q_states.shape[1]
    take = jnp.minimum(tail - head, K)
    states = jax.lax.dynamic_slice(q_states, (head, 0), (K, L))
    lo = jax.lax.dynamic_slice(q_lo, (head,), (K,))
    hi = jax.lax.dynamic_slice(q_hi, (head,), (K,))
    ebits = jax.lax.dynamic_slice(q_ebits, (head,), (K,))
    depth = jax.lax.dynamic_slice(q_depth, (head,), (K,))
    active = jnp.arange(K, dtype=jnp.int32) < take
    return states, lo, hi, ebits, depth, active, head + take


def append_new(  # srlint: step-region
    q_states, q_lo, q_hi, q_ebits, q_depth, tail,
    flat, slo, shi, ebits_rows, depth_rows, is_new,
):
    """Append the is_new rows at the queue tail via cumsum-compacted scatter
    (sort-free). Returns the five queue arrays and the new tail. Shared by
    the resident and sharded engines."""
    Q = q_lo.shape[0]
    pos_all = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    qpos = jnp.where(is_new, tail + pos_all, Q)
    q_states = q_states.at[qpos].set(flat, mode="drop")
    q_lo = q_lo.at[qpos].set(slo, mode="drop")
    q_hi = q_hi.at[qpos].set(shi, mode="drop")
    q_ebits = q_ebits.at[qpos].set(ebits_rows, mode="drop")
    q_depth = q_depth.at[qpos].set(depth_rows, mode="drop")
    tail = tail + is_new.sum().astype(jnp.int32)
    return q_states, q_lo, q_hi, q_ebits, q_depth, tail


def resolve_append(append, platform: str) -> str:
    """One source of truth for the queue-append variant default: the
    row-scatter append is pathological on TPU (column-major queue layout;
    44.7% of the paxos-3 step — round-4 silicon profile) while the
    compact+dynamic_update_slice form measured ~5x slower on the 1-core
    CPU backend at 2pc-10 scale, so the default follows the platform the
    engine will actually run on."""
    if append is None:
        return "scatter" if platform == "cpu" else "dus"
    if append not in APPEND_KINDS:  # one knob universe: stateright_tpu/knobs.py
        raise ValueError(f"append must be one of {APPEND_KINDS}, got {append!r}")
    return append


def append_rows(lanes: int, tile: int) -> int:
    """The most rows one `append_new_dus` call over `lanes` lanes writes
    past the tail: whole tiles of min(tile, lanes) rows. Queue slack and
    queue-full guards are sized by it, so the DUS start never clamps."""
    t = min(tile, lanes)
    return -(-lanes // t) * t


def append_new_dus(  # srlint: step-region
    q_states, q_lo, q_hi, q_ebits, q_depth, tail,
    flat, slo, shi, ebits_rows, depth_rows, is_new, *, tile, fan=1,
):
    """DUS-append: write the is_new rows at the queue tail, in lane order,
    as `tile`-row blocks, each with ONE contiguous `dynamic_update_slice`
    per queue array.

    Compaction is by lane index, not by row: one int32 scatter maps each
    compacted row to its source lane (rows past the new count map to M,
    the padding lane, which gathers zeros); then each of the
    ceil(new_count / tile) tiles gathers its rows of every column and
    writes them. A step with few new rows runs one tile, whatever M is.
    `fan`: `ebits_rows` and `depth_rows` hold one entry per `fan`
    consecutive lanes (lane i reads entry i // fan), so an engine passes
    its popped batch's columns without expanding them to M lanes.

    Why this exists next to `append_new` (whole-array scatter): XLA reliably
    updates a DUS'd while-loop carry IN PLACE, while the equivalent scatter
    was measured copying the multi-GB queue arrays every step (2pc-10,
    batch 8192, table 2^27: ~77% of per-step execution time was `copy.*`
    thunks in the round-4 CPU trace — the round-3 "staged append-DUS
    experiment" evidence). CONTRACT: the caller must allocate Q >= max_tail
    + append_rows(M, tile) slack so the DUS start never clamps (a clamped
    start would shift live rows); rows past tail + new_count up to the last
    tile's end become zero scratch beyond the tail, which nothing reads
    (pops are bounded by tail)."""
    M, L = flat.shape
    T = min(tile, M)
    MP = append_rows(M, T)
    pos_all = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    n_new = is_new.sum().astype(jnp.int32)
    src = jnp.full(MP, M, jnp.int32).at[jnp.where(is_new, pos_all, MP)].set(
        jnp.arange(M, dtype=jnp.int32), mode="drop", unique_indices=True
    )

    def take(col, ix):
        return col.at[ix].get(mode="fill", fill_value=0)

    def write_tile(t, q):
        rows = jax.lax.dynamic_slice(src, (t * T,), (T,))
        par = rows // fan if fan > 1 else rows
        at = tail + t * T
        qs, ql, qh, qe, qd = q
        # The state rows are gathered a lane column at a time: with one row
        # gather XLA laid the queue out row-major and copied all of it back
        # to column-major for the next pop, every step (paxos-3 on a v5e).
        states = jnp.stack([take(flat[:, j], rows) for j in range(L)], axis=1)
        return (
            jax.lax.dynamic_update_slice(qs, states, (at, 0)),
            jax.lax.dynamic_update_slice(ql, take(slo, rows), (at,)),
            jax.lax.dynamic_update_slice(qh, take(shi, rows), (at,)),
            jax.lax.dynamic_update_slice(qe, take(ebits_rows, par), (at,)),
            jax.lax.dynamic_update_slice(qd, take(depth_rows, par), (at,)),
        )

    q_states, q_lo, q_hi, q_ebits, q_depth = jax.lax.fori_loop(
        0, (n_new + T - 1) // T, write_tile,
        (q_states, q_lo, q_hi, q_ebits, q_depth),
    )
    return q_states, q_lo, q_hi, q_ebits, q_depth, tail + n_new


def compact_new(flat, slo, shi, is_new):
    """Scatter-compact the is_new rows (and their fingerprints + source row
    indices) to the front — the sort-free replacement for argsort ranking.
    Returns (states, lo, hi, src_rows, new_count)."""
    M, L = flat.shape
    pos_all = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    pos = jnp.where(is_new, pos_all, M)
    out_states = jnp.zeros((M, L), flat.dtype).at[pos].set(flat, mode="drop")
    out_lo = jnp.zeros(M, jnp.uint32).at[pos].set(slo, mode="drop")
    out_hi = jnp.zeros(M, jnp.uint32).at[pos].set(shi, mode="drop")
    src = jnp.arange(M, dtype=jnp.int32)
    out_src = jnp.zeros(M, jnp.int32).at[pos].set(src, mode="drop")
    return out_states, out_lo, out_hi, out_src, is_new.sum()


def compact_flags(flags, is_new):
    """Compact a per-lane flag column with the SAME positions compact_new
    assigns its rows, so flag i annotates compacted row i (used for the
    tiered store's suspect bits)."""
    M = flags.shape[0]
    pos_all = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    pos = jnp.where(is_new, pos_all, M)
    return jnp.zeros(M, dtype=bool).at[pos].set(flags, mode="drop")


def record_discovery(discovered, disc_lo, disc_hi, i, hit, lo, hi):
    """First-witness discovery recording for property bit `i` inside a traced
    search body (shared by the resident and sharded engines). Keeps the first
    hit only; cross-batch/cross-chip races are tolerated exactly as the
    reference tolerates discovery-insertion races (ref: src/checker/bfs.rs:243).
    """
    bit = jnp.uint32(1 << i)
    already = (discovered & bit) != 0
    any_hit = jnp.any(hit)
    first = jnp.argmax(hit)
    record = (~already) & any_hit
    disc_lo = disc_lo.at[i].set(jnp.where(record, lo[first], disc_lo[i]))
    disc_hi = disc_hi.at[i].set(jnp.where(record, hi[first], disc_hi[i]))
    discovered = jnp.where(record, discovered | bit, discovered)
    return discovered, disc_lo, disc_hi


class _Replay:
    """The compiled programs of a path replay for one model definition, or
    for one instance of a model that names none: the fingerprints of the
    init rows, and one transition (expand a row uint32[1, L], fingerprint
    all its successors). Shape-stable, so one compiled transition serves
    every path of any length.

    The calling model is a static argument (`_Static`), so a trace reads
    the model of the call that traces and no program keeps a model alive.
    Both take the model's operand tables (lowered models, `dyn_tables`) as
    an argument and install them as `model._dyn` while tracing, as the
    resident engine's kernels do, so tables that `extend()` refilled are
    read fresh and new table shapes retrace. `max_actions` is a static
    argument of the transition: with the row and table shapes it is what
    `extend()` can change. `traces` counts the transition's traces, and
    each call's `_Static` counts those that call made."""

    def __init__(self):
        self.traces = 0

        def replay_fingerprint(static, rows, dyn):
            return _with_tables(static.ref(), dyn, state_fingerprint, rows)

        def replay_step(static, row, dyn, max_actions):
            self.traces += 1
            static.traces += 1

            def expand_fp(m, row):
                succs, valid = m.expand(row)
                return (succs[0], valid[0], *state_fingerprint(m, succs[0]))

            return _with_tables(static.ref(), dyn, expand_fp, row)

        self.fingerprint = jax.jit(replay_fingerprint, static_argnums=0)
        self.step = jax.jit(replay_step, static_argnums=(0, 3))


class _Static:
    """A model as a static argument of its `_Replay`'s programs: every
    instance compares equal (one `_Replay` serves one definition), and it
    holds its model weakly, since jit's cache keeps the first it saw. A
    trace runs with the static argument of the call that made it, so
    `traces` counts the traces of the replay that passed it, whatever
    other checks of the definition do at the same time."""

    __slots__ = ("ref", "traces")

    def __init__(self, model: TensorModel):
        self.ref = weakref.ref(model)
        self.traces = 0

    def __eq__(self, other) -> bool:
        return isinstance(other, _Static)

    def __hash__(self) -> int:
        return 0


def _with_tables(model, dyn, fn, rows):
    if not hasattr(model, "dyn_tables"):
        return fn(model, rows)
    model._dyn = dyn
    try:
        return fn(model, rows)
    finally:
        model._dyn = None


#: Replay programs of models that name no definition, by id(model) (the
#: tensor models are dataclasses, so unhashable), each dropped when its
#: model is collected. Those of named definitions are in
#: `compile_cache.REPLAYS`.
_REPLAYS: dict = {}


def _replay_programs(model: TensorModel) -> _Replay:
    key = model.definition_key()
    if key is not None:
        rp = REPLAYS.get(key)
        if rp is None:
            rp = _Replay()
            REPLAYS.put(key, rp)
        return rp
    key = id(model)
    rp = _REPLAYS.get(key)
    if rp is None:
        rp = _REPLAYS[key] = _Replay()
        weakref.finalize(model, _REPLAYS.pop, key, None)
    return rp


def replay_traces(model: TensorModel) -> int:
    """How often the replay's transition program has been traced for this
    model's definition (for this instance, if it names none)."""
    return _replay_programs(model).traces


def replay_fp_chain(model: TensorModel, chain: list) -> Path:
    """Re-execute the tensor model along a chain of packed fingerprints,
    recovering decoded states and action labels (the host checkers'
    Path.from_fingerprints technique, ref: src/checker/path.rs:20-97).
    Each transition is one call of a compiled program and one fetch."""
    return _replay_chain(model, chain, _Static(model))


def _replay_chain(model: TensorModel, chain: list, static: _Static) -> Path:
    rp = _replay_programs(model)
    dyn = model.dyn_tables() if hasattr(model, "dyn_tables") else {}
    init = np.asarray(model.init_states(), dtype=np.uint32)
    ilo, ihi = jax.device_get(rp.fingerprint(static, init, dyn))
    init_fps = pack_fp(ilo, ihi)
    rows = np.nonzero(init_fps == np.uint64(chain[0]))[0]
    if len(rows) == 0:
        # srlint: fault-ok host-side path reconstruction after the search; no recovery path exists
        raise RuntimeError(
            "failed to reconstruct init state from device fingerprint; "
            "the tensor model may be nondeterministic"
        )
    cur_row = init[rows[0]]
    pairs = []
    for next_fp in chain[1:]:
        succs, valid, slo, shi = jax.device_get(
            rp.step(static, cur_row[None], dyn, model.max_actions)
        )
        sfps = pack_fp(slo, shi)
        hits = np.nonzero(valid & (sfps == np.uint64(next_fp)))[0]
        if len(hits) == 0:
            # srlint: fault-ok host-side path reconstruction after the search; no recovery path exists
            raise RuntimeError(
                "failed to reconstruct a step from device fingerprints; "
                "the tensor model may be nondeterministic"
            )
        a = int(hits[0])
        pairs.append((model.decode(cur_row), model.action_label(cur_row, a)))
        cur_row = succs[a]
    pairs.append((model.decode(cur_row), None))
    return Path(pairs)


def map_chain(parent_map: dict, fp: int) -> list:
    """`fp` and its parents in `parent_map`, `fp` first, up to a state whose
    parent is 0 (an init state) or missing."""
    chain: list[int] = []
    cur = fp
    while cur:
        chain.append(cur)
        cur = parent_map.get(cur, 0)
    return chain


def replay_path(
    model: TensorModel, chain: list, tracer=NULL_TRACER,
    counters: Optional[dict] = None,
) -> Path:
    """Re-execute the model along `chain` (packed fingerprints, see
    tensor/fingerprint.py pack_fp; an init state's first): the TLC
    fingerprint-stack technique, ref: src/checker/bfs.rs:380-409. The
    replay is the span `path.replay`, its `steps` the transitions replayed
    (one expand of one state each) and `traced` 1 if it traced the
    transition program. `counters` (an engine's), when given, add both up
    in `replay_steps_total` and `replay_traces_total`."""
    with tracer.span("path.replay", cat="path", steps=len(chain) - 1) as span:
        static = _Static(model)
        path = _replay_chain(model, chain, static)
        traced = int(static.traces > 0)
        span.set(traced=traced)
    if counters is not None:
        counters["replay_steps_total"] += len(chain) - 1
        counters["replay_traces_total"] += traced
    return path


@dataclass
class SearchResult:
    state_count: int
    unique_state_count: int
    max_depth: int
    discoveries: dict  # name -> device fingerprint (packed int)
    complete: bool  # queue exhausted (vs early exit)
    duration: float
    steps: int = 0
    detail: Optional[dict] = None  # engine-specific (e.g. per-chip balance)


@dataclass
class _Chunk:
    states: np.ndarray  # uint32[n, L]
    lo: np.ndarray  # uint32[n]
    hi: np.ndarray  # uint32[n]
    ebits: np.ndarray  # bool[n, P]
    depth: int


class FrontierSearch:
    # Same variant names/semantics as ResidentSearch.insert_variant (the
    # host-orchestrated engine races the same visited-set designs; the
    # table layout here is always split). THE dispatch table — defined once
    # in tensor/inserts.py, aliased (never restated) here; knobs.
    # check_registry() pins the alias.
    INSERT_VARIANTS = INSERT_TABLE
    # Corpus warm ladder: the ONE kind vocabulary and the ONE preload seam
    # (store/warm.py) — aliased, never restated; knobs.check_registry()
    # pins both on every engine.
    WARM_KINDS = WARM_KINDS
    WARM_SEAM = warm_seam

    def __init__(
        self,
        model: TensorModel,
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
    ):
        """`store="tiered"` enables the two-tier state store
        (stateright_tpu/store/): when device-table occupancy crosses
        `high_water`, cold non-full buckets are evicted to a host spill
        tier and a device Bloom summary (2^summary_log2 bits) filters
        re-probes — searches whose unique-state count exceeds the table
        degrade gracefully instead of aborting. With the default
        `store="device"` behavior is byte-identical to before.

        `telemetry=True` (default) records one obs.STEP_COLS metrics row
        per device step, host-side — this engine already fetches every
        per-step scalar the row needs, so telemetry adds no device work or
        sync; the digest lands in `SearchResult.detail["telemetry"]`.
        `tracer` (obs.Tracer) records host phases (step dispatch, suspect
        resolution, eviction) as Chrome trace events."""
        self.model = model
        self.batch_size = batch_size
        if insert_variant not in self.INSERT_VARIANTS:
            raise ValueError(
                f"insert_variant must be one of "
                f"{sorted(self.INSERT_VARIANTS)}, got {insert_variant!r}"
            )
        self.insert_variant = insert_variant
        # Variant-aware handle (PallasHashTable for "pallas", so seeding
        # probes the variant's own slot layout) + the shared tiling guard —
        # both defined once in tensor/inserts.py.
        self.table = make_table(insert_variant, table_log2)
        if store not in STORE_KINDS:  # one knob universe: stateright_tpu/knobs.py
            raise ValueError(f"store must be one of {STORE_KINDS}, got {store!r}")
        self.store = store
        self._store = None
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
            # Spill trigger with one-batch headroom: a single step can claim
            # up to batch x max_actions slots, and eviction only runs
            # between steps — without the headroom a near-high-water table
            # can blow straight through to a hard insert overflow.
            ka = batch_size * model.max_actions
            self._spill_trigger = min(
                self._store.high_slots, self.table.size - ka
            )
            if self._spill_trigger <= self._store.low_slots:
                raise ValueError(
                    "table too small for tiered spilling at this batch: "
                    f"table 2^{table_log2} minus one batch of claims "
                    f"({ka}) leaves no room above the low-water mark "
                    f"({self._store.low_slots} slots); raise table_log2 or "
                    "lower batch_size/low_water"
                )
        self._hot_claims = 0  # occupied device-table slots (claims - evictions)
        self._telemetry = telemetry
        self._tm_capacity = 1 << telemetry_log2  # host row-retention window
        self._ring: Optional[StepRing] = None  # created per seed (fresh search)
        self._tracer = as_tracer(tracer)
        # Weakly registered: /metrics scrapes can see any live engine, and
        # the registry never keeps a finished search alive (obs/registry.py).
        self._metrics_name = REGISTRY.register("frontier", self.metrics)
        # Calibration comparator (obs/calib.py): joins the step times this
        # engine already measures against the costmodel prediction for this
        # exact config — host arithmetic only, observes and never steers.
        self._calib = None
        if telemetry:
            # Lazy import: obs.calib prices through tensor.costmodel, so a
            # module-level import would cycle when obs loads first.
            from ..obs.calib import CalibConfig, Comparator, calib_enabled

        if telemetry and calib_enabled():
            self._calib = Comparator(CalibConfig(
                engine="frontier",
                variant=ENGINE_VARIANTS.get(
                    ("split", insert_variant), "split"
                ),
                lanes=model.lanes,
                max_actions=model.max_actions,
                batch=batch_size,
                table_log2=table_log2,
                spill=(store == "tiered"),
            ))
            REGISTRY.register("calib", self._calib.metrics)
        # Placeholder summary operand for store="device" (the step signature
        # is uniform so both modes share one code path).
        self._no_summary = jnp.zeros(1, dtype=jnp.uint32)
        self.properties = model.properties()
        self._step = self._build_step()
        # Resumable search state (seeded lazily by run(); see _seed).
        self._q = None
        self._counts = None
        self._disc: dict = {}
        # Warm-start corpus payload (store/corpus.py; see warm_start).
        self._warm: Optional[dict] = None
        self._warm_states = 0
        self._warm_kind: Optional[str] = None  # knobs.WARM_KINDS rung served

    # -- the fused device step -------------------------------------------------

    def _build_step(self):
        model = self.model
        K = self.batch_size
        props = self.properties
        tiered = self._store is not None
        if tiered:
            s_cfg = (
                self._store.config.summary_log2,
                self._store.config.summary_hashes,
            )
        else:
            s_cfg = None
        insert = resolve_insert(self.insert_variant, summary_cfg=s_cfg)

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def step(t_lo, t_hi, p_lo, p_hi, states, lo, hi, active, summary):
            # Property masks on the input states (ref: bfs.rs:230-280).
            prop_masks = (
                jnp.stack([p.condition(model, states) for p in props])
                if props
                else jnp.zeros((0, K), dtype=bool)
            )
            # Tiered store: a fresh device claim whose fingerprint hits the
            # Bloom summary of the spilled set is a SUSPECT — possibly a
            # revisit of an evicted state (expand_insert computes the mask,
            # fused into the Pallas kernel's own partition pass when that
            # variant is selected). The host resolves suspects exactly
            # (store/host.py); a summary miss PROVES novelty, so the
            # common path never leaves the device.
            (
                t_lo, t_hi, p_lo, p_hi,
                flat, slo, shi, is_new, suspect,
                gen_rows, has_succ, ovf,
            ) = expand_insert(
                model, t_lo, t_hi, p_lo, p_hi, states, lo, hi, active,
                insert=insert,
                summary=summary if tiered else None,
                summary_cfg=s_cfg,
            )
            gen_count = gen_rows.sum()
            out_states, out_lo, out_hi, out_src, new_count = compact_new(
                flat, slo, shi, is_new
            )
            out_sus = compact_flags(suspect, is_new)
            return (
                t_lo, t_hi, p_lo, p_hi,
                out_states, out_lo, out_hi, out_src, out_sus,
                new_count, gen_count, has_succ, ovf, prop_masks,
            )

        return step

    # -- static analysis -------------------------------------------------------

    def audit_step(self):
        """(step_fn, abstract_operands, host_slots) for the jaxpr auditor
        (analysis/auditor.py): operands mirror one run() dispatch as
        ShapeDtypeStructs, so tracing touches no device data. host_slots
        are the operand indices the host re-uploads every step (this
        engine's per-step PCIe floor: the popped batch + active mask)."""
        K, L, S = self.batch_size, self.model.lanes, self.table.size
        sds = jax.ShapeDtypeStruct
        summary = (
            self._store.device_summary()
            if self._store is not None
            else self._no_summary
        )
        args = (
            sds((S,), jnp.uint32), sds((S,), jnp.uint32),
            sds((S,), jnp.uint32), sds((S,), jnp.uint32),
            sds((K, L), jnp.uint32), sds((K,), jnp.uint32),
            sds((K,), jnp.uint32), sds((K,), jnp.bool_),
            sds(summary.shape, summary.dtype),
        )
        return self._step, args, (4, 5, 6, 7)

    # -- host orchestration ----------------------------------------------------

    def _seed(self) -> None:
        """Seed the resumable search state (queue + counters + discoveries)
        held on the instance — `run()` continues where the last call left
        off, which is what makes checkpoint/resume possible."""
        model = self.model
        K = self.batch_size
        P = len(self.properties)
        eventually_i = [
            i
            for i, p in enumerate(self.properties)
            if p.expectation == Expectation.EVENTUALLY
        ]
        init, init_lo, init_hi, n_raw = seed_init(model)
        n0 = len(init)
        self._counts = dict(
            state_count=n_raw,  # host checkers count pre-dedup (bfs.rs:54)
            unique_count=0,
            max_depth=0,
            steps=0,
            early_exit=False,
        )
        self._disc = {}
        self._hot_claims = 0
        self._ring = StepRing(self._tm_capacity) if self._telemetry else None

        # Chaos-plane boundary: the seed inserts below dispatch to the
        # device and can overflow exactly like a run() step; before this
        # boundary a seeding fault was the one engine failure surface the
        # chaos plane could not reach (found by srlint SR004).
        maybe_fault("engine.step", engine="frontier", phase="seed")
        # Insert init states (chunked to batch size).
        for b0 in range(0, n0, K):
            sl = slice(b0, min(b0 + K, n0))
            n = sl.stop - sl.start
            lo_pad = np.zeros(K, dtype=np.uint32)
            hi_pad = np.zeros(K, dtype=np.uint32)
            lo_pad[:n] = init_lo[sl]
            hi_pad[:n] = init_hi[sl]
            res = self.table.insert(
                jnp.asarray(lo_pad),
                jnp.asarray(hi_pad),
                jnp.zeros(K, dtype=jnp.uint32),
                jnp.zeros(K, dtype=jnp.uint32),
                jnp.asarray(np.arange(K) < n),
            )
            if bool(res.overflow):
                raise RuntimeError("hash table full; raise table_log2")
            n_new = int(np.asarray(res.is_new).sum())
            self._counts["unique_count"] += n_new
            self._hot_claims += n_new

        ebits0 = np.zeros((n0, P), dtype=bool)
        for i in eventually_i:
            ebits0[:, i] = True
        self._q = deque()
        self._q.append(_Chunk(init, init_lo, init_hi, ebits0, depth=1))

    def warm_start(self, entry, kind: Optional[str] = None) -> int:
        """Preload a published corpus entry (store/corpus.py CorpusEntry:
        packed unsalted fps/parents + serialized Bloom summary) into the
        tiered store BEFORE the first run() — the standalone-engine half of
        the cross-job warm-start, routed through the one seam
        (store/warm.py; knobs.WARM_KINDS).

        A COMPLETE entry replays: known states dedup-filter on device from
        the very first expansion (the seeding inserts init states into the
        device table as usual; their successors hit the pre-warmed summary
        and resolve as spilled duplicates on host), the search collapses to
        the init frontier, and the result replays the publisher's
        bookkeeping bit-identically. A PARTIAL entry (corpus v2: an
        interrupted run's visited prefix + frontier snapshot) CONTINUES:
        the prefix preloads the same way, the frontier snapshot seeds the
        queue in place of the init states, counters/discoveries restore
        from the entry's meta, and run() picks up exactly where the
        publisher was cut — the completed result is bit-identical to a
        cold run and (on the service path) supersedes the partial.

        Standalone engines run unsalted, so a matching summary geometry
        takes the serialized-summary fast path (no re-hash). Call before
        run(); applies to an uninterrupted run (checkpoints do not carry
        the replay payload). The caller owns key discipline here: the
        entry must have been published for THIS model + lowering config
        (`warm.can_replay` / `warm.can_continue` are the gates), and a
        replay's run() must use the publisher's finish policy — the
        service path (service/scheduler.py) derives and checks the
        content key for you. `kind` labels the rung served, drawn from
        knobs.WARM_KINDS ("exact" when omitted; "near" for a family
        match; "delta" for a Spec-CI salvage — an entry store/warm.
        salvage_delta already re-evaluated/re-derived for an edited
        definition; partials default to "partial"). Returns the state
        count preloaded."""
        if self._store is None:
            raise ValueError(
                "warm_start requires store='tiered' (known states are "
                "dedup-filtered through the spill tier's Bloom suspect "
                "path)"
            )
        n = warm_seam.preload_store(self._store, entry)
        self._warm_states = n
        if getattr(entry, "complete", True):
            self._warm = dict(entry.meta)
            self._warm_kind = kind or "exact"
            return n
        # Partial continuation: frontier snapshot -> queue (in place of
        # _seed(); the prefix's states — init included — live in the
        # preloaded spill tier), counters/discoveries -> meta baselines.
        # No self._warm: the run accumulates real counts, never replays.
        if entry.frontier is None:
            raise ValueError(
                "partial corpus entry has no frontier snapshot (coverage-"
                "only); a continuation needs the publisher's cut frontier"
            )
        self._warm_kind = kind if kind == "delta" else "partial"
        m = entry.meta
        self._q = deque()
        for states, c_lo, c_hi, ebits, depth in warm_seam.frontier_chunks(
            entry
        ):
            self._q.append(_Chunk(states, c_lo, c_hi, ebits, depth))
        self._counts = dict(
            state_count=int(m["state_count"]),
            unique_count=int(m["unique_count"]),
            max_depth=int(m["max_depth"]),
            steps=0,
            early_exit=False,
        )
        self._disc = dict(m.get("discoveries", {}))
        self._hot_claims = 0
        self._ring = StepRing(self._tm_capacity) if self._telemetry else None
        return n

    def run(
        self,
        finish_when: HasDiscoveries = HasDiscoveries.ALL,
        target_state_count: Optional[int] = None,
        target_max_depth: Optional[int] = None,
        timeout: Optional[float] = None,
        progress: Optional[callable] = None,
        max_steps: Optional[int] = None,
    ) -> SearchResult:
        model = self.model
        K = self.batch_size
        A = model.max_actions
        P = len(self.properties)
        start = time.monotonic()
        props = self.properties
        prop_is = {
            "always": [i for i, p in enumerate(props) if p.expectation == Expectation.ALWAYS],
            "sometimes": [i for i, p in enumerate(props) if p.expectation == Expectation.SOMETIMES],
            "eventually": [i for i, p in enumerate(props) if p.expectation == Expectation.EVENTUALLY],
        }

        if self._q is None:
            self._seed()
        queue = self._q
        counts = self._counts
        discoveries = self._disc
        state_count = counts["state_count"]
        unique_count = counts["unique_count"]
        max_depth = counts["max_depth"]
        steps = counts["steps"]
        run_steps = 0

        complete = True
        while queue:
            if timeout is not None and time.monotonic() - start > timeout:
                complete = False
                break
            chunk = queue.popleft()
            # Coalesce same-depth chunks so narrow frontiers still fill the
            # batch (depths in the queue are monotonically nondecreasing).
            while queue and queue[0].depth == chunk.depth:
                nxt = queue.popleft()
                chunk = _Chunk(
                    np.concatenate([chunk.states, nxt.states]),
                    np.concatenate([chunk.lo, nxt.lo]),
                    np.concatenate([chunk.hi, nxt.hi]),
                    np.concatenate([chunk.ebits, nxt.ebits]),
                    chunk.depth,
                )
            max_depth = max(max_depth, chunk.depth)
            if target_max_depth is not None and chunk.depth >= target_max_depth:
                # Not expanded, not evaluated (ref: bfs.rs:219-224).
                continue
            n = len(chunk.states)
            for b0 in range(0, n, K):
                b1 = min(b0 + K, n)
                m = b1 - b0
                st = np.zeros((K, model.lanes), dtype=np.uint32)
                st[:m] = chunk.states[b0:b1]
                lo = np.zeros(K, dtype=np.uint32)
                lo[:m] = chunk.lo[b0:b1]
                hi = np.zeros(K, dtype=np.uint32)
                hi[:m] = chunk.hi[b0:b1]
                active = np.arange(K) < m

                # Chaos-plane boundary: simulated device OOM / XlaRuntime
                # errors land BEFORE the dispatch, so a faulted step never
                # half-updates the visited tables (faults/plan.py).
                maybe_fault("engine.step", engine="frontier", step=steps)
                t_step0 = time.monotonic()
                with self._tracer.span("frontier.step", cat="engine"):
                    (
                        t_lo, t_hi, p_lo, p_hi,
                        out_states, out_lo, out_hi, out_src, out_sus,
                        new_count, gen_count, has_succ, overflow, prop_masks,
                    ) = self._step(
                        self.table.t_lo,
                        self.table.t_hi,
                        self.table.p_lo,
                        self.table.p_hi,
                        jnp.asarray(st),
                        jnp.asarray(lo),
                        jnp.asarray(hi),
                        jnp.asarray(active),
                        self._store.device_summary()
                        if self._store is not None
                        else self._no_summary,
                    )
                    self.table.t_lo, self.table.t_hi = t_lo, t_hi
                    self.table.p_lo, self.table.p_hi = p_lo, p_hi
                    steps += 1
                    run_steps += 1
                    if bool(overflow):  # first host sync of the step
                        raise RuntimeError(
                            "hash table full; raise table_log2"
                        )
                step_us = (time.monotonic() - t_step0) * 1e6

                prop_masks = np.asarray(prop_masks)
                ebits = chunk.ebits[b0:b1]

                # Discoveries (ref: bfs.rs:230-280).
                for i in prop_is["always"]:
                    if props[i].name in discoveries:
                        continue
                    viol = active[:m] & ~prop_masks[i][:m]
                    if viol.any():
                        j = int(np.argmax(viol))
                        discoveries[props[i].name] = int(pack_fp(lo[j], hi[j]))
                for i in prop_is["sometimes"]:
                    if props[i].name in discoveries:
                        continue
                    sat = active[:m] & prop_masks[i][:m]
                    if sat.any():
                        j = int(np.argmax(sat))
                        discoveries[props[i].name] = int(pack_fp(lo[j], hi[j]))
                if prop_is["eventually"]:
                    for i in prop_is["eventually"]:
                        # Clear pending bits where observed; successors
                        # inherit the cleared bits below.
                        ebits[:, i] &= ~prop_masks[i][:m]
                    # Terminal states with pending eventually bits are
                    # counterexamples (ref: bfs.rs:326-333).
                    term = ~np.asarray(has_succ)[:m]
                    for i in prop_is["eventually"]:
                        if props[i].name in discoveries:
                            continue
                        bad = term & ebits[:, i]
                        if bad.any():
                            j = int(np.argmax(bad))
                            discoveries[props[i].name] = int(
                                pack_fp(lo[j], hi[j])
                            )

                # Early exit when every property is discovered
                # (ref: bfs.rs:278-280) or finish_when matches.
                if (props and len(discoveries) == len(props)) or (
                    finish_when.matches(props, set(discoveries))
                ):
                    if self._ring is not None:
                        # The exiting step ran but its contribution is
                        # discarded (never counted) — record it as an
                        # uncaptured step so telemetry steps == result
                        # steps while dropped_steps marks the gap.
                        self._ring.note_uncaptured()
                    complete = False
                    counts["early_exit"] = True
                    queue.clear()
                    break

                gen_i = int(gen_count)
                state_count += gen_i
                nc = int(new_count)
                claims = nc  # device slot claims this step (incl. suspects)
                sus_n = 0
                self._hot_claims += nc
                if nc:
                    out_states = np.asarray(out_states[:nc])
                    out_lo = np.asarray(out_lo[:nc])
                    out_hi = np.asarray(out_hi[:nc])
                    parent_rows = np.asarray(out_src[:nc]) // A
                    if self._store is not None:
                        sus = np.asarray(out_sus[:nc])
                        sus_n = int(sus.sum())
                        if sus.any():
                            # Exact membership check against the spill tier:
                            # confirmed duplicates of spilled states are
                            # dropped (not unique, not re-enqueued); Bloom
                            # false positives stay.
                            with self._tracer.span(
                                "tiered.suspect_resolve", cat="store",
                                suspects=sus_n,
                            ):
                                dup = self._store.resolve_suspects(
                                    out_lo[sus], out_hi[sus]
                                )
                            if dup.any():
                                keep = np.ones(nc, dtype=bool)
                                keep[np.nonzero(sus)[0][dup]] = False
                                out_states = out_states[keep]
                                out_lo = out_lo[keep]
                                out_hi = out_hi[keep]
                                parent_rows = parent_rows[keep]
                                nc = int(keep.sum())
                unique_count += nc
                if nc:
                    child_ebits = (
                        ebits[parent_rows]
                        if P
                        else np.zeros((nc, 0), dtype=bool)
                    )
                    queue.append(
                        _Chunk(
                            out_states, out_lo, out_hi, child_ebits,
                            chunk.depth + 1,
                        )
                    )
                if (
                    self._store is not None
                    and self._hot_claims >= self._spill_trigger
                ):
                    with self._tracer.span("tiered.evict", cat="store"):
                        tl, th, pl, ph, n_ev = self._store.evict(
                            self.table.t_lo, self.table.t_hi,
                            self.table.p_lo, self.table.p_hi,
                            self._hot_claims,
                        )
                    if n_ev == 0:
                        raise RuntimeError(
                            "tiered store could not free any bucket (every "
                            "bucket is full and pinned); raise table_log2 "
                            "or lower high_water"
                        )
                    self.table.t_lo, self.table.t_hi = tl, th
                    self.table.p_lo, self.table.p_hi = pl, ph
                    self._hot_claims -= n_ev
                if self._ring is not None:
                    # Every scalar here was already fetched for the counters
                    # above — telemetry adds no device work or extra sync.
                    self._ring.append(
                        active=m,
                        generated=gen_i,
                        claimed=claims,
                        queue_len=(
                            sum(len(c.lo) for c in queue) + (n - b1)
                        ),
                        table_claims=self._hot_claims,
                        suspects=sus_n,
                        depth=chunk.depth,
                        step_us=step_us,
                    )
                    if self._calib is not None:
                        # Same already-fetched scalars, joined against the
                        # costmodel prediction at chunk granularity.
                        self._calib.observe(steps, step_us, state_count)
                if (
                    target_state_count is not None
                    and state_count >= target_state_count
                ):
                    complete = False
                    counts["early_exit"] = True
                    queue.clear()
                    break
                if max_steps is not None and run_steps >= max_steps:
                    # Suspend mid-search, preserving the unprocessed rest of
                    # this chunk for resume (possibly after a checkpoint).
                    if b1 < n:
                        queue.appendleft(
                            _Chunk(
                                chunk.states[b1:],
                                chunk.lo[b1:],
                                chunk.hi[b1:],
                                chunk.ebits[b1:],
                                chunk.depth,
                            )
                        )
                    complete = False
                    break
                if progress is not None:
                    progress(state_count, unique_count, max_depth)
            else:
                continue
            break

        if (
            self._warm is not None
            and complete
            and not queue
            and not counts.get("early_exit", False)
        ):
            # Warm-start replay (store/corpus.py): the run only
            # re-expanded the init frontier (everything deeper
            # dedup-filtered against the preloaded corpus), so the result
            # bookkeeping is the publisher's — bit-identical to what this
            # search's own cold run would have produced for this content
            # key. Discoveries replay into self._disc so reconstruct_path
            # walks the preloaded spill-tier parent chains.
            w = self._warm
            state_count = w["state_count"]
            unique_count = w["unique_count"]
            max_depth = w["max_depth"]
            discoveries.clear()
            discoveries.update(w["discoveries"])
        counts["state_count"] = state_count
        counts["unique_count"] = unique_count
        counts["max_depth"] = max_depth
        counts["steps"] = steps
        detail = self._detail()
        if self._warm_kind is not None:
            detail = dict(detail or {})
            detail["corpus"] = {
                "warm_start": True,
                "preloaded_states": self._warm_states,
                "warm_kind": self._warm_kind,
            }
        return SearchResult(
            state_count=state_count,
            unique_state_count=unique_count,
            max_depth=max_depth,
            discoveries=dict(discoveries),
            # An early-exited search stays incomplete across resumed run()
            # calls and checkpoint/restore (the frontier was discarded).
            complete=complete
            and not queue
            and not counts.get("early_exit", False),
            duration=time.monotonic() - start,
            steps=steps,
            detail=detail,
        )

    def store_stats(self) -> Optional[dict]:
        """Per-tier occupancy counters (None with the plain device store) —
        surfaced in SearchResult.detail, the bench JSON, and `/.status`."""
        if self._store is None:
            return None
        return self._store.stats(self._hot_claims)

    def telemetry_summary(self) -> Optional[dict]:
        """Step-telemetry digest (obs/ring.py; None with telemetry off) —
        surfaced in SearchResult.detail["telemetry"] and `/metrics`."""
        if self._ring is None:
            return None
        return self._ring.summary(self.table.size, self.batch_size)

    def metrics(self) -> dict:
        """Flat counter snapshot for the obs registry / Prometheus export
        (host-side values only — scraping never touches the device). The
        ring's totals update per step, so a mid-search scrape sees LIVE
        steps/generated values (self._counts is only written back when
        run() returns); non-numeric leaves (the store's kind string) are
        dropped by the Prometheus renderer itself."""
        if self._ring is not None:
            out = {
                "steps": self._ring.steps,
                "generated_states": self._ring.generated_total,
                "claimed_states": self._ring.claimed_total,
            }
        else:
            out = {
                "steps": self._counts["steps"] if self._counts else 0,
                "generated_states": (
                    self._counts["state_count"] if self._counts else 0
                ),
            }
        out["table_fill"] = round(self._hot_claims / self.table.size, 4)
        stats = self.store_stats()
        if stats:
            out["store"] = stats
        return out

    def _detail(self) -> Optional[dict]:
        """SearchResult.detail under the one documented schema
        (obs/schema.py, shared assembly in obs.build_detail)."""
        detail = build_detail(self.store_stats(), self.telemetry_summary())
        if self._calib is not None:
            self._calib.finish()
        if self._calib is not None and self._calib.chunks:
            detail = dict(detail or {})
            detail["calib"] = self._calib.detail()
            self._calib.flush_records()
        return detail

    # -- checkpoint / resume ---------------------------------------------------
    # SURVEY.md §5: the reference has no partial-search checkpointing; with
    # the frontier and visited set as device arrays it is nearly free here.

    def checkpoint(self, path: str) -> None:
        """Dump the visited table, pending frontier queue, counters, and
        discoveries to `path` (.npz). Valid any time `run()` has returned —
        including after a suspension via max_steps/timeout — so an
        interrupted search can be resumed elsewhere via `load_checkpoint`.
        The write is crash-atomic (tmp+fsync+rename with a CRC32 footer,
        previous generation kept at `path + ".prev"` — faults/ckptio.py):
        a torn write can never poison resume."""
        import json

        if self._q is None:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError("nothing to checkpoint: run() has not started")
        self._tracer.instant("checkpoint", cat="engine", path=path)
        chunks = list(self._q)
        # Tiered runs serialize the spill tier alongside the device table
        # (the Bloom summary is rebuilt from the fingerprints on load).
        spill = self._store.to_checkpoint() if self._store is not None else {}
        arrays = dict(
            **spill,
            t_lo=np.asarray(self.table.t_lo),
            t_hi=np.asarray(self.table.t_hi),
            p_lo=np.asarray(self.table.p_lo),
            p_hi=np.asarray(self.table.p_hi),
            q_states=(
                np.concatenate([c.states for c in chunks])
                if chunks
                else np.zeros((0, self.model.lanes), np.uint32)
            ),
            q_lo=(
                np.concatenate([c.lo for c in chunks])
                if chunks
                else np.zeros(0, np.uint32)
            ),
            q_hi=(
                np.concatenate([c.hi for c in chunks])
                if chunks
                else np.zeros(0, np.uint32)
            ),
            q_ebits=(
                np.concatenate([c.ebits for c in chunks])
                if chunks
                else np.zeros((0, len(self.properties)), bool)
            ),
            q_lens=np.asarray([len(c.states) for c in chunks], np.int64),
            q_depths=np.asarray([c.depth for c in chunks], np.int64),
            meta=np.frombuffer(
                json.dumps(
                    {
                        "counts": self._counts,
                        "discoveries": self._disc,
                        "lanes": self.model.lanes,
                        "max_actions": self.model.max_actions,
                        "properties": [p.name for p in self.properties],
                        "table_log2": self.table.log2_size,
                        "insert_variant": self.insert_variant,
                        "hot_claims": self._hot_claims,
                        "store": (
                            self._store.meta()
                            if self._store is not None
                            else None
                        ),
                    }
                ).encode(),
                dtype=np.uint8,
            ),
        )
        fenced_savez(path, arrays)

    @classmethod
    def load_checkpoint(
        cls, model: TensorModel, path: str, batch_size: int = 1024
    ) -> "FrontierSearch":
        """Rebuild a suspended search from a `checkpoint` file; the next
        `run()` continues exactly where the dump left off. The CRC footer
        is verified; a corrupt current generation falls back to
        `path + ".prev"` instead of raising (faults/ckptio.load_latest)."""
        import json

        data, _src = load_latest(path)
        meta = json.loads(bytes(data["meta"].tobytes()).decode())
        if (meta["lanes"], meta["max_actions"]) != (
            model.lanes,
            model.max_actions,
        ):
            raise ValueError(
                "checkpoint was taken with a different model layout "
                f"(lanes/max_actions {meta['lanes']}/{meta['max_actions']} "
                f"!= {model.lanes}/{model.max_actions})"
            )
        prop_names = [p.name for p in model.properties()]
        if meta.get("properties", prop_names) != prop_names:
            # q_ebits columns and discovery bits are indexed by property
            # position; a different set/order would silently misalign them.
            raise ValueError(
                "checkpoint was taken with a different property list "
                f"({meta['properties']} != {prop_names})"
            )
        store_meta = meta.get("store")
        fs = cls(
            model,
            batch_size=batch_size,
            table_log2=meta["table_log2"],
            insert_variant=meta.get("insert_variant", "sort"),
            store="tiered" if store_meta else "device",
            **(
                {
                    "high_water": store_meta["high_water"],
                    "low_water": store_meta["low_water"],
                    "summary_log2": store_meta["summary_log2"],
                }
                if store_meta
                else {}
            ),
        )
        if store_meta:
            from ..store.tiered import TieredStore

            fs._store.close()  # replaced by the checkpointed tier
            fs._store = TieredStore.from_checkpoint(
                fs.table.size, store_meta,
                data["spill_fps"], data["spill_parents"],
            )
        fs.table.t_lo = jnp.asarray(data["t_lo"])
        fs.table.t_hi = jnp.asarray(data["t_hi"])
        fs.table.p_lo = jnp.asarray(data["p_lo"])
        fs.table.p_hi = jnp.asarray(data["p_hi"])
        fs._counts = meta["counts"]
        fs._disc = dict(meta["discoveries"])
        fs._hot_claims = int(meta.get("hot_claims", 0))
        if fs._telemetry:
            # Pre-restore steps happened in another process: count them as
            # uncaptured so the resumed digest stays honest.
            fs._ring = StepRing(fs._tm_capacity)
            fs._ring.skip_to(int(meta["counts"].get("steps", 0)))
        fs._q = deque()
        off = 0
        for ln, depth in zip(data["q_lens"], data["q_depths"]):
            ln = int(ln)
            fs._q.append(
                _Chunk(
                    data["q_states"][off : off + ln],
                    data["q_lo"][off : off + ln],
                    data["q_hi"][off : off + ln],
                    data["q_ebits"][off : off + ln],
                    int(depth),
                )
            )
            off += ln
        return fs

    # -- path reconstruction ---------------------------------------------------

    def reconstruct_path(self, fp: int) -> Path:
        parent_map = self.table.dump()
        if self._store is not None:
            # Spill entries win on keys present in both tiers: they carry
            # the ORIGINAL (BFS-discovery) parent, which keeps the walked
            # chain acyclic; a post-spill re-claim's parent can sit deeper
            # than the state itself.
            parent_map.update(self._store.parent_map())
        return replay_path(
            self.model, map_chain(parent_map, fp)[::-1], self._tracer
        )
