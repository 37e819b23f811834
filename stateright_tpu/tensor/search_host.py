"""The host side of the whole-search engines, written once over a leading
shard axis.

`ResidentSearch` (tensor/resident.py) and `ShardedSearch`
(parallel/sharded.py) each keep their own traced programs. Everything they
do on the host before or after a dispatch lives here: the seed padding, the
vacuous-finish return, turning per-shard counts and discovery witnesses into
a `SearchResult`, the parent map and path rebuild, the state dump, and the
checkpoint file. The sharded engine's tables are `[N, S]` and its queues
`[N, Q, L]`; the one-chip engine is the N = 1 case, its `[S]` and `[Q, L]`
arrays viewed as `[1, S]` and `[1, Q, L]`.

The engine-neutral pieces both engines' programs and host loops share live
here too: the finish-policy masks, the abort codes, the chunking policy, the
staged compile (`Stages`), the table regrow and the queue service kernels.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..compile_cache import EXECUTABLES, ProgramCache, trace_context
from ..core.discovery import HasDiscoveries
from ..core.model import Expectation
from ..faults.ckptio import fenced_savez, load_latest, normalize_ckpt_path
from ..knobs import INSERT_VARIANTS, STORE_KINDS
from ..obs import (
    N_COLS,
    PHASES_DETAIL_KEYS,
    REGISTRY,
    STAGES_COUNTER_KEYS,
    StepRing,
    as_tracer,
)
from .costmodel import ENGINE_VARIANTS
from .fingerprint import pack_fp
from .frontier import SearchResult, map_chain, replay_path, seed_init
from .hashtable import KV_BUCKET, _insert_impl
from .inserts import check_table_log2, resolve_insert
from .parent_walk import slot_keys, walk_parents


def _finish_masks(finish_when: HasDiscoveries, props) -> tuple[int, int]:
    """Encode a HasDiscoveries policy as (required_mask, any_mask):
    stop when (discovered & required) == required != 0, or
    (discovered & any_mask) != 0."""
    name_bit = {p.name: 1 << i for i, p in enumerate(props)}
    failure_bits = sum(
        1 << i
        for i, p in enumerate(props)
        if p.expectation in (Expectation.ALWAYS, Expectation.EVENTUALLY)
    )
    all_bits = (1 << len(props)) - 1
    k = finish_when.kind
    if k == "all":
        return all_bits, 0
    if k == "any":
        return 0, all_bits
    if k == "any_failures":
        return 0, failure_bits
    if k == "all_failures":
        return failure_bits, 0
    if k == "all_of":
        return sum(name_bit[n] for n in finish_when.names), 0
    if k == "any_of":
        return 0, sum(name_bit[n] for n in finish_when.names)
    raise ValueError(f"unknown HasDiscoveries kind {k!r}")


# Abort-code bits carried in the carry's `overflow` (uint32): nonzero aborts
# the loop; the bits name the resource that actually ran out, so overflow
# recovery (checkpoint + load_checkpoint into bigger arrays) can grow the
# RIGHT one instead of guessing.
ABORT_TABLE = 1  # hash-table insert exhausted MAX_ROUNDS (table full)
ABORT_QUEUE = 2  # frontier queue tail crossed its capacity
# Non-fatal exit (tiered store only): the loop hands control back to the
# host — occupancy crossed the spill trigger, the suspect buffer is near
# capacity, or the queue tail needs compaction. The host services the
# condition (store/tiered.py) and resumes the same carry; it is never
# surfaced as an error.
EXIT_SERVICE = 4


def _abort_reason(code: int) -> str:
    parts = []
    if code & ABORT_TABLE:
        parts.append("hash table full (raise table_log2)")
    if code & ABORT_QUEUE:
        parts.append("frontier queue full (raise queue_log2)")
    return " and ".join(parts) if parts else "overflow"


def _resolve_chunking(budget, timeout, progress, carry):
    """Shared run()-entry policy for the chunked engines: validate the
    budget, decide whether this run is chunked, and default the chunk size
    (64 steps between wall-clock polls; effectively-unbounded otherwise)."""
    if budget is not None and budget <= 0:
        raise ValueError("budget must be a positive step count")
    chunked = (
        budget is not None
        or timeout is not None
        or progress is not None
        or carry is not None
    )
    if timeout is not None and budget is None:
        budget = 64  # poll granularity for wall-clock checks
    if chunked and budget is None:
        budget = 1 << 20
    return chunked, budget


class Stages:
    """A whole-search call in the stages jit runs internally, each its own
    span `<engine>.<stage>`: trace, lower, compile (a backend compile or a
    load from the persistent cache), then execute. Always timed (two clock
    reads a stage): `phases` holds this run's seconds (`detail["phases"]`)
    and `totals` the engine's `<stage>_seconds_total` counters, with
    `executable_cache_hits_total`/`executable_cache_misses_total` beside
    them. Shared by the resident and the sharded engines."""

    def __init__(self, engine: str, tracer):
        self.engine = engine
        self.tracer = tracer
        self.phases = dict.fromkeys(PHASES_DETAIL_KEYS, 0.0)
        self.totals = {
            k: 0.0 if k.endswith("_seconds_total") else 0
            for k in STAGES_COUNTER_KEYS
        }
        self._executables = ProgramCache(size=None)

    def new_run(self) -> None:
        self.phases = dict.fromkeys(PHASES_DETAIL_KEYS, 0.0)

    @contextlib.contextmanager
    def stage(self, stage: str):
        t0 = time.monotonic()
        try:
            with self.tracer.span(f"{self.engine}.{stage}", cat="engine"):
                yield
        finally:
            dt = time.monotonic() - t0
            self.phases[stage + "_s"] += dt
            self.totals[stage + "_seconds_total"] += dt

    def executable(self, jitted, args: tuple, key, definition=None):
        """`(compiled, cached)`: the compiled program of `jitted` for `args`,
        traced, lowered and compiled once per `key` (what jit's own cache
        would key on), and whether it came from a cache: the same module
        and executable as a plain call of `jitted`. With a `definition`
        (the model's definition key and everything the engine's `_build`
        reads) the program is shared by every engine of this process
        (`compile_cache.EXECUTABLES`), under the JAX settings it was traced
        with; without one it is this engine's."""
        if definition is None:
            store = self._executables
        else:
            store = EXECUTABLES
            key = (self.engine, definition, key, trace_context())
        exe = store.get(key)
        cached = exe is not None
        if cached:
            self.totals["executable_cache_hits_total"] += 1
        else:
            self.totals["executable_cache_misses_total"] += 1
            with self.stage("trace"):
                traced = jitted.trace(*args)
            with self.stage("lower"):
                lowered = traced.lower()
            with self.stage("compile"):
                exe = lowered.compile()
            store.put(key, exe)
        return exe, cached


_ins_jit = jax.jit(_insert_impl)  # one compile cache shared by every regrow
# The pallas table's slot layout is partition-relative (partition = hi mod P,
# row = hi div P — tensor/pallas_hashtable.py), so a pallas run's regrow must
# re-hash through the pallas insert itself; every XLA variant shares the
# global bucket = hi mod n_buckets layout and regrows through _insert_impl.
_ins_jit_pallas = None


def _regrow_insert(insert_variant: str):
    global _ins_jit_pallas
    if insert_variant != "pallas":
        return _ins_jit
    if _ins_jit_pallas is None:
        _ins_jit_pallas = jax.jit(resolve_insert("pallas"))
    return _ins_jit_pallas


# `.npz`-suffix normalization so `checkpoint(p)` / `load_checkpoint(..., p)`
# round-trip on the same string (now owned by the atomic checkpoint writer).
_ckpt_path = normalize_ckpt_path


def _validate_ckpt_meta(model, meta: dict) -> None:
    """Shared layout/property guards for engine checkpoints: lane widths and
    property positions index into the dumped arrays, so any mismatch would
    silently misalign them."""
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
    if meta["properties"] != prop_names:
        raise ValueError(
            "checkpoint was taken with a different property list "
            f"({meta['properties']} != {prop_names})"
        )


def _regrow(
    model, fields, old_log2: int, new_log2: int, K: int,
    queue_rows: Optional[int] = None,
    insert_variant: str = "sort",
) -> dict:
    """Re-hash a checkpointed visited table into a larger one and pad the
    frontier queue to `queue_rows` (default: the new table size — what the
    sharded engine's per-shard queues use; the resident engine passes its
    slacked capacity so the queue is padded exactly once). Queue rows live
    at [0, tail). Bucket slots depend on the table size, so growth is a
    full re-insert of every occupied slot — done on device in `K`-row
    batches."""
    S_new = 1 << new_log2
    Q_new = queue_rows if queue_rows is not None else S_new
    t_lo, t_hi = fields["t_lo"], fields["t_hi"]
    p_lo, p_hi = fields["p_lo"], fields["p_hi"]
    nz = t_lo != 0  # lo == 0 is the empty-slot sentinel (fingerprint.py)
    keys = [a[nz] for a in (t_lo, t_hi, p_lo, p_hi)]
    ins = _regrow_insert(insert_variant)
    zero = jnp.zeros(S_new, dtype=jnp.uint32)
    tl, th, pl, ph = zero, zero, zero, zero
    n = keys[0].size
    for i in range(0, max(n, 1), K):
        batch = [np.zeros(K, dtype=np.uint32) for _ in range(4)]
        m = min(K, n - i) if n else 0
        for b, k in zip(batch, keys):
            b[:m] = k[i : i + m]
        active = np.arange(K) < m
        tl, th, pl, ph, _, ovf = ins(tl, th, pl, ph, *batch, active)
        if bool(ovf):
            # srlint: fault-ok deterministic capacity wall during host-side regrow, not injectable infra
            raise RuntimeError(
                "table overflow while re-growing; raise table_log2 further"
            )
    out = {"t_lo": tl, "t_hi": th, "p_lo": pl, "p_hi": ph}
    for f in ("q_states", "q_lo", "q_hi", "q_ebits", "q_depth"):
        old = fields[f]
        grown = np.zeros((Q_new,) + old.shape[1:], dtype=old.dtype)
        keep = min(old.shape[0], Q_new)
        grown[:keep] = old[:keep]
        out[f] = grown
    # The carry's abort code is NOT touched here: a checkpointed carry sits
    # at the last sound chunk boundary (code 0), and the reason for the
    # abort that prompted the regrow travels in checkpoint meta
    # ("abort_reason"), where load_checkpoint enforces that the overflowed
    # resource actually grew.
    return out


@jax.jit
def _compact_queue(q_states, q_lo, q_hi, q_ebits, q_depth, head):
    """Shift live queue rows [head, tail) to the front (one gather per
    array) — the tiered store's answer to the append-only tail growing past
    capacity once uniques exceed the table. Static shapes: the out-of-range
    tail of the gather fills with zeros, which nothing past the new tail
    reads."""
    idx = head + jnp.arange(q_lo.shape[0], dtype=jnp.int32)
    one = lambda a: jnp.take(a, idx, mode="fill", fill_value=0)  # noqa: E731
    return (
        jnp.take(q_states, idx, axis=0, mode="fill", fill_value=0),
        one(q_lo), one(q_hi), one(q_ebits), one(q_depth),
    )


@jax.jit
def _inject_rows(
    q_states, q_lo, q_hi, q_ebits, q_depth, tail,
    b_states, b_lo, b_hi, b_eb, b_dp,
):
    """Write a host-built block of confirmed-new suspect rows at the queue
    tail (one contiguous dynamic_update_slice per array; rows past the
    caller's real count are scratch beyond the new tail). The caller
    guarantees tail + block_rows <= Q via the tiered queue slack."""
    upd2 = jax.lax.dynamic_update_slice(q_states, b_states, (tail, 0))
    one = lambda q, b: jax.lax.dynamic_update_slice(q, b, (tail,))  # noqa: E731
    return (
        upd2, one(q_lo, b_lo), one(q_hi, b_hi),
        one(q_ebits, b_eb), one(q_depth, b_dp),
    )


def to_host(x):
    """Device-to-host transfer that also works in multi-process runs.

    Single-process (all shards addressable): plain `np.asarray`. Under
    `jax.distributed.initialize()` the sharded engine's kernel outputs are
    sharded across hosts, so each process first all-gathers the shards it
    cannot address (`process_allgather(tiled=True)` reassembles the global
    array on every host). This is what lets `ShardedSearch.run()` return
    identical global `SearchResult`s on every participating process with no
    engine changes — the multi-host twin of the reference's spawn-per-host
    aggregation (ref: src/job_market.rs:149-176 is single-machine;
    cross-machine the reference has no built-in story at all).

    Accepts a pytree and gathers it with ONE `process_allgather` dispatch —
    callers batch related outputs into a single `to_host` call so
    multi-host epilogues pay one DCN round-trip, not one per array."""
    leaves = jax.tree.leaves(x)
    if any(
        isinstance(leaf, jax.Array) and not leaf.is_fully_addressable
        for leaf in leaves
    ):
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return jax.tree.map(np.asarray, x)


def summary_fields(s, n_props: int, timed_out: bool) -> dict:
    """The `SearchHost._result` arguments read from a chunk summary
    `[N, 12 + 2*max(P, 1)]`, one row per shard: columns gen_lo, gen_hi,
    unique, max_depth, discovered, head, tail, overflow, steps, stop,
    hot_claims, s_tail, then the discovery witnesses' lo and hi halves.
    The generated count and the discovery bits are global (read from shard
    0); a shard is drained when its head reached its tail."""
    P_ = max(n_props, 1)
    return dict(
        gen=int(s[0, 0]) | (int(s[0, 1]) << 32),
        unique=s[:, 2],
        max_depth=s[:, 3],
        steps=s[:, 8],
        discovered=int(s[0, 4]),
        disc_lo=s[:, 12 : 12 + P_],
        disc_hi=s[:, 12 + P_ : 12 + 2 * P_],
        complete=bool((s[:, 5] >= s[:, 6]).all()) and not timed_out,
    )


def _fit_rows(fields: dict, names, rows: int) -> None:
    """Pad (or cut) the per-shard row axis of `fields[names]` to `rows`.
    Live rows sit at the front; the callers' guards make the cut drop only
    rows past every shard's tail."""
    for f in names:
        old = fields[f]
        if old.shape[1] != rows:
            new = np.zeros((old.shape[0], rows) + old.shape[2:], old.dtype)
            keep = min(old.shape[1], rows)
            new[:, :keep] = old[:, :keep]
            fields[f] = new


_QUEUE = ("q_states", "q_lo", "q_hi", "q_ebits", "q_depth")
_SUSPECTS = ("s_states", "s_lo", "s_hi", "s_ebits", "s_depth")


class SearchHost:
    """The host side the resident and the sharded engines inherit. An
    engine calls `_init_host` from its constructor, sets `_Q` (queue rows a
    shard) when it builds its programs, and supplies what differs:
    `_seed_operands`, `_queue_rows`, `_checkpoint_arrays`,
    `_checkpoint_meta`, `_set_carry` and `store_stats`."""

    #: Shards of the visited set and queue: the one-chip engine is N = 1.
    n_chips = 1
    #: Visited-table layout ("kv" exists on the resident engine only).
    table_layout = "split"
    #: Whether the checkpoint file leads every carry array with the shard
    #: axis (and keeps one spill tier and one store record per shard).
    _CKPT_SHARD_AXIS = False
    #: The engine's carry NamedTuple.
    _carry_type = None

    def _init_host(
        self, engine: str, model, batch_size: int, table_log2: int,
        insert_variant: str, store: str, store_args: tuple,
        telemetry: bool, telemetry_log2: int, tracer, step_claims: int,
        append_tile: int,
    ) -> None:
        """The host state both engines keep; `engine` names the registry
        source, the stage spans and the calibration record.

        `store="tiered"` gives each shard a two-tier state store
        (stateright_tpu/store/): past `high_water` fill, cold non-full
        buckets spill to a host fingerprint store and a device Bloom summary
        (2^summary_log2 bits) filters re-probes; the loop exits to the host
        (EXIT_SERVICE) instead of aborting. A step claims at most
        `step_claims` slots of a shard's table and eviction only runs
        between dispatches, so the spill trigger keeps that much headroom;
        the suspect buffer holds two steps of accumulation and one of
        append slack. `telemetry` keeps a ring of 2^telemetry_log2
        obs.STEP_COLS rows a shard in the carry; `append_tile` is the rows
        of the queue append's tile (0 for the scatter append), from which
        the drain counts `append_tiles_total`."""
        self.model = model
        self.props = model.properties()
        self.batch_size = batch_size
        self.table_log2 = table_log2
        if insert_variant not in INSERT_VARIANTS:  # knob universe: knobs.py
            raise ValueError(
                f"insert_variant must be one of {INSERT_VARIANTS}, "
                f"got {insert_variant!r}"
            )
        check_table_log2(insert_variant, table_log2)  # pallas tiling guard
        self.insert_variant = insert_variant
        if store not in STORE_KINDS:  # knob universe: knobs.py
            raise ValueError(f"store must be one of {STORE_KINDS}, got {store!r}")
        self.store = store
        self._store_args = store_args
        self._spill_trigger = self._SQ = 0
        if store == "tiered":
            self._fresh_stores()
            first = self._stores[0]
            self._spill_trigger = min(
                first.high_slots, (1 << table_log2) - step_claims
            )
            if self._spill_trigger <= first.low_slots:
                raise ValueError(
                    "table too small for tiered spilling: table "
                    f"2^{table_log2} minus one step's claims ({step_claims}) "
                    "leaves no room above the low-water mark "
                    f"({first.low_slots} slots); raise table_log2 or lower "
                    "batch_size (and dest_capacity on several chips) or "
                    "low_water"
                )
            self._SQ = 3 * step_claims
        self._TMR = (1 << telemetry_log2) if telemetry else 0
        self._ring = StepRing(self._TMR, append_tile) if telemetry else None
        self._tracer = as_tracer(tracer)
        # The whole-search call in jit's stages, each timed and spanned
        # (`<engine>.trace/lower/compile/execute`), and the path counters
        # beside them (metrics()).
        self._stages = Stages(engine, self._tracer)
        self._counters = {
            "parent_map_entries": 0,
            "parent_walk_steps_total": 0,
            "parent_walk_dispatches_total": 0,
            "replay_steps_total": 0,
            "replay_traces_total": 0,
        }
        self._metrics_name = REGISTRY.register(engine, self.metrics)
        # Calibration comparator (obs/calib.py): prices one shard's step and
        # consumes the already-synced ring drains — no extra device work,
        # observes, never steers.
        self._calib = None
        if telemetry:
            # Lazy import: obs.calib prices through tensor.costmodel, so a
            # module-level import would cycle when obs loads first.
            from ..obs.calib import CalibConfig, Comparator, calib_enabled

        if telemetry and calib_enabled():
            self._calib = Comparator(CalibConfig(
                engine=engine,
                variant=ENGINE_VARIANTS.get(
                    (self.table_layout, insert_variant), "split"
                ),
                lanes=model.lanes,
                max_actions=model.max_actions,
                batch=batch_size,
                table_log2=table_log2,
                spill=(store == "tiered"),
            ))
            REGISTRY.register("calib", self._calib.metrics)
        self._last_tables = None
        self._parent_map = None
        self._seed = None
        # Suspended-search carry (chunked runs only): retained across run()
        # calls so budget/timeout suspensions and overflows are resumable.
        self._carry = None
        self._q_compacted = False
        # Warm-start corpus payload (store/warm.py; see warm_start).
        self._warm: Optional[dict] = None
        self._warm_states = 0
        self._warm_kind: Optional[str] = None  # knobs.WARM_KINDS rung served
        self._warm_summary_pending = False

    def _fresh_stores(self) -> None:
        """(Re)build the tiered stores, one per shard — a fresh search owes
        nothing to a previous run's spill tier or Bloom summary."""
        from ..store.tiered import TieredConfig, TieredStore

        for s in self._stores or ():
            s.close()  # stop the old spill tier's compactor
        high_water, low_water, summary_log2 = self._store_args
        cfg = TieredConfig(
            high_water=high_water,
            low_water=low_water,
            summary_log2=summary_log2,
        )
        self._stores = [
            TieredStore(1 << self.table_log2, cfg)
            for _ in range(self.n_chips)
        ]

    def telemetry_summary(self) -> Optional[dict]:
        """Step-telemetry digest (obs/ring.py; None with telemetry off) —
        surfaced in SearchResult.detail["telemetry"] and `/metrics`. Table
        claims drain as the MAX across shards, so the fill digest is the
        hottest shard's fill against the per-shard table size (the
        store_stats()["hot_fill"] convention); active lanes SUM across
        shards, so utilization is against the mesh-wide batch."""
        if self._ring is None:
            return None
        return self._ring.summary(
            1 << self.table_log2, self.n_chips * self.batch_size
        )

    def metrics(self) -> dict:
        """Flat counter snapshot for the obs registry / Prometheus export.
        Host-side values only (drained telemetry + store counters) — a
        scrape never syncs the device mid-search."""
        out: dict = {**self._stages.totals, **self._counters}
        if self._ring is not None:
            out.update(
                steps=self._ring.steps,
                generated_states=self._ring.generated_total,
                claimed_states=self._ring.claimed_total,
                append_tiles_total=self._ring.append_tiles_total,
            )
        stats = self.store_stats()
        if stats:
            # Non-numeric leaves (the store's kind string) are dropped by
            # the Prometheus renderer's flatten step.
            out["store"] = stats
        return out

    # -- before a dispatch -----------------------------------------------------

    def _begin_run(self) -> None:
        self._parent_map = None  # invalidate any prior reconstruction cache
        self._stages.new_run()
        if self._ring is not None and self._carry is None and self._ring.steps:
            # Fresh search (no suspended carry): telemetry starts over too.
            self._ring = self._ring.fresh()

    def _seed_rows(self) -> tuple:
        """`(operands, n0, n_raw)`: the initial states padded to one batch
        in the engine's operand form, with the deduplicated and the raw init
        counts. seed_init is deterministic per model; cached, so repeat runs
        skip the host expansion and fingerprint work."""
        if self._seed is None:
            model = self.model
            K = self.batch_size
            init, init_lo, init_hi, n_raw = seed_init(model)
            if len(init) > K:
                raise ValueError(
                    "more init states than batch_size; raise batch_size"
                )
            n0 = len(init)
            st = np.zeros((K, model.lanes), dtype=np.uint32)
            st[:n0] = init
            lo = np.zeros(K, dtype=np.uint32)
            lo[:n0] = init_lo
            hi = np.zeros(K, dtype=np.uint32)
            hi[:n0] = init_hi
            self._seed = (self._seed_operands(st, lo, hi, n0), n0, n_raw)
        return self._seed

    def _vacuous_result(
        self, finish_when, n0: int, n_raw: int, start: float
    ) -> Optional[SearchResult]:
        """The result of a vacuously-true finish policy (e.g. ALL with zero
        properties), which stops before exploring anything, matching the
        host checkers' immediate is_awaiting_discoveries early-out (ref:
        bfs.rs:278-280); None when the policy asks for a search."""
        if not (finish_when.matches(self.props, set()) or not self.props):
            return None
        S = 1 << self.table_log2
        z = np.zeros((self.n_chips, S), dtype=np.uint32)
        kv = self.table_layout == "kv"
        self._last_tables = (
            np.zeros((self.n_chips, 2 * S), np.uint32) if kv else z,
            z[:, :0] if kv else z,
            z,
            z,
        )
        return SearchResult(
            state_count=n_raw,
            unique_state_count=n0,
            max_depth=1 if n0 else 0,
            discoveries={},
            complete=False,
            duration=time.monotonic() - start,
            steps=0,
        )

    # -- after a dispatch ------------------------------------------------------

    def _drain_ring(self, tm_rows, steps: int, window_us: float) -> None:
        """Fold the telemetry ring of `steps` steps over `window_us` —
        `[TMR, C]`, or `[N, TMR, C]` a shard each — into the host digest and
        the calibration comparator. The dispatch has already synced (its
        summary fetch): this is one bulk copy, never a per-step sync."""
        if self._ring is None:
            return
        tm_rows = to_host(tm_rows)
        if tm_rows.ndim == 3:
            self._ring.drain_sharded(tm_rows, steps, window_us=window_us)
        else:
            self._ring.drain(tm_rows, steps, window_us=window_us)
        if self._calib is not None:
            self._calib.observe(
                self._ring.steps, window_us, self._ring.generated_total
            )

    def _result(
        self, start: float, chunked: bool, *, gen: int, unique, max_depth,
        steps, discovered: int, disc_lo, disc_hi, complete: bool,
        extra: Optional[dict] = None,
    ) -> SearchResult:
        """The `SearchResult` of per-shard end counts: `gen` is the global
        generated count, `unique`/`max_depth`/`steps` are `[N]` (summed,
        maxed, maxed), `discovered` the global discovery bits and
        `disc_lo`/`disc_hi` `[N, P]` witnesses, of which each property
        reports its first nonzero one (its bit is set on every shard, its
        witness only on the shards that saw it). `extra` leads `detail`."""
        discoveries = {}
        for i, p in enumerate(self.props):
            if discovered & (1 << i):
                witnesses = pack_fp(
                    np.asarray(disc_lo)[:, i].astype(np.uint32),
                    np.asarray(disc_hi)[:, i].astype(np.uint32),
                )
                discoveries[p.name] = int(witnesses[witnesses != 0][0])
        state_count = gen
        unique_total = int(np.asarray(unique).sum())
        depth = int(np.asarray(max_depth).max())
        if self._warm is not None and complete:
            # Complete-entry replay (store/warm.py): the run only re-expanded
            # the init frontier (everything deeper dedup-filtered against
            # the preloaded corpus through the Bloom suspect path), so the
            # result bookkeeping is the publisher's — bit-identical to this
            # engine's own cold run for this content key.
            m = self._warm
            state_count = int(m.get("state_count", state_count))
            unique_total = int(m.get("unique_count", unique_total))
            depth = int(m.get("max_depth", depth))
            discoveries = {
                k: int(v) for k, v in m.get("discoveries", {}).items()
            }
        return SearchResult(
            state_count=state_count,
            unique_state_count=unique_total,
            max_depth=depth,
            discoveries=discoveries,
            complete=complete,
            duration=time.monotonic() - start,
            steps=int(np.asarray(steps).max()),
            detail=self._detail(chunked, extra),
        )

    def _detail(self, chunked: bool, extra: Optional[dict]) -> Optional[dict]:
        """SearchResult.detail under the one documented schema
        (obs/schema.py): the tier counters at the top level, the telemetry
        digest, the calibration record (flushed here, at the run's end),
        the whole-search stages' seconds and the warm-start rung; None when
        there is nothing to report."""
        detail = dict(extra or {})
        detail.update(self.store_stats() or {})
        if self._ring is not None:
            detail["telemetry"] = self.telemetry_summary()
        if self._calib is not None:
            self._calib.finish()
            if self._calib.chunks:
                detail["calib"] = self._calib.detail()
                self._calib.flush_records()
        if not chunked:
            detail["phases"] = dict(self._stages.phases)
        if self._warm_kind is not None:
            detail["corpus"] = {
                "warm_start": True,
                "preloaded_states": self._warm_states,
                "warm_kind": self._warm_kind,
            }
        return detail or None

    # -- paths -----------------------------------------------------------------

    def _carry_tables(self) -> tuple:
        """The retained carry's visited set (keys and parents): the table
        snapshot a chunked run leaves for the parent map."""
        c = self._carry
        return c.t_lo, c.t_hi, c.p_lo, c.p_hi

    def _tables(self) -> tuple:
        if self._last_tables is None:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError(
                "no table snapshot to reconstruct from: run() has not "
                "completed since the last reset, donated resume or "
                "donated overflow"
            )
        return self._last_tables

    def _kv_bucket(self) -> int:
        """Slots a kv bucket row holds (0 for the split layout)."""
        if self.table_layout != "kv":
            return 0
        return min(KV_BUCKET, 1 << self.table_log2)

    def build_parent_map(self) -> dict:
        """{fingerprint: parent fingerprint (0 = init)} decoded from the
        last run's table snapshot, every shard's table in one transfer —
        layout-aware (split vs kv) and cached; the TPU checker's visitors
        read it, and path reconstruction where the tables span several
        processes."""
        if self._parent_map is None:
            tables = self._tables()
            with self._tracer.span("path.parent_map", cat="path") as span:
                t_lo, t_hi, p_lo, p_hi = to_host(tables)
                t_lo, t_hi = slot_keys(t_lo, t_hi, self._kv_bucket())
                p_lo, p_hi = p_lo.reshape(-1), p_hi.reshape(-1)
                nz = t_lo != 0
                keys = pack_fp(t_lo[nz], t_hi[nz])
                parents = pack_fp(p_lo[nz], p_hi[nz])
                self._parent_map = dict(zip(keys.tolist(), parents.tolist()))
                for s in self._stores or ():
                    # Spill entries win on keys present in both tiers: they
                    # carry the ORIGINAL (BFS-discovery) parent, which keeps
                    # reconstructed chains acyclic.
                    self._parent_map.update(s.parent_map())
                span.set(entries=len(self._parent_map))
            self._counters["parent_map_entries"] = len(self._parent_map)
        return self._parent_map

    def reconstruct_path(self, fp: int):
        """TLC-style reconstruction from the final table contents: `fp`'s
        parents looked up where the table lies (`tensor/parent_walk.py`,
        the span `path.parents`), then the path replayed (the logic is
        shared with the host-orchestrated engine). Tables spread over
        several processes go through `build_parent_map` instead."""
        tables = self._tables()
        with self._tracer.span("path.parents", cat="path") as span:
            if all(
                not isinstance(a, jax.Array) or a.is_fully_addressable
                for a in tables
            ):
                spill: dict = {}
                for s in self._stores or ():
                    spill.update(s.parent_map())
                chain, dispatches = walk_parents(
                    tables, fp, self._kv_bucket(), spill
                )
            else:
                chain, dispatches = map_chain(self.build_parent_map(), fp), 0
            span.set(steps=len(chain), dispatches=dispatches)
        self._counters["parent_walk_steps_total"] += len(chain)
        self._counters["parent_walk_dispatches_total"] += dispatches
        return replay_path(
            self.model, chain[::-1], self._tracer, self._counters
        )

    # -- retained carry --------------------------------------------------------

    def reset(self) -> None:
        """Drop any suspended carry so the next `run()` starts fresh."""
        self._carry = None
        self._parent_map = None
        self._last_tables = None
        self._q_compacted = False
        if self._ring is not None:
            self._ring = self._ring.fresh()  # telemetry starts over too
        if self._stores is not None:
            self._fresh_stores()  # spill tiers + Bloom summaries start empty

    def dump_states(
        self, decode: bool = True, evaluated_only: bool = False,
        raw: bool = False, start: int = 0,
    ) -> list:
        """Batched state dump: every unique state the search reached, pulled
        from the frontier queues (a queue never wraps, and each unique state
        is enqueued once, on its owner shard, so the union of every shard's
        rows [0, tail) is exactly the unique states ever enqueued). This is
        the device analogue of the reference's `StateRecorder` visitor (ref:
        src/checker/visitor.rs:75-111) — exact state-set assertions against
        device engines. Requires a chunked run (`budget=`/`timeout=`/
        `progress=`), which retains the carry.

        `evaluated_only` restricts the dump to rows the search popped
        ([0, head) per shard) — on an early exit the tail also holds
        never-evaluated frontier rows; for an exhausted run the two dumps
        coincide. (Rows cut off by target_max_depth are popped-but-
        unevaluated and still appear — the one divergence from reference
        visitor semantics.)

        `raw=True` is the bulk form, uint32[n, lanes]: refine_check's
        per-round poison scan works on millions of rows, where building
        python tuples dominated the round cost. Its `start` skips rows
        already read, so incremental callers transfer only the delta; on
        several shards a flat index into their concatenation is not stable
        across runs (when a non-last shard appends, every later shard's rows
        shift), so `start > 0` is refused there."""
        if self._carry is None:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError(
                "no retained carry to dump: run with budget=... (chunked "
                "dispatch) before dump_states()"
            )
        if self._q_compacted:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError(
                "dump_states is unavailable once the tiered store has "
                "compacted a frontier queue (rows [0, tail) no longer cover "
                "every unique state; spilled states live host-side) — use "
                "store='device' for exact state-set dumps"
            )
        if not raw:
            start = 0
        elif start and self.n_chips > 1:
            raise ValueError(
                "start > 0 is unsupported for multi-shard raw dumps "
                "(per-shard appends shift the concatenated indices)"
            )
        parts = self._queue_rows(evaluated_only, start)
        if raw:
            return parts[0] if len(parts) == 1 else np.concatenate(parts)
        if decode:
            return [self.model.decode(r) for rows in parts for r in rows]
        return [tuple(int(x) for x in r) for rows in parts for r in rows]

    # -- checkpoint / resume ---------------------------------------------------
    # SURVEY.md §5: the reference has no partial-search checkpointing; the
    # whole carry (tables + queue + counters) is a handful of device arrays,
    # so dumping it is one transfer. Only chunked runs (`run(budget=...)`)
    # keep a carry to dump.

    def checkpoint(self, path: str) -> None:
        """Dump the suspended search carry to `path` (.npz). Valid after a
        chunked `run()` has suspended (budget/timeout exhausted) or raised on
        overflow; `load_checkpoint` rebuilds the search — optionally into a
        LARGER table — and the next `run()` continues exactly."""
        if self._carry is None:
            # srlint: fault-ok caller-contract guard, not an I/O/device surface
            raise RuntimeError(
                "nothing to checkpoint: no suspended carry (run with "
                "budget=... to enable chunked dispatch)"
            )
        arrays = self._checkpoint_arrays(path)
        if arrays is None:
            return  # another process writes the file
        store_meta = None
        if self._stores is not None:
            # Spill tiers ride along; each Bloom summary is rebuilt from the
            # fingerprints on load (see store/tiered.py).
            if self._CKPT_SHARD_AXIS:
                # One pair of arrays per shard (shards spill independently,
                # so lengths differ).
                store_meta = [s.meta() for s in self._stores]
                for i, s in enumerate(self._stores):
                    for k, v in s.to_checkpoint().items():
                        arrays[f"{k}_{i}"] = v
            else:
                (store,) = self._stores
                store_meta = store.meta()
                arrays.update(store.to_checkpoint())
        arrays["meta"] = np.frombuffer(
            json.dumps(
                {
                    "lanes": self.model.lanes,
                    "max_actions": self.model.max_actions,
                    "properties": [p.name for p in self.props],
                    "table_log2": self.table_log2,
                    "batch_size": self.batch_size,
                    "insert_variant": self.insert_variant,
                    "store": store_meta,
                    "q_compacted": self._q_compacted,
                    **self._checkpoint_meta(),
                }
            ).encode(),
            dtype=np.uint8,
        )
        # Crash-atomic write (tmp+fsync+rename, CRC32 footer, previous
        # generation kept at `path + ".prev"` — faults/ckptio.py).
        fenced_savez(_ckpt_path(path), arrays)

    @staticmethod
    def _read_checkpoint(model, path: str) -> tuple:
        """`(arrays, meta)` of a checkpoint file, the meta validated against
        `model`. The CRC footer is verified; a corrupt current generation
        falls back to `path + ".prev"` instead of raising
        (faults/ckptio.load_latest)."""
        data, _src = load_latest(_ckpt_path(path))
        meta = json.loads(bytes(data["meta"].tobytes()).decode())
        _validate_ckpt_meta(model, meta)
        return data, meta

    @classmethod
    def _resume_kwargs(
        cls, meta: dict, batch_size: Optional[int], table_log2: Optional[int]
    ) -> dict:
        """Engine arguments of a resume from `meta`: the batch and table
        (never smaller than the checkpoint's), the insert design and the
        store. A run must resume on the same insert design — overflow
        recovery happens exactly on the long at-scale runs where silently
        falling back to the full-batch sort would reintroduce the cost the
        variant was chosen to avoid, and a pallas table's slot layout
        depends on it."""
        log2 = table_log2 if table_log2 is not None else meta["table_log2"]
        if log2 < meta["table_log2"]:
            raise ValueError("cannot shrink the table on resume")
        kw = dict(
            batch_size=batch_size or meta["batch_size"],
            table_log2=log2,
            insert_variant=meta.get("insert_variant", "sort"),
            store="device",
        )
        store_meta = meta.get("store")
        if store_meta:
            first = store_meta[0] if cls._CKPT_SHARD_AXIS else store_meta
            kw.update(
                store="tiered",
                high_water=first["high_water"],
                low_water=first["low_water"],
                summary_log2=first["summary_log2"],
            )
        return kw

    def _restore(self, data, meta: dict) -> dict:
        """The checkpointed carry as per-shard host arrays fitted to this
        engine (built by `_resume_kwargs`): the spill tiers rebuilt, fields
        missing from pre-tiered and pre-obs files defaulted, each shard's
        table re-hashed into a larger one through `_regrow`, and the queues
        padded to this engine's capacity. The caller checks that its live
        frontier fits, then `_set_carry`s the result."""
        N = self.n_chips
        if self._CKPT_SHARD_AXIS:
            per_shard = np.asarray
        else:
            def per_shard(a):
                return np.asarray(a)[None]
        store_meta = meta.get("store")
        if store_meta:
            from ..store.tiered import TieredStore

            for s in self._stores:
                s.close()  # replaced by the checkpointed tiers
            metas, keys = (
                (store_meta, [f"_{i}" for i in range(N)])
                if self._CKPT_SHARD_AXIS
                else ([store_meta], [""])
            )
            self._stores = [
                TieredStore.from_checkpoint(
                    1 << self.table_log2, m,
                    data["spill_fps" + k], data["spill_parents" + k],
                )
                for m, k in zip(metas, keys)
            ]
            self._q_compacted = bool(meta.get("q_compacted", False))
        # Pre-tiered checkpoints lack the suspect-buffer/summary fields;
        # default them to this engine's (empty) shapes.
        L, SQ = self.model.lanes, self._SQ
        defaults = {
            "hot_claims": (per_shard(data["t_lo"]) != 0).sum(axis=1)
            .astype(np.int32),
            "s_states": np.zeros((N, SQ, L), np.uint32),
            "s_lo": np.zeros((N, SQ), np.uint32),
            "s_hi": np.zeros((N, SQ), np.uint32),
            "s_ebits": np.zeros((N, SQ), np.uint32),
            "s_depth": np.zeros((N, SQ), np.uint32),
            "s_tail": np.zeros(N, np.int32),
            "summary": np.zeros((N, 1), np.uint32),
            "tm_rows": np.zeros((N, self._TMR, N_COLS), np.uint32),
        }
        fields = {
            f: per_shard(data[f]) if f in data else defaults[f]
            for f in self._carry_type._fields
        }
        # Pre-abort-code checkpoints stored overflow as a bool; the carry
        # now holds a uint32 abort bitmask.
        fields["overflow"] = fields["overflow"].astype(np.uint32)
        # The telemetry ring is observability, not search state: a restore
        # with a different ring size (or a pre-obs checkpoint) just starts
        # the ring empty, with the pre-restore steps counted as uncaptured.
        if fields["tm_rows"].shape != (N, self._TMR, N_COLS):
            fields["tm_rows"] = defaults["tm_rows"]
        if self._ring is not None:
            self._ring.skip_to(int(fields["steps"].max()))
        if store_meta:
            # The summary is a pure function of each shard's spilled set —
            # always use the freshly rebuilt words (also covers regrown
            # tables).
            fields["summary"] = np.stack([s.summary_np for s in self._stores])
        if self.table_log2 != meta["table_log2"]:
            grown = [
                _regrow(
                    self.model,
                    {k: fields[k][i] for k in ("t_lo", "t_hi", "p_lo",
                                               "p_hi", *_QUEUE)},
                    meta["table_log2"], self.table_log2, self.batch_size,
                    queue_rows=self._Q,
                    insert_variant=self.insert_variant,
                )
                for i in range(N)
            ]
            for k in grown[0]:
                fields[k] = np.stack([np.asarray(g[k]) for g in grown])
            # The overflow that prompted this regrow is resolved by the
            # bigger tables; a stale code would re-abort the resumed run.
            fields["overflow"] = np.zeros(N, np.uint32)
            # Bucket residency changed wholesale; recount occupied slots
            # (the spilled set is untouched by a regrow).
            fields["hot_claims"] = (
                (fields["t_lo"] != 0).sum(axis=1).astype(np.int32)
            )
        # Normalize the queues to this engine's capacity (covers checkpoints
        # from the pre-slack format, changed batch sizes, and regrown
        # tables).
        _fit_rows(fields, _QUEUE, self._Q)
        return fields
