"""Device simulation checker: the FOURTH first-class engine — vmapped random
root-to-terminal walks (ref: src/checker/simulation.rs:102-209), promoted from
the original lockstep-batch island to the full cross-cutting treatment the
exhaustive engines got.

Where the reference runs one walk per OS thread, here thousands of traces
advance together inside one `lax.while_loop` dispatch: per step every lane
evaluates the property masks on its current state, detects cycles, chooses
uniformly among the valid successors with a counter-based `jax.random` stream
(explicit keys — reproducible by construction, unlike the reference's FIXMEd
StdRng, ref: src/checker/simulation.rs:47,154), and steps.

Two designs beyond the original lockstep batch:

- **Continuous walk batching** (`continuous=True`, the default): when a trace
  ends (terminal / cycle / boundary / depth cap / staleness), its lane
  immediately re-seeds from a fresh fold-in key and starts a new walk within
  the SAME dispatch, bounded by the `walks` budget — lane utilization stays
  ~1 instead of collapsing to the tail walk (the r8 service's
  continuous-batching insight applied inside the walk kernel), so walks/s
  scales with the trace count. `continuous=False` reproduces the original
  one-walk-per-lane dispatch (the baseline of the lane_util A/B).
- **Shared visited table** (`dedup="shared"`; knobs.SIM_DEDUP_KINDS): the
  per-trace [T, 2^C] cycle tables are replaced by a small per-walk depth RING
  (cycles with period <= `ring` are detected; longer walks fall to the depth
  cap) plus ONE global visited table shared by every walk — the same
  tensor/inserts.py dispatch table the exhaustive engines use (capped/pallas
  variants, optionally job-salted keys via `salt=`), persisted across
  rounds — so `unique_state_count` becomes real coverage instead of aliasing
  `state_count`, and the `stale_limit` knob restarts walks stuck in
  fully-explored territory (`stale_limit` consecutive already-visited
  states ends the walk WITHOUT the eventually check, like the depth cap).
  The default `dedup="trace"` keeps exact per-walk cycle tables
  (generation-stamped so a lane restart is O(1), not a table clear) and the
  host checker's no-global-dedup accounting.

Walk-semantics parity with the host checker (same order of checks per
iteration, ref: src/checker/simulation.rs:254-397):
 depth cap -> walk ends WITHOUT the eventually check; boundary exit, cycle
 exit, and genuine terminals DO record pending eventually-bits as
 counterexamples; properties are evaluated before expansion.

Discoveries snapshot the discovering walk's fingerprint path at record time
(lane re-seeding overwrites the live path arrays, so the witness is copied
out the moment it is found); the host reconstructs a `Path` by re-executing
the model along those fingerprints, exactly like the exhaustive engines.

First-class wiring: `CheckerBuilder.spawn_simulation(device=True, ...)` /
`spawn_tpu(mode="simulation")` (checker/simulation.py DeviceSimulationChecker),
`engine.step` chaos point per round, checkpoint/resume of the rounds loop
through the ckptio plane, telemetry digest under
`SearchResult.detail["telemetry"]` (keys pinned in obs/schema.py), a
costmodel walk-step term (tensor/costmodel.py sim_step_cost), tpu_tune
traces x dedup sweep axes, and the BENCH_SIM=1 host-vs-device A/B row.
"""

from __future__ import annotations

import json
import math
import time
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.discovery import HasDiscoveries
from ..core.model import Expectation
from ..core.path import Path
from ..faults.ckptio import fenced_savez, load_latest
from ..faults.plan import maybe_fault
from ..knobs import SIM_DEDUP_KINDS, WARM_KINDS
from ..obs import REGISTRY, build_detail
from .costmodel import ENGINE_VARIANTS
from ..store import warm as warm_seam
from .fingerprint import job_salt, pack_fp, salt_fp
from .frontier import SearchResult, state_fingerprint
from .inserts import make_table, resolve_insert
from .model import TensorModel


class _Carry(NamedTuple):
    states: jnp.ndarray  # uint32[T, L] current state per lane
    done: jnp.ndarray  # bool[T] (continuous=False only; else all-False)
    ebits: jnp.ndarray  # uint32[T] pending eventually bits of the walk
    gen: jnp.ndarray  # uint32[T] walk generation (stamps cycle structures)
    restart_n: jnp.ndarray  # int32[T] walks started on this lane - 1
    # dedup="trace": exact per-walk cycle table, generation-stamped.
    v_lo: jnp.ndarray  # uint32[T, C] (dummy [1, 1] in shared mode)
    v_hi: jnp.ndarray
    v_gen: jnp.ndarray
    # dedup="shared": per-walk depth ring + the global visited table.
    ring_lo: jnp.ndarray  # uint32[T, R] (dummy [1, 1] in trace mode)
    ring_hi: jnp.ndarray
    ring_gen: jnp.ndarray
    t_lo: jnp.ndarray  # uint32[S] global table (dummy [1] in trace mode)
    t_hi: jnp.ndarray
    p_lo: jnp.ndarray
    p_hi: jnp.ndarray
    prev_lo: jnp.ndarray  # uint32[T] parent fp of the current state
    prev_hi: jnp.ndarray
    stale: jnp.ndarray  # int32[T] consecutive already-visited states
    # live walk paths + the record-time discovery snapshots.
    path_lo: jnp.ndarray  # uint32[T, D]
    path_hi: jnp.ndarray
    path_len: jnp.ndarray  # int32[T]
    disc_lo: jnp.ndarray  # uint32[Pm, D] witness path snapshot per property
    disc_hi: jnp.ndarray
    disc_len: jnp.ndarray  # int32[Pm]
    # counters
    state_count: jnp.ndarray  # int32
    unique_count: jnp.ndarray  # int32 (shared mode: fresh global claims)
    walks: jnp.ndarray  # int32 completed walks
    restarts: jnp.ndarray  # int32 lane re-seeds (walks beyond the first T)
    stale_restarts: jnp.ndarray  # int32 walks ended by the staleness knob
    dedup_hits: jnp.ndarray  # int32 walk states already in the global table
    active_sum: jnp.ndarray  # int32 sum of live lanes per step (lane_util)
    overflow_steps: jnp.ndarray  # int32 steps whose global insert overflowed
    max_depth: jnp.ndarray  # int32
    discovered: jnp.ndarray  # uint32 bitmask
    step: jnp.ndarray  # int32


class DeviceSimulation:
    """Continuous-batched random walks on device; `run()` executes one ROUND
    (up to `walks` completed walks in one dispatch) and may be called
    repeatedly — the seed advances per round, totals and the shared visited
    table persist across rounds, and `checkpoint`/`load_checkpoint` persist
    the rounds loop itself."""

    #: THE dedup-design universe — aliased from the one knob registry
    #: (stateright_tpu/knobs.py); knobs.check_registry() pins the alias.
    DEDUP_KINDS = SIM_DEDUP_KINDS
    # Warm-knob registry pins (knobs.check_registry): the kind vocabulary
    # and the mechanics both alias the ONE seam, never a local copy.
    WARM_KINDS = WARM_KINDS
    WARM_SEAM = warm_seam

    def __init__(
        self,
        model: TensorModel,
        seed: int = 0,
        traces: int = 2048,
        max_depth: int = 256,
        dedup: str = "trace",
        cycle_log2: int = 9,
        ring: int = 64,
        table_log2: int = 20,
        insert_variant: str = "capped",
        walks: Optional[int] = None,
        stale_limit: int = 0,
        salt: int = 0,
        continuous: bool = True,
        telemetry: bool = True,
    ):
        """`traces` lanes walk concurrently; one `run()` completes at least
        `walks` walks (default: `traces`). `dedup`/"shared" knobs are
        documented in the module docstring; `cycle_log2` sizes the exact
        per-walk cycle table (trace mode), `ring` the per-walk cycle ring
        and `table_log2`/`insert_variant`/`salt` the shared global table
        (shared mode). `stale_limit` > 0 restarts a walk after that many
        consecutive already-visited states (shared mode only)."""
        self.model = model
        self.seed = seed
        self.traces = traces
        self.max_depth = max_depth
        if dedup not in SIM_DEDUP_KINDS:  # knob universe: knobs.py
            raise ValueError(
                f"dedup must be one of {SIM_DEDUP_KINDS}, got {dedup!r}"
            )
        self.dedup = dedup
        self.cycle_log2 = cycle_log2
        self.ring = ring
        self.table_log2 = table_log2
        self.insert_variant = insert_variant
        self.walks = walks
        self.stale_limit = stale_limit
        self.salt = salt
        self.continuous = continuous
        self.telemetry = telemetry
        if dedup == "trace" and (1 << cycle_log2) < 2 * max_depth:
            raise ValueError(
                "per-walk cycle table must hold 2x max_depth entries; "
                "raise cycle_log2"
            )
        if stale_limit and dedup != "shared":
            raise ValueError(
                "stale_limit needs the shared visited table (dedup='shared')"
            )
        self.table = (
            make_table(insert_variant, table_log2)
            if dedup == "shared"
            else None
        )
        self.props = model.properties()
        self._kernel = self._build()
        self._rounds = 0
        self._totals = dict(
            states=0, unique=0, max_depth=0, steps=0, walks=0, restarts=0,
            stale_restarts=0, dedup_hits=0, active_sum=0, overflow_steps=0,
            duration=0.0,
        )
        self._discoveries: dict = {}  # name -> list of packed fps (the path)
        self._warm_states = 0
        self._warm_kind: Optional[str] = None
        self._metrics_name = REGISTRY.register("simulation", self.metrics)
        # Calibration comparator (obs/calib.py): one observation per run()
        # round (the engine's only sync boundary) against sim_step_cost for
        # this exact walk config — observes, never steers.
        self._calib = None
        if telemetry:
            # Lazy import: obs.calib prices through tensor.costmodel, so a
            # module-level import would cycle when obs loads first.
            from ..obs.calib import CalibConfig, Comparator, calib_enabled

        if telemetry and calib_enabled():
            self._calib = Comparator(CalibConfig(
                engine="simulation",
                variant=ENGINE_VARIANTS.get(
                    ("split", insert_variant), "capped"
                ),
                lanes=model.lanes,
                max_actions=model.max_actions,
                batch=traces,
                table_log2=table_log2,
                sim=True,
                dedup=dedup,
                cycle_log2=cycle_log2,
                ring=ring,
            ))
            REGISTRY.register("calib", self._calib.metrics)

    def warm_start(self, entry, kind: Optional[str] = None) -> int:
        """Preload the shared visited table from a published `CorpusEntry`
        (store/warm.py seam): walks re-entering the published set then
        count as `dedup_hits` instead of fresh coverage, so a warm second
        job spends its walk budget on the NEW part of the space. Any entry
        kind serves — coverage is sound whether the source run completed
        or not (`salt=` re-keys exactly as the engine's own inserts do),
        including frontier-less coverage-only entries published by
        `publish_coverage` and Spec-CI salvages (pass kind="delta";
        coverage needs no edit gate — a visited SET is sound under any
        property/boundary edit of the same geometry). Best-effort on
        table overflow. Returns states inserted."""
        if self.table is None:
            raise ValueError(
                "warm_start needs the shared visited table (dedup='shared')"
            )
        n = warm_seam.preload_table(
            self.table, entry.fps, entry.parents, salt=self.salt
        )
        self._warm_states += n
        self._warm_kind = kind or (
            "exact" if getattr(entry, "complete", True) else "partial"
        )
        return n

    def publish_coverage(self, corpus, tenant: Optional[str] = None) -> bool:
        """Publish this simulation's shared visited table as a COVERAGE-ONLY
        partial corpus entry (complete=False, no frontier) — the random-walk
        campaign's contribution to the corpus: a later campaign on the same
        model definition preloads it through `corpus.lookup_family` +
        `warm_start` and spends its walk budget on the unexplored part of
        the space. The exhaustive ladder stays safe by construction:
        `warm.can_continue` refuses frontier-less entries, the service's
        near rung never matches the simulation's batch_size=0 lowering, and
        the Spec-CI delta rung serves complete entries only. Requires
        dedup="shared"; the dumped table is UNSALTED back to canonical
        fingerprints before publish (salt_fp is an involution; the parent-0
        root sentinel survives). Returns True when the entry was written."""
        if self.table is None:
            raise ValueError(
                "publish_coverage needs the shared visited table "
                "(dedup='shared')"
            )
        from ..store.corpus import content_key, key_components

        dump = self.table.dump()
        fps = np.fromiter(dump.keys(), dtype=np.uint64, count=len(dump))
        parents = np.fromiter(
            dump.values(), dtype=np.uint64, count=len(dump)
        )
        if self.salt:
            s_lo, s_hi = job_salt(self.salt)
            lo, hi = warm_seam.split_fps(fps)
            lo, hi = salt_fp(lo, hi, s_lo, s_hi)
            fps = pack_fp(lo, hi)
            plo, phi = warm_seam.split_fps(parents)
            root = parents == 0
            plo, phi = salt_fp(plo, phi, s_lo, s_hi)
            parents = np.where(root, np.uint64(0), pack_fp(plo, phi))
        lowering = {
            "engine": "simulation",
            "dedup": self.dedup,
            "table_log2": self.table_log2,
            "insert_variant": self.insert_variant,
            # batch_size 0 / finish None: a coverage lowering can never
            # collide with (or near-match) an exhaustive engine's key.
            "batch_size": 0,
            "finish": None,
        }
        key = content_key(self.model, lowering, tenant=tenant)
        comp = key_components(self.model, lowering, tenant=tenant)
        meta = {
            "state_count": int(self._totals["states"]),
            "unique_count": int(fps.size),
            "max_depth": int(self._totals["max_depth"]),
            # Coverage only: simulation witnesses are walk paths, not the
            # exhaustive engines' first-match fingerprints — replaying
            # them from a membership preload would claim discoveries the
            # warmed run never re-verified.
            "discoveries": {},
        }
        return corpus.publish(
            key, fps, parents, meta,
            complete=False, frontier=None, components=comp,
        )

    # -- kernel ----------------------------------------------------------------

    def _build(self):
        model = self.model
        T = self.traces
        D = self.max_depth
        shared = self.dedup == "shared"
        C = 1 << self.cycle_log2
        R = self.ring
        stale_limit = self.stale_limit
        continuous = self.continuous
        props = self.props
        P = len(props)
        Pm = max(P, 1)
        always_i = [i for i, p in enumerate(props) if p.expectation == Expectation.ALWAYS]
        sometimes_i = [i for i, p in enumerate(props) if p.expectation == Expectation.SOMETIMES]
        eventually_i = [i for i, p in enumerate(props) if p.expectation == Expectation.EVENTUALLY]
        ebits0 = np.uint32(sum(1 << i for i in eventually_i))
        all_bits = jnp.uint32((1 << P) - 1)
        insert_fn = resolve_insert(self.insert_variant) if shared else None
        salt_words = job_salt(self.salt) if self.salt else None

        def record(c, i, hit, path_lo, path_hi, path_len):
            """First-witness recording for property bit `i`, SNAPSHOTTING
            the discovering lane's fingerprint path (lane re-seeding reuses
            the live path arrays, so the witness is copied out now)."""
            disc, dlo, dhi, dlen = c
            bit = jnp.uint32(1 << i)
            already = (disc & bit) != 0
            any_hit = jnp.any(hit)
            first = jnp.argmax(hit).astype(jnp.int32)
            rec = (~already) & any_hit
            dlo = dlo.at[i].set(jnp.where(rec, path_lo[first], dlo[i]))
            dhi = dhi.at[i].set(jnp.where(rec, path_hi[first], dhi[i]))
            dlen = dlen.at[i].set(jnp.where(rec, path_len[first], dlen[i]))
            return jnp.where(rec, disc | bit, disc), dlo, dhi, dlen

        def probe_insert(v_lo, v_hi, v_gen, g, lo, hi, active):
            """Per-lane linear probe of (lo, hi) in each lane's own cycle
            table, generation-stamped: slots written by a previous walk of
            the same lane (v_gen != g) count as free, so a lane restart
            costs nothing instead of an O(C) clear. Returns
            (v_lo, v_hi, v_gen, seen)."""
            idx0 = (hi % jnp.uint32(C)).astype(jnp.int32)

            def cond(s):
                _vl, _vh, _vg, _idx, resolved, _seen, n = s
                return (~jnp.all(resolved)) & (n < C)

            def body(s):
                v_lo, v_hi, v_gen, idx, resolved, seen, n = s
                cur_lo = jnp.take_along_axis(v_lo, idx[:, None], axis=1)[:, 0]
                cur_hi = jnp.take_along_axis(v_hi, idx[:, None], axis=1)[:, 0]
                cur_g = jnp.take_along_axis(v_gen, idx[:, None], axis=1)[:, 0]
                current = cur_g == g
                hit = current & (cur_lo == lo) & (cur_hi == hi)
                free = (cur_lo == 0) | ~current
                claim = (~resolved) & free
                # One fp per lane per call: no intra-lane races possible;
                # within a generation claimed slots are never freed, so the
                # linear-probe membership argument holds per walk.
                tgt = jnp.where(claim, idx, C)[:, None]
                v_lo = jnp.put_along_axis(
                    v_lo, tgt, jnp.where(claim, lo, 0)[:, None], axis=1,
                    inplace=False, mode="drop",
                )
                v_hi = jnp.put_along_axis(
                    v_hi, tgt, jnp.where(claim, hi, 0)[:, None], axis=1,
                    inplace=False, mode="drop",
                )
                v_gen = jnp.put_along_axis(
                    v_gen, tgt, jnp.where(claim, g, 0)[:, None], axis=1,
                    inplace=False, mode="drop",
                )
                seen = seen | ((~resolved) & hit)
                resolved = resolved | hit | claim
                idx = jnp.where(resolved, idx, (idx + 1) % C)
                return v_lo, v_hi, v_gen, idx, resolved, seen, n + 1

            resolved0 = ~active
            seen0 = jnp.zeros_like(active)
            v_lo, v_hi, v_gen, _i, _r, seen, _n = jax.lax.while_loop(
                cond, body,
                (v_lo, v_hi, v_gen, idx0, resolved0, seen0, jnp.int32(0)),
            )
            return v_lo, v_hi, v_gen, seen

        @partial(jax.jit, static_argnums=(4, 5))
        def simulate(
            seed, init_states, walks_target, step_cap,
            required_mask: int, any_mask: int, tables,
        ):
            n0 = init_states.shape[0]
            base_keys = jax.random.split(jax.random.key(seed), T)

            def walk_keys(restart_n):
                return jax.vmap(jax.random.fold_in)(base_keys, restart_n)

            def pick_init(wk):
                ik = jax.vmap(lambda k: jax.random.fold_in(k, 0x5EED))(wk)
                return jax.vmap(
                    lambda k: jax.random.randint(k, (), 0, n0)
                )(ik)

            req = jnp.uint32(required_mask)
            anym = jnp.uint32(any_mask)

            def body(c: _Carry) -> _Carry:
                active = ~c.done
                # Host parity order (simulation.rs:254-397): depth cap first.
                capped = active & (c.path_len >= D)
                in_bounds = model.within_boundary(c.states)
                out_b = active & ~capped & ~in_bounds
                lo, hi = state_fingerprint(model, c.states)
                live = active & ~capped & in_bounds

                # Cycle detection: exact per-walk table (trace) or the
                # per-walk depth ring (shared; period <= R cycles).
                v_lo, v_hi, v_gen = c.v_lo, c.v_hi, c.v_gen
                ring_lo, ring_hi, ring_gen = c.ring_lo, c.ring_hi, c.ring_gen
                if shared:
                    in_ring = ring_gen == c.gen[:, None]
                    seen = jnp.any(
                        in_ring
                        & (ring_lo == lo[:, None])
                        & (ring_hi == hi[:, None]),
                        axis=1,
                    )
                    rpos = jnp.where(live, c.path_len % R, R)[:, None]
                    ring_lo = jnp.put_along_axis(
                        ring_lo, rpos, lo[:, None], axis=1,
                        inplace=False, mode="drop",
                    )
                    ring_hi = jnp.put_along_axis(
                        ring_hi, rpos, hi[:, None], axis=1,
                        inplace=False, mode="drop",
                    )
                    ring_gen = jnp.put_along_axis(
                        ring_gen, rpos, c.gen[:, None], axis=1,
                        inplace=False, mode="drop",
                    )
                else:
                    v_lo, v_hi, v_gen, seen = probe_insert(
                        v_lo, v_hi, v_gen, c.gen, lo, hi, live
                    )
                looped = live & seen
                walking = live & ~seen

                # Record the fp into the walk path (also for loop breaks,
                # matching the host's fingerprint_path.append order: the fp
                # is appended BEFORE the loop check; boundary-exited walks
                # do NOT append — the host breaks first).
                ppos = jnp.where(live, c.path_len, D)
                path_lo = jnp.put_along_axis(
                    c.path_lo, ppos[:, None], lo[:, None], axis=1,
                    inplace=False, mode="drop",
                )
                path_hi = jnp.put_along_axis(
                    c.path_hi, ppos[:, None], hi[:, None], axis=1,
                    inplace=False, mode="drop",
                )
                path_len = c.path_len + live.astype(jnp.int32)

                # Shared global dedup/coverage insert (job-salted keys when
                # co-resident with other users of the table).
                t_lo, t_hi, p_lo, p_hi = c.t_lo, c.t_hi, c.p_lo, c.p_hi
                unique_count = c.unique_count
                dedup_hits = c.dedup_hits
                stale = c.stale
                overflow_steps = c.overflow_steps
                stale_out = jnp.zeros_like(walking)
                if shared:
                    if salt_words is not None:
                        key_lo, key_hi = salt_fp(lo, hi, *salt_words)
                        par_lo, par_hi = salt_fp(
                            c.prev_lo, c.prev_hi, *salt_words
                        )
                    else:
                        key_lo, key_hi = lo, hi
                        par_lo, par_hi = c.prev_lo, c.prev_hi
                    t_lo, t_hi, p_lo, p_hi, is_new, overflow = insert_fn(
                        t_lo, t_hi, p_lo, p_hi,
                        key_lo, key_hi, par_lo, par_hi, walking,
                    )
                    fresh = walking & is_new
                    unique_count = unique_count + fresh.sum(dtype=jnp.int32)
                    dedup_hits = dedup_hits + (
                        walking & ~is_new
                    ).sum(dtype=jnp.int32)
                    stale = jnp.where(
                        walking & ~is_new,
                        stale + 1,
                        jnp.where(walking, 0, stale),
                    )
                    if stale_limit:
                        stale_out = walking & (stale >= stale_limit)
                    overflow_steps = overflow_steps + overflow.astype(
                        jnp.int32
                    )

                state_count = c.state_count + walking.sum(dtype=jnp.int32)
                max_depth = jnp.maximum(c.max_depth, jnp.max(path_len))
                active_sum = c.active_sum + active.sum(dtype=jnp.int32)

                # Properties on the current state (walking lanes only).
                disc = (c.discovered, c.disc_lo, c.disc_hi, c.disc_len)
                ebits = c.ebits
                if P:
                    masks = jnp.stack(
                        [p.condition(model, c.states) for p in props]
                    )
                    for i in always_i:
                        disc = record(
                            disc, i, walking & ~masks[i],
                            path_lo, path_hi, path_len,
                        )
                    for i in sometimes_i:
                        disc = record(
                            disc, i, walking & masks[i],
                            path_lo, path_hi, path_len,
                        )
                    for i in eventually_i:
                        ebits = jnp.where(
                            walking & masks[i],
                            ebits & jnp.uint32(~(1 << i) & 0xFFFFFFFF),
                            ebits,
                        )

                # Expand and choose uniformly among valid successors.
                succs, valid = model.expand(c.states)
                vcount = valid.sum(axis=1).astype(jnp.int32)
                sk = jax.vmap(jax.random.fold_in)(
                    walk_keys(c.restart_n), jnp.broadcast_to(c.step, (T,))
                )
                r = jax.vmap(
                    lambda k, n: jax.random.randint(k, (), 0, jnp.maximum(n, 1))
                )(sk, vcount)
                pick = jnp.argmax(
                    jnp.cumsum(valid.astype(jnp.int32), axis=1)
                    == (r + 1)[:, None],
                    axis=1,
                )
                next_states = jnp.take_along_axis(
                    succs, pick[:, None, None], axis=1
                )[:, 0]
                terminal = walking & (vcount == 0)
                stepping = walking & (vcount > 0) & ~stale_out
                states = jnp.where(stepping[:, None], next_states, c.states)
                prev_lo, prev_hi = c.prev_lo, c.prev_hi
                if shared:
                    prev_lo = jnp.where(stepping, lo, jnp.uint32(0))
                    prev_hi = jnp.where(stepping, hi, jnp.uint32(0))

                # Walk endings. Terminal/loop/boundary record pending
                # eventually-bits; the depth cap and the staleness restart
                # do not (host `return` parity: the walk is cut short, not
                # known to be terminal).
                ended_record = looped | out_b | terminal
                for i in eventually_i:
                    bad = ended_record & (
                        (ebits >> jnp.uint32(i)) & 1
                    ).astype(bool)
                    disc = record(disc, i, bad, path_lo, path_hi, path_len)
                discovered, disc_lo, disc_hi, disc_len = disc
                ended_all = ended_record | capped | stale_out
                walks = c.walks + ended_all.sum(dtype=jnp.int32)
                stale_restarts = c.stale_restarts + stale_out.sum(
                    dtype=jnp.int32
                )

                done = c.done
                gen = c.gen
                restart_n = c.restart_n
                restarts = c.restarts
                if continuous:
                    # Continuous walk batching: ended lanes re-seed NOW and
                    # start a fresh walk next step — utilization stays ~1.
                    restart = ended_all
                    restarts = restarts + restart.sum(dtype=jnp.int32)
                    restart_n = c.restart_n + restart.astype(jnp.int32)
                    pick0 = pick_init(walk_keys(restart_n))
                    states = jnp.where(
                        restart[:, None], init_states[pick0], states
                    )
                    path_len = jnp.where(restart, 0, path_len)
                    ebits = jnp.where(restart, jnp.uint32(ebits0), ebits)
                    gen = c.gen + restart.astype(jnp.uint32)
                    if shared:
                        stale = jnp.where(restart, 0, stale)
                        prev_lo = jnp.where(restart, jnp.uint32(0), prev_lo)
                        prev_hi = jnp.where(restart, jnp.uint32(0), prev_hi)
                else:
                    done = c.done | ended_all

                return _Carry(
                    states=states,
                    done=done,
                    ebits=ebits,
                    gen=gen,
                    restart_n=restart_n,
                    v_lo=v_lo,
                    v_hi=v_hi,
                    v_gen=v_gen,
                    ring_lo=ring_lo,
                    ring_hi=ring_hi,
                    ring_gen=ring_gen,
                    t_lo=t_lo,
                    t_hi=t_hi,
                    p_lo=p_lo,
                    p_hi=p_hi,
                    prev_lo=prev_lo,
                    prev_hi=prev_hi,
                    stale=stale,
                    path_lo=path_lo,
                    path_hi=path_hi,
                    path_len=path_len,
                    disc_lo=disc_lo,
                    disc_hi=disc_hi,
                    disc_len=disc_len,
                    state_count=state_count,
                    unique_count=unique_count,
                    walks=walks,
                    restarts=restarts,
                    stale_restarts=stale_restarts,
                    dedup_hits=dedup_hits,
                    active_sum=active_sum,
                    overflow_steps=overflow_steps,
                    max_depth=max_depth,
                    discovered=discovered,
                    step=c.step + 1,
                )

            def cond(c: _Carry):
                all_found = (P > 0) & (c.discovered == all_bits)
                policy = (
                    (req != 0) & ((c.discovered & req) == req)
                ) | ((c.discovered & anym) != 0)
                if continuous:
                    running = c.walks < walks_target
                else:
                    running = ~jnp.all(c.done)
                return running & (~all_found) & (~policy) & (
                    c.step < step_cap
                )

            states0 = init_states[pick_init(walk_keys(jnp.zeros(T, jnp.int32)))]
            if shared:
                t_lo, t_hi, p_lo, p_hi = tables
                v_shape, r_shape, s_shape = (1, 1), (T, R), T
            else:
                t_lo = t_hi = p_lo = p_hi = jnp.zeros(1, jnp.uint32)
                v_shape, r_shape, s_shape = (T, C), (1, 1), 1
            carry = _Carry(
                states=states0,
                done=jnp.zeros(T, bool),
                ebits=jnp.full(T, jnp.uint32(ebits0)),
                gen=jnp.ones(T, jnp.uint32),
                restart_n=jnp.zeros(T, jnp.int32),
                v_lo=jnp.zeros(v_shape, jnp.uint32),
                v_hi=jnp.zeros(v_shape, jnp.uint32),
                v_gen=jnp.zeros(v_shape, jnp.uint32),
                ring_lo=jnp.zeros(r_shape, jnp.uint32),
                ring_hi=jnp.zeros(r_shape, jnp.uint32),
                ring_gen=jnp.zeros(r_shape, jnp.uint32),
                t_lo=t_lo,
                t_hi=t_hi,
                p_lo=p_lo,
                p_hi=p_hi,
                prev_lo=jnp.zeros(s_shape, jnp.uint32),
                prev_hi=jnp.zeros(s_shape, jnp.uint32),
                stale=jnp.zeros(s_shape, jnp.int32),
                path_lo=jnp.zeros((T, D), jnp.uint32),
                path_hi=jnp.zeros((T, D), jnp.uint32),
                path_len=jnp.zeros(T, jnp.int32),
                disc_lo=jnp.zeros((Pm, D), jnp.uint32),
                disc_hi=jnp.zeros((Pm, D), jnp.uint32),
                disc_len=jnp.zeros(Pm, jnp.int32),
                state_count=jnp.int32(0),
                unique_count=jnp.int32(0),
                walks=jnp.int32(0),
                restarts=jnp.int32(0),
                stale_restarts=jnp.int32(0),
                dedup_hits=jnp.int32(0),
                active_sum=jnp.int32(0),
                overflow_steps=jnp.int32(0),
                max_depth=jnp.int32(0),
                discovered=jnp.uint32(0),
                step=jnp.int32(0),
            )
            carry = jax.lax.while_loop(cond, body, carry)
            out = {
                "disc_lo": carry.disc_lo,
                "disc_hi": carry.disc_hi,
                "disc_len": carry.disc_len,
                "counters": jnp.stack(
                    [
                        carry.state_count,
                        carry.unique_count,
                        carry.max_depth,
                        carry.discovered.astype(jnp.int32),
                        carry.step,
                        carry.walks,
                        carry.restarts,
                        carry.stale_restarts,
                        carry.dedup_hits,
                        carry.active_sum,
                        carry.overflow_steps,
                    ]
                ),
            }
            if shared:
                out["table"] = (carry.t_lo, carry.t_hi, carry.p_lo, carry.p_hi)
            return out

        return simulate

    # -- host entry ------------------------------------------------------------

    def run(
        self,
        finish_when: HasDiscoveries = HasDiscoveries.ALL,
        walks: Optional[int] = None,
    ) -> SearchResult:
        from .search_host import _finish_masks

        # Chaos-plane boundary: one round = one device dispatch.
        maybe_fault(
            "engine.step", engine="simulation", round=self._rounds
        )
        start = time.monotonic()
        model = self.model
        init = np.asarray(model.init_states(), dtype=np.uint32)
        in_bounds = np.asarray(model.within_boundary(jnp.asarray(init)))
        init = init[in_bounds]
        required_mask, any_mask = _finish_masks(finish_when, self.props)
        walks_target = walks or self.walks or self.traces
        if self.continuous:
            waves = math.ceil(walks_target / self.traces) + 1
            step_cap = waves * (self.max_depth + 2)
        else:
            step_cap = self.max_depth + 2
        tables = (
            (self.table.t_lo, self.table.t_hi, self.table.p_lo,
             self.table.p_hi)
            if self.table is not None
            else ()
        )
        out = self._kernel(
            np.uint32(self.seed + self._rounds),
            jnp.asarray(init),
            np.int32(walks_target),
            np.int32(step_cap),
            required_mask,
            any_mask,
            tables,
        )
        self._rounds += 1
        if self.table is not None:
            (self.table.t_lo, self.table.t_hi,
             self.table.p_lo, self.table.p_hi) = out["table"]
        counters = np.asarray(out["counters"])
        (states, unique, max_depth, discovered, steps, walks_done, restarts,
         stale_restarts, dedup_hits, active_sum, overflow_steps) = (
            int(x) for x in counters
        )
        disc_len = np.asarray(out["disc_len"])
        disc_lo = np.asarray(out["disc_lo"])
        disc_hi = np.asarray(out["disc_hi"])
        for i, p in enumerate(self.props):
            if discovered & (1 << i) and p.name not in self._discoveries:
                ln = int(disc_len[i])
                fps = pack_fp(disc_lo[i, :ln], disc_hi[i, :ln])
                self._discoveries[p.name] = [int(f) for f in fps]

        t = self._totals
        t["states"] += states
        t["unique"] += unique
        t["max_depth"] = max(t["max_depth"], max_depth)
        t["steps"] += steps
        t["walks"] += walks_done
        t["restarts"] += restarts
        t["stale_restarts"] += stale_restarts
        t["dedup_hits"] += dedup_hits
        t["active_sum"] += active_sum
        t["overflow_steps"] += overflow_steps
        duration = time.monotonic() - start
        t["duration"] += duration
        if self._calib is not None:
            # One observation per round: cumulative walk steps vs the
            # round's wall window (cold first rounds include compile time;
            # the K-consecutive drift guard absorbs that).
            self._calib.observe(t["steps"], duration * 1e6, t["states"])
        detail = build_detail(
            {
                "corpus": {
                    "warm_start": True,
                    "preloaded_states": self._warm_states,
                    "warm_kind": self._warm_kind,
                }
            }
            if self._warm_kind is not None
            else None,
            self.telemetry_summary(),
        )
        if self._calib is not None:
            self._calib.finish()
        if self._calib is not None and self._calib.chunks:
            detail = dict(detail or {})
            detail["calib"] = self._calib.detail()
            self._calib.flush_records()
        return SearchResult(
            state_count=t["states"],
            unique_state_count=(
                t["unique"] if self.dedup == "shared" else t["states"]
            ),
            max_depth=t["max_depth"],
            discoveries={
                name: fps[-1] for name, fps in self._discoveries.items()
            },
            complete=False,  # simulation never proves exhaustion
            duration=duration,
            steps=t["steps"],
            detail=detail,
        )

    # -- observability ---------------------------------------------------------

    def telemetry_summary(self) -> Optional[dict]:
        """The walk-plane digest for `SearchResult.detail["telemetry"]`
        (keys pinned in obs/schema.py TELEMETRY_KEYS); None with telemetry
        off."""
        if not self.telemetry:
            return None
        t = self._totals
        out = {
            "steps": t["steps"],
            "generated_total": t["states"],
            "walks": t["walks"],
            "walks_per_sec": round(
                t["walks"] / max(t["duration"], 1e-9), 1
            ),
            "lane_util": round(
                t["active_sum"] / max(t["steps"] * self.traces, 1), 4
            ),
            "restarts": t["restarts"],
        }
        if self.dedup == "shared":
            out["dedup_hit_rate"] = round(
                t["dedup_hits"] / max(t["states"], 1), 4
            )
            out["stale_restarts"] = t["stale_restarts"]
        return out

    def metrics(self) -> dict:
        """The "simulation" obs-REGISTRY source (`/metrics` scrape)."""
        t = self._totals
        return {
            "rounds": self._rounds,
            "states": t["states"],
            "unique": t["unique"],
            "walks": t["walks"],
            "restarts": t["restarts"],
            "stale_restarts": t["stale_restarts"],
            "dedup_hits": t["dedup_hits"],
            "overflow_steps": t["overflow_steps"],
            "discoveries": len(self._discoveries),
        }

    def discovery_path(self, name: str) -> Path:
        """Re-execute the model along the snapshotted fingerprint path of
        the discovering walk (the host checkers' Path.from_fingerprints
        technique, ref: src/checker/path.rs:20-97)."""
        from .frontier import replay_fp_chain

        return replay_fp_chain(self.model, self._discoveries[name])

    # -- checkpoint / resume ---------------------------------------------------

    def checkpoint(self, path: str) -> None:
        """Persist the rounds loop — seed position, cumulative totals,
        discoveries, and (shared mode) the global visited table — through
        the crash-atomic ckptio plane; `load_checkpoint` continues the
        walk schedule exactly where this dump left off (same seed stream,
        same coverage table)."""
        arrays = {}
        if self.table is not None:
            arrays.update(
                t_lo=np.asarray(self.table.t_lo),
                t_hi=np.asarray(self.table.t_hi),
                p_lo=np.asarray(self.table.p_lo),
                p_hi=np.asarray(self.table.p_hi),
            )
        arrays["meta"] = np.frombuffer(
            json.dumps(
                {
                    "engine": "simulation",
                    "seed": self.seed,
                    "rounds": self._rounds,
                    "totals": self._totals,
                    "discoveries": self._discoveries,
                    "lanes": self.model.lanes,
                    "max_actions": self.model.max_actions,
                    "properties": [p.name for p in self.props],
                    "traces": self.traces,
                    "max_depth": self.max_depth,
                    "dedup": self.dedup,
                    "cycle_log2": self.cycle_log2,
                    "ring": self.ring,
                    "table_log2": self.table_log2,
                    "insert_variant": self.insert_variant,
                    "walks": self.walks,
                    "stale_limit": self.stale_limit,
                    "salt": self.salt,
                    "continuous": self.continuous,
                    "telemetry": self.telemetry,
                }
            ).encode(),
            dtype=np.uint8,
        )
        fenced_savez(path, arrays)

    @classmethod
    def load_checkpoint(
        cls, model: TensorModel, path: str
    ) -> "DeviceSimulation":
        """Rebuild a simulation from a `checkpoint` dump; the next `run()`
        continues the rounds loop (seed advance, totals, discoveries, and
        the shared coverage table) exactly where the dump left off."""
        data, _src = load_latest(path)
        meta = json.loads(bytes(data["meta"].tobytes()).decode())
        if (meta["lanes"], meta["max_actions"]) != (
            model.lanes, model.max_actions,
        ):
            raise ValueError(
                "checkpoint was taken with a different model layout "
                f"(lanes/max_actions {meta['lanes']}/{meta['max_actions']} "
                f"!= {model.lanes}/{model.max_actions})"
            )
        prop_names = [p.name for p in model.properties()]
        if meta.get("properties", prop_names) != prop_names:
            raise ValueError(
                "checkpoint was taken with a different property list "
                f"({meta['properties']} != {prop_names})"
            )
        sim = cls(
            model,
            seed=meta["seed"],
            traces=meta["traces"],
            max_depth=meta["max_depth"],
            dedup=meta["dedup"],
            cycle_log2=meta["cycle_log2"],
            ring=meta["ring"],
            table_log2=meta["table_log2"],
            insert_variant=meta["insert_variant"],
            walks=meta["walks"],
            stale_limit=meta["stale_limit"],
            salt=meta["salt"],
            continuous=meta["continuous"],
            telemetry=meta.get("telemetry", True),
        )
        sim._rounds = meta["rounds"]
        sim._totals = dict(meta["totals"])
        sim._discoveries = {
            name: [int(f) for f in fps]
            for name, fps in meta["discoveries"].items()
        }
        if sim.table is not None:
            sim.table.t_lo = jnp.asarray(data["t_lo"])
            sim.table.t_hi = jnp.asarray(data["t_hi"])
            sim.table.p_lo = jnp.asarray(data["p_lo"])
            sim.table.p_hi = jnp.asarray(data["p_hi"])
        return sim
