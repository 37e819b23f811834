"""The TPU frontier checker behind the standard `Checker` interface — the
plug-in boundary BASELINE.json requires: `TensorModel.checker().spawn_tpu()`
gives the same handle API (counts, discoveries, join, report, assertions) as
the host checkers, with the search executed as batched device kernels.
"""

from __future__ import annotations

import threading
from typing import Optional

from ..core.path import Path
from .base import Checker


class TpuChecker(Checker):
    def __init__(
        self,
        options,
        batch_size: int = 1024,
        table_log2: int = 20,
        resident: bool = None,
        trace_out: Optional[str] = None,
        chips: int = 1,
        **engine_kwargs,
    ):
        # engine_kwargs pass through to the underlying engine —
        # ResidentSearch options like table_layout ("split"/"kv"),
        # insert_variant (knobs.INSERT_VARIANTS: "sort"/"phased"/"capped"/
        # "capped-phased"/"pallas" — the last is the partitioned-VMEM
        # Pallas kernel, interpret mode on the CPU),
        # append ("scatter"/"dus"), queue_log2, donate_chunks, the
        # tiered-store knobs (store="tiered", high_water, low_water,
        # summary_log2 — stateright_tpu/store/), and the telemetry knobs
        # (telemetry=..., telemetry_log2=... — stateright_tpu/obs/) — so
        # builder-API users can reach the same design knobs the tuner
        # races. With resident=False the host-orchestrated engine accepts
        # insert_variant, the tiered-store knobs, and telemetry (it races
        # the same visited-set designs). `trace_out=<path>` records host
        # phases as Chrome trace-event JSON, each event's args carrying one
        # check id; saved when the search thread finishes and again when
        # discoveries() has built the paths (load it in Perfetto; see
        # obs/trace.py).
        #
        # `chips=N` (N > 1) runs the check on the first N devices through
        # the multi-chip engine (parallel/sharded.py ShardedSearch over
        # make_mesh(N)): the visited set and the queue split by state
        # fingerprint, each chip owning a share, the successors routed to
        # their owners by one all_to_all a step. There `batch_size` and
        # `table_log2` are PER CHIP (N x batch_size states expanded a step,
        # N x 2^table_log2 table slots in all); the handle is the same.
        # Its engine options: dest_capacity, donate_chunks, append,
        # insert_variant, the tiered-store and the telemetry knobs.
        from ..obs import Tracer, mint_trace_id
        from ..tensor.frontier import FrontierSearch
        from ..tensor.model import TensorModel
        from ..tensor.resident import ResidentSearch

        model = options.model
        if not isinstance(model, TensorModel):
            raise TypeError(
                "spawn_tpu() requires a stateright_tpu.tensor.TensorModel; "
                f"got {type(model).__name__}. Host Models run on spawn_bfs/"
                "spawn_dfs; tensor encodings of the bundled workloads live in "
                "stateright_tpu.tensor.models."
            )
        if options.symmetry_fn_ is not None:
            raise NotImplementedError(
                "the builder's symmetry_fn is a host-level callable and "
                "cannot run inside a device kernel; device symmetry "
                "reduction is expressed as the TensorModel.representative "
                "canonicalization kernel instead (see tensor/symmetry.py), "
                "which every device engine honors automatically"
            )
        self._recorder = None
        if options.visitor_ is not None:
            # Visitors run POST-SEARCH over the retained carry: a
            # StateRecorder gets the batched queue dump (every evaluated
            # state, one transfer — ref: src/checker/visitor.rs:75-111);
            # any other CheckerVisitor gets a full parent-pointer Path per
            # evaluated state, rebuilt incrementally in BFS queue order
            # (parents always precede children, so each path is its
            # parent's path plus one replayed step — batched expands, one
            # device call per parent chunk). Path building costs
            # O(states x depth) host memory/time: it serves the
            # reference's visitor use case (test-scale assertions), not
            # flagship-scale spaces. The reference calls visitors DURING
            # the search; here results are identical for recorders since
            # the search always runs to its finish policy first.
            if resident is False:
                raise NotImplementedError(
                    "visitors on spawn_tpu require the resident engine "
                    "(the default); drop resident=False"
                )
            if chips > 1:
                raise NotImplementedError(
                    "visitors on spawn_tpu require the one-chip resident "
                    "engine (the default); drop chips"
                )
            if engine_kwargs.get("store") == "tiered":
                raise NotImplementedError(
                    "visitors on spawn_tpu require the device store (the "
                    "tiered store compacts the frontier queue the visitor "
                    "dump reads); drop store='tiered'"
                )
            self._recorder = options.visitor_
        super().__init__(model)
        # The resident engine runs the whole search in one device dispatch —
        # the default. A timeout makes it run in chunked dispatches (the
        # wall clock is polled between chunks), which also feeds the live
        # counters; pass resident=False for the host-orchestrated engine's
        # finer-grained (per-device-step) progress instead.
        if resident is None:
            resident = True
        common = {"insert_variant", "store", "high_water", "low_water",
                  "summary_log2", "telemetry", "telemetry_log2"}
        if chips < 1:
            raise ValueError(f"chips must be at least 1, got {chips}")
        if chips > 1:
            import jax

            if not resident:
                raise ValueError(
                    "chips > 1 runs the multi-chip engine; it has no "
                    "host-orchestrated form (drop resident=False)"
                )
            if chips > len(jax.devices()):
                raise ValueError(
                    f"chips={chips} but only {len(jax.devices())} devices "
                    "are visible"
                )
            allowed, drop = common | {
                "dest_capacity", "donate_chunks", "append", "tracer",
            }, "chips"
        elif not resident:
            allowed, drop = common, "resident=False"
        else:
            allowed = None
        if allowed is not None and set(engine_kwargs) - allowed:
            raise ValueError(
                f"engine options {sorted(set(engine_kwargs) - allowed)} "
                f"require the resident engine (drop {drop})"
            )
        self._trace_out = trace_out
        if trace_out is not None:
            engine_kwargs["tracer"] = Tracer(
                annotate=True, args={"check": mint_trace_id()}
            )
        if chips > 1:
            from ..parallel.sharded import ShardedSearch, make_mesh

            self._search = ShardedSearch(
                model, make_mesh(chips), batch_size, table_log2,
                **engine_kwargs,
            )
        elif resident:
            self._search = ResidentSearch(
                model, batch_size, table_log2, **engine_kwargs
            )
        else:
            self._search = FrontierSearch(
                model, batch_size, table_log2, **engine_kwargs
            )
        self._options = options
        self._result = None
        self._discovery_paths = None
        self._live = {"states": 0, "unique": 0, "depth": 0}
        self._panic: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        def progress(states, unique, depth):
            self._live["states"] = states
            self._live["unique"] = unique
            self._live["depth"] = depth

        from ..tensor.frontier import FrontierSearch
        from ..tensor.resident import ResidentSearch

        kwargs = dict(
            finish_when=self._options.finish_when_,
            target_state_count=self._options.target_state_count_,
            target_max_depth=self._options.target_max_depth_,
            timeout=self._options.timeout_,
        )
        if self._options.timeout_ is not None or isinstance(
            self._search, FrontierSearch
        ):
            # Chunked/host-orchestrated runs surface live counters; a
            # single-dispatch resident or sharded run has no host
            # involvement to report from (forcing it chunked just for
            # counters would cost perf).
            kwargs["progress"] = progress
        if (
            self._recorder is not None
            and isinstance(self._search, ResidentSearch)
            and self._options.timeout_ is None
        ):
            # dump_states() needs the retained carry of a chunked run. With a
            # timeout, _resolve_chunking already picks the 64-step polling
            # budget — overriding it here would defeat the wall clock.
            kwargs.setdefault("budget", 1 << 20)
        try:
            # Chaos-plane boundary: the spawn's search thread itself (the
            # engines add their own per-dispatch points; this one exercises
            # the join()/panic surface — faults/plan.py).
            from ..faults.plan import maybe_fault

            maybe_fault(
                "checker.run", engine=type(self._search).__name__
            )
            with self._search._tracer.span("search.run", cat="checker",
                                           **self._model.trace_args()):
                self._result = self._search.run(**kwargs)
            if self._recorder is not None:
                from ..core.visitor import StateRecorder

                if isinstance(self._recorder, StateRecorder):
                    from ..core.path import Path as _Path

                    # evaluated_only: rows the search actually popped — on
                    # an early exit the queue tail also holds
                    # never-evaluated frontier rows, which the reference's
                    # visitor never sees.
                    for s in self._search.dump_states(evaluated_only=True):
                        self._recorder.visit(self._model, _Path([(s, None)]))
                else:
                    self._visit_paths()
        except BaseException as e:  # noqa: BLE001 — surfaced by join()
            self._panic = e
        finally:
            self._save_trace()

    def _save_trace(self) -> None:
        if self._trace_out is not None:
            try:
                self._search._tracer.save(self._trace_out)
            except OSError:
                pass  # tracing must never fail a finished search

    def _visit_paths(self) -> None:
        """Call the visitor with a full Path for every evaluated state.

        Paths rebuild incrementally in queue order: a child's path is its
        parent's path plus the one step that produced it, found by
        expanding each parent once (batched over chunks of unique parents)
        and matching child fingerprints against the successor table."""
        import numpy as np

        from ..core.path import Path as _Path
        from ..tensor.fingerprint import pack_fp
        from ..tensor.frontier import state_fingerprint

        import jax.numpy as jnp

        search = self._search
        c = search._carry
        if c is None:
            return  # vacuous-finish early exit: nothing was evaluated
        head = int(c.head)
        if head == 0:
            return
        rows = np.asarray(c.q_states[:head])
        fps = pack_fp(np.asarray(c.q_lo[:head]), np.asarray(c.q_hi[:head]))
        parent_of = search.build_parent_map()  # layout-aware, cached
        idx_of = {int(f): i for i, f in enumerate(fps)}
        model = self._model

        # One batched expand per chunk of unique parents; per parent, map
        # successor fingerprint -> action slot.
        action_cache: dict[int, dict[int, int]] = {}

        def succ_actions(parent_idxs: list[int]) -> None:
            batch = jnp.asarray(rows[parent_idxs])
            succs, valid = model.expand(batch)
            B, A = valid.shape
            flat = succs.reshape(B * A, model.lanes)
            # Boundary-mask exactly like the search itself
            # (frontier.expand_insert): a boundary-excluded action is not a
            # transition and must never label a path step.
            validn = (
                np.asarray(valid.reshape(-1) & model.within_boundary(flat))
                .reshape(B, A)
            )
            slo, shi = state_fingerprint(model, flat)
            sfps = pack_fp(np.asarray(slo), np.asarray(shi)).reshape(B, A)
            for j, pi in enumerate(parent_idxs):
                # First matching slot wins (reversed dict build keeps the
                # LOWEST action index on fingerprint ties), matching the
                # insert's first-writer semantics closely enough for replay.
                action_cache[pi] = {
                    int(sfps[j, a]): a
                    for a in reversed(range(A))
                    if validn[j, a]
                }

        CHUNK = 512
        need: list[int] = []
        seen_parents = set()
        for i in range(head):
            pfp = parent_of.get(int(fps[i]), 0)
            pi = idx_of.get(pfp)
            if pi is not None and pi not in seen_parents:
                seen_parents.add(pi)
                need.append(pi)
        for k in range(0, len(need), CHUNK):
            succ_actions(need[k : k + CHUNK])

        paths: list[Optional[list]] = [None] * head
        for i in range(head):
            state = model.decode(rows[i])
            pfp = parent_of.get(int(fps[i]), 0)
            pi = idx_of.get(pfp)
            if pi is None or paths[pi] is None:
                pairs = [(state, None)]
            else:
                a = action_cache[pi].get(int(fps[i]))
                label = (
                    model.action_label(rows[pi], a) if a is not None else None
                )
                parent_pairs = paths[pi]
                pairs = (
                    parent_pairs[:-1]
                    + [(parent_pairs[-1][0], label), (state, None)]
                )
            paths[i] = pairs
            if self._recorder.should_visit():
                # The visitor API's rate-limit hook: honored AFTER the path
                # list is extended (cheap) but gating the Path build + call,
                # like the host checkers (e.g. checker/bfs.py).
                self._recorder.visit(model, _Path(list(pairs)))

    # -- Checker interface -----------------------------------------------------

    def state_count(self) -> int:
        r = self._result
        return r.state_count if r is not None else self._live["states"]

    def unique_state_count(self) -> int:
        r = self._result
        return r.unique_state_count if r is not None else self._live["unique"]

    def max_depth(self) -> int:
        r = self._result
        return r.max_depth if r is not None else self._live["depth"]

    def store_stats(self) -> Optional[dict]:
        """Per-tier occupancy of the engine's state store (None unless the
        engine runs store="tiered") — surfaced in the Explorer `/.status`."""
        stats = getattr(self._search, "store_stats", None)
        return stats() if stats is not None else None

    def telemetry_summary(self) -> Optional[dict]:
        """The engine's step-telemetry digest (obs/ring.py; None with
        telemetry off) — surfaced in the Explorer `/.status`/`/metrics`."""
        t = getattr(self._search, "telemetry_summary", None)
        return t() if t is not None else None

    def table_fill(self) -> Optional[float]:
        """Visited-table fill for the WriteReporter `fill=` field: the
        tiered store's exact hot_fill when present, else live uniques over
        table slots over every chip (exact for the device store — claims
        == uniques)."""
        stats = self.store_stats()
        if stats and "hot_fill" in stats:
            return stats["hot_fill"]
        log2 = getattr(self._search, "table_log2", None)
        if log2 is None:
            log2 = self._search.table.log2_size
        slots = (1 << log2) * getattr(self._search, "n_chips", 1)
        return min(self.unique_state_count() / slots, 1.0)

    def drift_ratio(self) -> Optional[float]:
        """Measured/predicted ratio of the engine's live calibration
        comparator (obs/calib.py) for the WriteReporter `drift=` field;
        None until its first chunk closes (or with calibration off)."""
        calib = getattr(self._search, "_calib", None)
        return calib.drift_ratio() if calib is not None else None

    def discoveries(self) -> dict[str, Path]:
        if self._result is None:
            return {}
        if self._discovery_paths is None:
            # Reconstruction dumps the device table; results are immutable
            # once the search thread finishes, so build the paths once.
            with self._search._tracer.span("checker.discoveries",
                                           cat="checker"):
                self._discovery_paths = {
                    name: self._search.reconstruct_path(fp)
                    for name, fp in self._result.discoveries.items()
                }
            self._save_trace()  # the path spans come after the search's save
        return dict(self._discovery_paths)

    def join(self) -> "TpuChecker":
        self._thread.join()
        if self._panic is not None:
            raise self._panic
        return self

    def is_done(self) -> bool:
        return not self._thread.is_alive()

    def assert_discovery(self, name, actions) -> None:
        """Panics unless `actions` (a list of the model's `action_label`
        values) also constitutes a valid discovery, validated by re-executing
        the tensor model (ref: src/checker.rs:521-577)."""
        import numpy as np

        from ..core.model import Expectation

        found = self.assert_any_discovery(name)
        model = self._model
        prop = model.property_by_name(name)
        additional_info: list[str] = []

        def cond(row) -> bool:
            import jax.numpy as jnp

            return bool(
                np.asarray(prop.condition(model, jnp.asarray(row[None])))[0]
            )

        for init_row in np.asarray(model.init_states()):
            states = self._replay(init_row, actions)
            if states is None:
                continue
            if prop.expectation == Expectation.ALWAYS:
                if not cond(states[-1]):
                    return
            elif prop.expectation == Expectation.EVENTUALLY:
                liveness_satisfied = any(cond(s) for s in states)
                terminal = self._is_terminal(states[-1])
                if not liveness_satisfied and terminal:
                    return
                if liveness_satisfied:
                    additional_info.append(
                        "incorrect counterexample satisfies eventually property"
                    )
                if not terminal:
                    additional_info.append(
                        "incorrect counterexample is nonterminal"
                    )
            else:  # SOMETIMES
                if cond(states[-1]):
                    return
        extra = f" ({'; '.join(additional_info)})" if additional_info else ""
        raise AssertionError(
            f'Invalid discovery for "{name}"{extra}, but a valid one was '
            f"found. found={found.actions()!r}"
        )

    def _valid_successors(self, row):
        """(successors, mask) with boundary-excluded successors masked out —
        the engines' notion of a transition (frontier.expand_insert)."""
        import jax.numpy as jnp
        import numpy as np

        model = self._model
        succs, valid = model.expand(jnp.asarray(np.asarray(row)[None]))
        in_bounds = model.within_boundary(succs[0])
        return np.asarray(succs)[0], np.asarray(valid)[0] & np.asarray(
            in_bounds
        )

    def _replay(self, init_row, actions):
        """Re-execute the tensor model along a list of action labels;
        returns the state rows visited, or None if a label has no valid
        matching action somewhere along the way."""
        import numpy as np

        model = self._model
        cur = np.asarray(init_row, dtype=np.uint32)
        states = [cur]
        for action in actions:
            succs, valid = self._valid_successors(cur)
            nxt = None
            for a in range(model.max_actions):
                if valid[a] and model.action_label(cur, a) == action:
                    nxt = succs[a]
                    break
            if nxt is None:
                return None
            cur = nxt
            states.append(cur)
        return states

    def _is_terminal(self, row) -> bool:
        _succs, valid = self._valid_successors(row)
        return not bool(valid.any())
