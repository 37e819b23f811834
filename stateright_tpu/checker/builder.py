"""Checker configuration builder (ref: src/checker.rs:65-288).

Instantiated via `Model.checker()`; fluent config then one of the `spawn_*`
methods. Beyond the reference's strategies (bfs/dfs/on_demand/simulation), this
builder adds `spawn_tpu()` — the batched device frontier checker — behind the
same `Checker` interface, the plug-in boundary BASELINE.json requires.

Memory note: consistency-tester properties (linearizability / sequential
consistency) memoize serialization verdicts in bounded process-global caches
(2^15 entries each) that retain tester histories after a run completes; a
long-lived process checking many unrelated models can call
`stateright_tpu.semantics.clear_serialization_caches()` between runs to
release them.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from ..core.discovery import HasDiscoveries
from ..core.visitor import as_visitor


class CheckerBuilder:
    def __init__(self, model):
        self.model = model
        self.symmetry_fn_: Optional[Callable] = None
        self.target_state_count_: Optional[int] = None
        self.target_max_depth_: Optional[int] = None
        self.thread_count_: int = 1
        self.visitor_ = None
        self.finish_when_: HasDiscoveries = HasDiscoveries.ALL
        self.timeout_: Optional[float] = None
        self.trace_out_: Optional[str] = None

    # -- config (fluent; ref: src/checker.rs:219-287) --------------------------

    def symmetry(self) -> "CheckerBuilder":
        """Enable symmetry reduction via the state's `representative()` method
        (ref: src/checker.rs:222-227)."""
        return self.symmetry_fn(lambda state: state.representative())

    def symmetry_fn(self, representative: Callable) -> "CheckerBuilder":
        self.symmetry_fn_ = representative
        return self

    def finish_when(self, has_discoveries: HasDiscoveries) -> "CheckerBuilder":
        self.finish_when_ = has_discoveries
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        self.target_state_count_ = count if count > 0 else None
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        self.target_max_depth_ = depth if depth > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        self.thread_count_ = max(1, thread_count)
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        self.visitor_ = as_visitor(visitor)
        return self

    def timeout(self, seconds: float) -> "CheckerBuilder":
        self.timeout_ = seconds
        return self

    def trace_out(self, path: str) -> "CheckerBuilder":
        """Record the spawned checker's host phases (dispatch, tiered-store
        servicing, checkpointing) as Chrome trace-event JSON at `path` —
        viewable in Perfetto (stateright_tpu/obs/trace.py). Honored by
        `spawn_tpu`; the host checkers ignore it."""
        self.trace_out_ = path
        return self

    @property
    def close_at(self) -> Optional[float]:
        return None if self.timeout_ is None else time.monotonic() + self.timeout_

    # -- spawn (ref: src/checker.rs:144-217) -----------------------------------

    def spawn_bfs(self):
        from .bfs import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self):
        from .dfs import DfsChecker

        return DfsChecker(self)

    def spawn_simulation(
        self, seed: int = 0, chooser=None, device: bool = False, **kwargs
    ):
        """Spawn the random-simulation checker (the fourth checker mode,
        ref: src/checker/simulation.rs). `device=False` (default) runs the
        host thread-pool walker over a host `Model`; `device=True` runs the
        vmapped device engine (tensor/simulation.py) over a `TensorModel` —
        thousands of continuously-rebatched walks per dispatch, with
        `kwargs` passing through to `DeviceSimulation` (traces, max_depth,
        dedup="trace"/"shared", table_log2, insert_variant, walks,
        stale_limit, salt, continuous, telemetry)."""
        if device:
            if chooser is not None:
                raise ValueError(
                    "chooser is a host-walker hook; the device engine "
                    "draws from counter-based jax.random streams"
                )
            from .simulation import DeviceSimulationChecker

            return DeviceSimulationChecker(self, seed=seed, **kwargs)
        from .simulation import SimulationChecker, UniformChooser

        if kwargs:
            raise TypeError(
                f"options {sorted(kwargs)} require the device engine "
                "(spawn_simulation(device=True, ...))"
            )
        return SimulationChecker(self, seed, chooser or UniformChooser())

    def spawn_on_demand(self):
        try:
            from .on_demand import OnDemandChecker
        except ImportError as e:
            raise NotImplementedError(
                "the on-demand checker has not landed yet in this build"
            ) from e
        return OnDemandChecker(self)

    def serve(self, address: str = "localhost:3000", block: bool = False):
        """Start the Explorer web service (ref: src/checker.rs:144-151)."""
        try:
            from ..explorer.server import serve
        except ImportError as e:
            raise NotImplementedError(
                "the Explorer web service has not landed yet in this build"
            ) from e
        return serve(self, address, block=block)

    def spawn_tpu(self, mode: str = "search", **kwargs):
        """Spawn a batched device (TPU) checker. The model must be a
        `stateright_tpu.tensor.TensorModel` or provide one via
        `tensor_model()`. `mode` picks the engine (knobs.CHECKER_MODES):
        "search" (default) is the exhaustive frontier checker;
        "simulation" is the device random-walk engine — equivalent to
        `spawn_simulation(device=True, **kwargs)`. `chips=N` runs the
        search on N devices (the fingerprint-sharded engine,
        parallel/sharded.py), with `batch_size` and `table_log2` per chip:
        `spawn_tpu(batch_size=8192, table_log2=23, chips=4)`."""
        from ..knobs import CHECKER_MODES

        if mode not in CHECKER_MODES:  # knob universe: knobs.py
            raise ValueError(
                f"mode must be one of {CHECKER_MODES}, got {mode!r}"
            )
        if mode == "simulation":
            return self.spawn_simulation(device=True, **kwargs)
        try:
            from .tpu import TpuChecker
        except ImportError as e:
            raise NotImplementedError(
                "the TPU frontier checker has not landed yet in this build"
            ) from e
        if self.trace_out_ is not None:
            kwargs.setdefault("trace_out", self.trace_out_)
        return TpuChecker(self, **kwargs)

    def run_supervised(
        self,
        engine: str = "resident",
        plan=None,
        config=None,
        checkpoint_path: str = None,
        **engine_kwargs,
    ):
        """Run this check under the self-healing supervisor
        (stateright_tpu/faults/): periodic atomic checkpoints, bounded
        retry with backoff, the degrade ladder, and the watchdog — with
        fault injection active when a `FaultPlan` is passed (or the
        `SR_TPU_FAULTS=` env is set). Blocking; returns the engine's
        `SearchResult` with recovery counters in `detail["faults"]`.
        Builder config (finish_when, targets) maps onto the run; the model
        must be a TensorModel, as on spawn_tpu."""
        from ..faults import run_supervised as _run_supervised
        from ..tensor.model import TensorModel

        if not isinstance(self.model, TensorModel):
            raise TypeError(
                "run_supervised requires a stateright_tpu.tensor."
                f"TensorModel; got {type(self.model).__name__}"
            )
        run_kwargs = {"finish_when": self.finish_when_}
        if self.target_state_count_ is not None:
            run_kwargs["target_state_count"] = self.target_state_count_
        if self.target_max_depth_ is not None:
            run_kwargs["target_max_depth"] = self.target_max_depth_
        return _run_supervised(
            self.model,
            engine=engine,
            plan=plan,
            config=config,
            checkpoint_path=checkpoint_path,
            engine_kwargs=engine_kwargs,
            run_kwargs=run_kwargs,
        )

    def spawn_service(self, service, priority: int = 0):
        """Submit this check as a JOB on a shared `CheckService` (the
        continuous-batching multi-job scheduler, stateright_tpu/service/)
        and return the same `Checker` handle surface `spawn_tpu` gives —
        except the device state tables are shared with every other job the
        service is running. Builder config (finish_when, targets, timeout)
        maps onto the job options; visitors/symmetry_fn are unsupported,
        as on spawn_tpu."""
        if self.visitor_ is not None:
            raise NotImplementedError(
                "visitors are not supported on service jobs; use spawn_tpu"
            )
        if self.symmetry_fn_ is not None:
            raise NotImplementedError(
                "symmetry_fn is host-level; device symmetry is the "
                "TensorModel.representative kernel (see spawn_tpu)"
            )
        handle = service.submit(
            self.model,
            finish_when=self.finish_when_,
            target_state_count=self.target_state_count_,
            target_max_depth=self.target_max_depth_,
            timeout=self.timeout_,
            priority=priority,
        )
        return handle.as_checker()
