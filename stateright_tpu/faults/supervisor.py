"""Self-healing supervisor: retry / degrade / checkpoint recovery loops.

`run_supervised(model, ...)` wraps any of the three device engines
(frontier / resident / sharded) in the crash-only discipline (Candea & Fox,
HotOS'03): the engine is allowed — expected — to die, and recovery is
always the same move: throw the instance away, reload the last good
checkpoint generation (atomic + CRC-verified, faults/ckptio.py), and
re-drive. The run is sliced into bounded-step chunks so there is always a
recent sound boundary to checkpoint, and BFS determinism makes the final
counts/discoveries bit-identical however many times the run was cut down
mid-flight.

Recovery policy, in order:

1. **Bounded retry with backoff** — retriable faults (injected `FaultError`s,
   `RuntimeError`/XLA errors, `OSError`) trigger an exponential backoff with
   deterministic jitter, then a restore-or-restart. Non-retriable errors
   (config/programming errors) propagate immediately.
2. **Targeted regrow** — overflow aborts ("hash table or queue full") grow
   the named resource through the engines' own checkpoint+regrow machinery
   instead of burning generic retries.
3. **Degrade ladder** — repeated failures at one rung escalate:
   retry-same-config → shrink batch K → enable (or widen) the tiered store.
   The ladder never leaves the device: a run that fails there fails.
4. **Watchdog** — each slice runs under a deadline; a hang is cancelled
   (injected hang gates) or abandoned (real ones) and converted into a
   retriable `WatchdogTimeout`.
5. **Graceful drain** — SIGTERM checkpoints the current boundary and
   returns the partial result instead of dying mid-write.

Every recovery event lands in the obs counter registry (source
"supervisor"), in spans via `tracer`, and in the returned
`SearchResult.detail["faults"]` under the documented schema
(obs/schema.py: FAULTS_DETAIL_KEYS).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time
from dataclasses import dataclass
from typing import Optional

from ..core.discovery import HasDiscoveries
from ..obs import REGISTRY, as_tracer
from .ckptio import CheckpointCorrupt, latest_generation
from .plan import (
    FaultError,
    FaultPlan,
    WatchdogTimeout,
    active,
    deterministic_backoff,
)

ENGINES = ("frontier", "resident", "sharded")

#: Degrade ladder rung names, in escalation order.
RUNGS = ("retry", "shrink_batch", "tiered")


@dataclass
class SupervisorConfig:
    """Knobs for `run_supervised`. Defaults suit unattended production
    runs; tests shrink the timers to keep the suite fast."""

    max_retries: int = 8  # total fault budget before giving up
    retries_per_rung: int = 2  # consecutive failures before escalating
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_cap_s: float = 2.0
    checkpoint_every_steps: int = 512  # slice size == checkpoint cadence
    checkpoint_interval_s: float = 0.0  # min seconds between generations
    watchdog_s: Optional[float] = None  # slice deadline (None = no watchdog)
    watchdog_grace_s: float = 1.0  # wait after cancelling a hang gate
    # Extra watchdog allowance for the FIRST slice of each engine build:
    # every fresh instance recompiles its step kernels (per-instance jit
    # closures), and compile time is progress, not a hang.
    compile_grace_s: float = 300.0
    min_batch: int = 64  # shrink_batch floor
    drain_on_sigterm: bool = True
    seed: int = 0  # jitter determinism


class SupervisorGaveUp(RuntimeError):
    """The fault budget ran out; the last underlying failure is chained."""


class Supervisor:
    """One supervised run. Use `run_supervised` unless you need to poke at
    the counters mid-flight."""

    def __init__(
        self,
        model,
        engine: str = "resident",
        plan: Optional[FaultPlan] = None,
        config: Optional[SupervisorConfig] = None,
        checkpoint_path: Optional[str] = None,
        engine_kwargs: Optional[dict] = None,
        run_kwargs: Optional[dict] = None,
        tracer=None,
    ):
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        self.model = model
        self.engine = engine
        self.plan = plan if plan is not None else FaultPlan.from_env()
        self.cfg = config or SupervisorConfig()
        self.ckpt = checkpoint_path
        self.engine_kwargs = dict(engine_kwargs or {})
        self.run_kwargs = dict(run_kwargs or {})
        for k in ("budget", "max_steps", "progress"):
            if k in self.run_kwargs:
                raise ValueError(
                    f"run_kwargs[{k!r}] is owned by the supervisor "
                    "(it slices the run itself)"
                )
        self._tracer = as_tracer(tracer)
        if self.plan is not None and self.plan.tracer is None:
            self.plan.tracer = self._tracer
        # Mutable config the degrade ladder rewrites between builds.
        self._batch = self.engine_kwargs.pop("batch_size", 1024)
        self._table_log2 = self.engine_kwargs.pop("table_log2", 20)
        self._queue_log2: Optional[int] = self.engine_kwargs.pop(
            "queue_log2", None
        )
        self._grow_table = False  # pass table_log2 to the next restore
        self._grow_queue = False
        self.counters = {
            "retries": 0,
            "backoff_ms": 0,
            "degrade_steps": 0,
            "degrade_rung": 0,
            "checkpoint_generations": 0,
            "restores": 0,
            "watchdog_fired": 0,
            "drained": 0,
        }
        self._rung = 0
        self._rung_failures = 0
        self._eng_warm = False  # current engine has completed >= 1 slice
        self._sigterm = False
        self._last_ckpt_t = 0.0
        self._metrics_name = REGISTRY.register("supervisor", self.metrics)

    # -- engine lifecycle ------------------------------------------------------

    def _fresh(self):
        kw = dict(
            self.engine_kwargs,
            batch_size=self._batch,
            table_log2=self._table_log2,
        )
        if self.engine == "frontier":
            from ..tensor.frontier import FrontierSearch

            return FrontierSearch(self.model, **kw)
        if self.engine == "resident":
            from ..tensor.resident import ResidentSearch

            if self._queue_log2 is not None:
                kw["queue_log2"] = self._queue_log2
            return ResidentSearch(self.model, **kw)
        from ..parallel.sharded import ShardedSearch

        return ShardedSearch(self.model, **kw)

    def _restore(self):
        """Rebuild from the newest intact checkpoint generation, or None
        when no restore is possible (caller falls back to a fresh build)."""
        if self.ckpt is None or latest_generation(self.ckpt) is None:
            return None
        try:
            if self.engine == "frontier":
                if self._grow_table or self._grow_queue:
                    # FrontierSearch.load_checkpoint cannot resize; a grown
                    # run restarts fresh at the new size instead.
                    return None
                from ..tensor.frontier import FrontierSearch

                eng = FrontierSearch.load_checkpoint(
                    self.model, self.ckpt, batch_size=self._batch
                )
            elif self.engine == "resident":
                from ..tensor.resident import ResidentSearch

                kw: dict = {"batch_size": self._batch}
                if self._grow_table:
                    kw["table_log2"] = self._table_log2
                if self._grow_queue and self._queue_log2 is not None:
                    kw["queue_log2"] = self._queue_log2
                eng = ResidentSearch.load_checkpoint(self.model, self.ckpt, **kw)
            else:
                from ..parallel.sharded import ShardedSearch

                kw = {"batch_size": self._batch}
                if "mesh" in self.engine_kwargs:
                    kw["mesh"] = self.engine_kwargs["mesh"]
                if self._grow_table:
                    kw["table_log2"] = self._table_log2
                eng = ShardedSearch.load_checkpoint(self.model, self.ckpt, **kw)
        except CheckpointCorrupt:
            return None
        self._grow_table = self._grow_queue = False
        self.counters["restores"] += 1
        self._tracer.instant("supervisor.restore", cat="faults")
        return eng

    def _build(self):
        eng = self._restore()
        if eng is None:
            eng = self._fresh()
        return eng

    def _checkpoint(self, eng, force: bool = False) -> None:
        if self.ckpt is None:
            return
        now = time.monotonic()
        if not force and now - self._last_ckpt_t < self.cfg.checkpoint_interval_s:
            return
        try:
            with self._tracer.span("supervisor.checkpoint", cat="faults"):
                eng.checkpoint(self.ckpt)
        except RuntimeError:
            # "nothing to checkpoint" (no carry yet / vacuous finish):
            # there is no progress to protect, so nothing is lost.
            return
        self.counters["checkpoint_generations"] += 1
        self._last_ckpt_t = now

    # -- slicing ---------------------------------------------------------------

    def _engine_steps(self, eng) -> int:
        import numpy as np

        carry = getattr(eng, "_carry", None)
        if carry is None:
            return 0
        return int(np.max(np.asarray(carry.steps)))

    def _slice(self, eng):
        """Drive the engine for at most checkpoint_every_steps steps."""
        B = self.cfg.checkpoint_every_steps
        if self.engine == "frontier":
            return eng.run(max_steps=B, **self.run_kwargs)
        steps0 = self._engine_steps(eng)
        return eng.run(budget=B, max_steps=steps0 + B, **self.run_kwargs)

    def _slice_watched(self, eng):
        """Run one slice under the watchdog deadline: a slice that neither
        finishes nor faults in time is cancelled (injected hang gates) or
        abandoned (real hangs) and surfaced as a retriable fault."""
        if self.cfg.watchdog_s is None:
            return self._slice(eng)
        box: list = []

        def work():
            try:
                box.append(("ok", self._slice(eng)))
            except BaseException as e:  # noqa: BLE001 — re-raised by caller
                box.append(("err", e))

        deadline = self.cfg.watchdog_s
        if not self._eng_warm:
            deadline += self.cfg.compile_grace_s
        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(deadline)
        if t.is_alive():
            self.counters["watchdog_fired"] += 1
            self._tracer.instant("supervisor.watchdog", cat="faults")
            if self.plan is not None:
                self.plan.cancel_hangs()
            t.join(self.cfg.watchdog_grace_s)
            if t.is_alive():
                # A real hang: abandon the worker (daemon) and rebuild from
                # the last checkpoint; the stuck engine object is dropped.
                raise WatchdogTimeout(
                    f"slice exceeded watchdog_s={self.cfg.watchdog_s}; "
                    "engine abandoned"
                )
        status, val = box[0]
        if status == "err":
            raise val
        return val

    # -- completion / policy ---------------------------------------------------

    def _policy_done(self, result) -> bool:
        props = self.model.properties()
        fw = self.run_kwargs.get("finish_when", HasDiscoveries.ALL)
        disc = set(result.discoveries)
        if props and len(disc) == len(props):
            return True
        if fw.matches(props, disc):
            return True
        tsc = self.run_kwargs.get("target_state_count")
        if tsc is not None and result.state_count >= tsc:
            return True
        return False

    def _done(self, eng, result) -> bool:
        if result.complete or self._policy_done(result):
            return True
        if self.engine == "frontier" and not getattr(eng, "_q", True):
            return True
        return False

    # -- failure handling ------------------------------------------------------

    @staticmethod
    def _retriable(e: BaseException) -> bool:
        return isinstance(e, (FaultError, RuntimeError, OSError))

    @staticmethod
    def _overflow_kind(e: BaseException) -> Optional[str]:
        msg = str(e)
        if "queue full" in msg:
            return "queue"
        if "table full" in msg or "table or queue full" in msg:
            return "table"
        return None

    def _backoff(self, attempt: int) -> None:
        # The ONE seeded backoff spelling (faults/plan.py), shared with
        # the fleet router's submit retries and the blob-store client.
        delay = deterministic_backoff(
            self.cfg.seed, "backoff", attempt,
            self.cfg.backoff_base_s, self.cfg.backoff_cap_s,
            factor=self.cfg.backoff_factor,
        )
        if delay <= 0:
            return
        self.counters["backoff_ms"] += int(delay * 1000)
        time.sleep(delay)

    def _degrade(self) -> None:
        """Escalate one rung of the ladder and rewrite the config the next
        engine build will use."""
        if self._rung >= len(RUNGS) - 1:
            return
        self._rung += 1
        self._rung_failures = 0
        self.counters["degrade_steps"] += 1
        self.counters["degrade_rung"] = self._rung
        rung = RUNGS[self._rung]
        self._tracer.instant("supervisor.degrade", cat="faults", rung=rung)
        if rung == "shrink_batch":
            # Halve toward the floor, but never GROW a batch that already
            # sits below min_batch (a tiny batch may be what makes the
            # user's table config valid at all).
            self._batch = max(self._batch // 2, min(self._batch, self.cfg.min_batch))
        elif rung == "tiered":
            if self.engine_kwargs.get("store") == "tiered":
                # Already tiered: widen the spill band instead.
                hw = self.engine_kwargs.get("high_water", 0.85)
                self.engine_kwargs["high_water"] = max(hw - 0.15, 0.3)
            else:
                self.engine_kwargs["store"] = "tiered"
            # A store change cannot ride a checkpoint resume (the store
            # config travels in checkpoint meta); restart fresh.
            self._drop_checkpoint()

    def _drop_checkpoint(self) -> None:
        if self.ckpt is None:
            return
        from .ckptio import normalize_ckpt_path

        p = normalize_ckpt_path(self.ckpt)
        for f in (p, p + ".prev"):
            try:
                os.remove(f)
            except OSError:
                pass

    # -- the supervised loop ---------------------------------------------------

    def run(self):
        """Drive the search to completion (or graceful drain); returns the
        engine's `SearchResult` with `detail["faults"]` merged in."""
        old_handler = None
        in_main = threading.current_thread() is threading.main_thread()
        if self.cfg.drain_on_sigterm and in_main:
            try:
                old_handler = signal.signal(
                    signal.SIGTERM, lambda *_: setattr(self, "_sigterm", True)
                )
            except ValueError:
                old_handler = None
        try:
            with active(self.plan):
                return self._run_supervised()
        finally:
            if old_handler is not None:
                signal.signal(signal.SIGTERM, old_handler)

    def _run_supervised(self):
        failures = 0
        eng = None
        result = None
        while True:
            if eng is None:
                eng = self._build()
                self._eng_warm = False
            try:
                with self._tracer.span("supervisor.slice", cat="faults"):
                    result = self._slice_watched(eng)
            except BaseException as e:  # noqa: BLE001 — classified below
                if not self._retriable(e):
                    raise
                failures += 1
                self._rung_failures += 1
                self.counters["retries"] += 1
                self._tracer.instant(
                    "supervisor.retry",
                    cat="faults",
                    error=type(e).__name__,
                    failures=failures,
                )
                if failures > self.cfg.max_retries:
                    raise SupervisorGaveUp(
                        f"fault budget exhausted after {failures} failures "
                        f"(last: {type(e).__name__}: {e})"
                    ) from e
                overflow = self._overflow_kind(e)
                if overflow is not None:
                    # Targeted regrow: checkpoint the reverted carry (the
                    # chunked engines keep it at the last sound boundary)
                    # and grow the resource that actually ran out.
                    if overflow == "table":
                        self._table_log2 += 1
                        self._grow_table = True
                    else:
                        self._queue_log2 = (
                            self._queue_log2 or self._table_log2
                        ) + 1
                        self._grow_queue = True
                    if self.engine != "frontier" and getattr(
                        eng, "_carry", None
                    ) is not None:
                        self._checkpoint(eng, force=True)
                elif self._rung_failures >= self.cfg.retries_per_rung:
                    self._degrade()
                self._backoff(failures - 1)
                eng = None  # crash-only: discard and rebuild
                continue
            # Slice succeeded: progress resets the per-rung failure streak.
            self._rung_failures = 0
            self._eng_warm = True
            if self._done(eng, result):
                self._checkpoint(eng)
                break
            if self._sigterm:
                self.counters["drained"] += 1
                self._tracer.instant("supervisor.drain", cat="faults")
                self._checkpoint(eng, force=True)
                break
            self._checkpoint(eng)
        return dataclasses.replace(
            result,
            detail={**(result.detail or {}), "faults": self.fault_stats()},
        )

    # -- reporting -------------------------------------------------------------

    def fault_stats(self) -> dict:
        """The `detail["faults"]` dict (obs/schema.py FAULTS_DETAIL_KEYS)."""
        out = (
            self.plan.stats()
            if self.plan is not None
            else {"injected_total": 0, "injected": {}}
        )
        out.update(self.counters)
        return out

    def metrics(self) -> dict:
        """Flat counters for the obs registry / `GET /metrics`."""
        return self.fault_stats()


def run_supervised(
    model,
    engine: str = "resident",
    plan: Optional[FaultPlan] = None,
    config: Optional[SupervisorConfig] = None,
    checkpoint_path: Optional[str] = None,
    engine_kwargs: Optional[dict] = None,
    run_kwargs: Optional[dict] = None,
    tracer=None,
):
    """Run `model` under the self-healing supervisor; see the module
    docstring for the recovery policy. `plan` defaults to
    `FaultPlan.from_env()` (the `SR_TPU_FAULTS=` knob); pass
    `checkpoint_path` to enable checkpoint-based recovery (strongly
    recommended — without it every recovery is a fresh restart)."""
    return Supervisor(
        model,
        engine=engine,
        plan=plan,
        config=config,
        checkpoint_path=checkpoint_path,
        engine_kwargs=engine_kwargs,
        run_kwargs=run_kwargs,
        tracer=tracer,
    ).run()
