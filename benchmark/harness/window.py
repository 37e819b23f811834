"""Set-up and the measured window: whole checks, back to back, each
through the public entry exactly as a user writes it —

    c = model.checker()[.finish_when(...)][.target_max_depth(d)]
                       .spawn_tpu(batch_size=..., table_log2=...)
    c.join()
    c.discoveries()

— with `jax.monitoring`'s compile and persistent-cache events on the host
clock, so that set-up and window can each say what compiled or loaded."""

from __future__ import annotations

import contextlib
import importlib
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"


class Events:
    """`jax.monitoring` events with the host time at which they arrived."""

    def __init__(self):
        self.log: list = []  # (monotonic t, event, seconds or None)

    def _on_event(self, event, **_kw):
        self.log.append((time.monotonic(), event, None))

    def _on_duration(self, event, duration, **_kw):
        self.log.append((time.monotonic(), event, duration))

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_listener(self._on_event)
        mon.unregister_event_duration_listener(self._on_duration)
        return False

    def summary(self, t0: float, t1: float) -> dict:
        """Counts and seconds of the events that arrived in [t0, t1]."""
        out = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
               "cache_misses": 0, "cache_load_s": 0.0, "traces": 0,
               "trace_s": 0.0}
        for t, event, d in self.log:
            if not t0 <= t <= t1:
                continue
            if event == COMPILE:
                out["compiles"] += 1
                out["compile_s"] += d
            elif event == TRACE:
                out["traces"] += 1
                out["trace_s"] += d
            elif event == RETRIEVE:
                out["cache_load_s"] += d
            elif event == HIT:
                out["cache_hits"] += 1
            elif event == MISS:
                out["cache_misses"] += 1
        return out


@dataclass
class Check:
    """One check's answer and timing (host monotonic seconds)."""

    start: float
    end: float = 0.0
    generated: int = 0
    unique: int = 0
    depth: int = 0
    steps: int = 0
    discovered: set = field(default_factory=set)  # property names
    paths: dict = field(default_factory=dict)  # property -> decoded states
    error: Optional[str] = None

    @property
    def wall_s(self) -> float:
        return self.end - self.start


def model_factory(config: dict) -> Callable:
    """The configuration's constructor, named in its file as module, class
    and keyword arguments."""
    m = config["model"]
    cls = getattr(importlib.import_module(m["module"]), m["class"])
    kwargs = dict(m.get("kwargs", {}))
    return lambda: cls(**kwargs)


def _span(name: str, annotate: bool):
    if not annotate:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_check(make_model: Callable, config: dict, traffic: dict,
              depth: Optional[int] = None, trace_out: Optional[str] = None,
              annotate: bool = False) -> Check:
    """One whole check through the public entry; an exception is the
    check's answer (`error`), not the harness's."""
    from stateright_tpu import HasDiscoveries

    rec = Check(start=time.monotonic())
    try:
        with _span("bench.check", annotate):
            with _span("bench.spawn", annotate):
                b = make_model().checker()
                if traffic.get("finish_when"):
                    b = b.finish_when(getattr(HasDiscoveries,
                                              traffic["finish_when"]))
                if depth is not None:
                    b = b.target_max_depth(depth)
                kw = dict(config["spawn"])
                if trace_out is not None:
                    kw["trace_out"] = trace_out
                c = b.spawn_tpu(**kw)
            with _span("bench.join", annotate):
                c.join()
            with _span("bench.discoveries", annotate):
                found = c.discoveries()
        rec.end = time.monotonic()
        rec.generated = c.state_count()
        rec.unique = c.unique_state_count()
        rec.depth = c.max_depth()
        rec.steps = (c.telemetry_summary() or {}).get("steps", 0)
        rec.discovered = set(found)
        rec.paths = {name: p.states() for name, p in found.items()}
    except Exception as e:  # noqa: BLE001 — a failed check is an answer
        rec.end = time.monotonic()
        rec.error = f"{type(e).__name__}: {e}"
    return rec


def run_window(seconds: float, check: Callable[[], Check]) -> list:
    """Checks back to back: the window opens when the first starts and
    closes at the verdict of the first that ends `seconds` or more after
    that (or at a check that fails)."""
    records = [check()]
    while not records[-1].error and records[-1].end - records[0].start < seconds:
        records.append(check())
    return records
