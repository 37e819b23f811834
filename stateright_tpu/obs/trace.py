"""Host-side span tracing as Chrome trace-event JSON.

The engines' device kernels are opaque to wall-clock tracing (one dispatch =
one black box), but everything AROUND them is host phases worth seeing on a
timeline: compile+dispatch chunks, tiered-store eviction / suspect
resolution, queue compaction, checkpointing, and the check service's
admission/grant/preempt/finalize lifecycle. `Tracer` records those phases as
complete ("ph": "X") events in the Chrome trace-event format, so the file a
run leaves behind (`trace_out=` on `CheckerBuilder`/`spawn_tpu`/
`CheckService`) loads directly in Perfetto (https://ui.perfetto.dev) or
chrome://tracing.

With `annotate=True` each span also enters a `jax.profiler.TraceAnnotation`
under the span's plain name (args stay in the JSON), so when a jax profiler
session is active the host phases line up with the XLA device trace in the
same Perfetto view.

`NULL_TRACER` is the default collaborator everywhere: `span()` returns a
shared no-op context manager, so call sites trace unconditionally with ~zero
cost when tracing is off.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._ann = None

    def __enter__(self):
        self._t0 = time.monotonic()
        if self._tracer.annotate:
            try:
                from jax.profiler import TraceAnnotation

                self._ann = TraceAnnotation(self._name)
                self._ann.__enter__()
            except Exception:  # noqa: BLE001 — annotation is best-effort
                self._ann = None
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # noqa: BLE001
                pass
        self._tracer._record(
            self._name, self._cat, self._t0, time.monotonic(), self._args
        )
        return False

    def set(self, **args) -> None:
        """Add args known only once the phase has run (counters such as
        the entries a span built); they land in the recorded event."""
        self._args.update(args)


class Tracer:
    """Collects trace events; thread-safe (the check service spans from its
    scheduler thread while clients span from theirs).

    With `out=` set the tracer ALSO flushes itself to that path every
    `flush_every` recorded events or `flush_interval_s` seconds (atomic
    tmp+rename, so the file is always loadable JSON) — a crashed replica
    leaves a usable partial trace instead of nothing, which is what lets
    obs/timeline.py merge a fleet's per-process traces after a chaos run.
    Before this, the only write was the owner's `save()` at clean close
    (service/api.py), so every crash erased its own evidence."""

    def __init__(
        self,
        annotate: bool = False,
        max_events: int = 200_000,
        out: Optional[str] = None,
        flush_every: int = 256,
        flush_interval_s: float = 2.0,
        args: Optional[dict] = None,
    ):
        self.annotate = annotate
        # Args every recorded event carries (e.g. the check id that joins
        # one spawn's spans); an event's own args win on a shared key.
        self.args = dict(args or {})
        self.max_events = max_events
        self.out = out
        self.flush_every = max(int(flush_every), 1)
        self.flush_interval_s = flush_interval_s
        self.events: list[dict] = []
        self.dropped = 0
        self._lock = threading.Lock()
        # Flush I/O runs OUTSIDE self._lock (recording threads must never
        # block on disk); this second lock only serializes concurrent
        # writers of the out file.
        self._io_lock = threading.Lock()
        self._epoch = time.monotonic()
        self._pid = os.getpid()
        self._unflushed = 0
        self._flush_threshold = self.flush_every
        self._last_flush = time.monotonic()

    @property
    def enabled(self) -> bool:
        return True

    def span(self, name: str, cat: str = "host", **args) -> _Span:
        """Context manager timing one phase; nests naturally per thread."""
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        args = {**self.args, **args}
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "i",
                    "s": "t",
                    "ts": (time.monotonic() - self._epoch) * 1e6,
                    "pid": self._pid,
                    "tid": threading.get_ident(),
                    **({"args": args} if args else {}),
                }
            )
            snap = self._maybe_flush_locked()
        self._write_snapshot(snap)

    def _record(self, name, cat, t0, t1, args) -> None:
        args = {**self.args, **args}
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "X",
                    "ts": (t0 - self._epoch) * 1e6,
                    "dur": (t1 - t0) * 1e6,
                    "pid": self._pid,
                    "tid": threading.get_ident(),
                    **({"args": args} if args else {}),
                }
            )
            snap = self._maybe_flush_locked()
        self._write_snapshot(snap)

    def _envelope(self, events: list) -> dict:
        meta = {
            "name": "process_name",
            "ph": "M",
            "pid": self._pid,
            "args": {"name": "stateright_tpu"},
        }
        return {
            "traceEvents": [meta] + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped, "pid": self._pid},
        }

    def _maybe_flush_locked(self) -> Optional[list]:
        """The crash-durability cadence decision (called with self._lock
        held): returns the event snapshot to persist, or None. The actual
        serialization + write happens in the CALLER, outside the lock —
        recording threads must never stall behind disk I/O. The
        event-count trigger grows with the log (each rewrite is
        O(events), so a fixed cadence would cost O(n^2) over a long run);
        the time trigger stays fixed — a crash loses at most
        `flush_interval_s` of recording, which is the durability
        contract."""
        if self.out is None:
            return None
        self._unflushed += 1
        now = time.monotonic()
        # The time trigger also backs off as the trace grows (up to 16x):
        # a trickle of events into a huge trace would otherwise rewrite
        # the whole file every interval for O(1) new data. The loss
        # window stays bounded (16 * flush_interval_s worst case).
        eff_interval = self.flush_interval_s * min(
            max(len(self.events) / (4.0 * self.flush_every), 1.0), 16.0
        )
        if (
            self._unflushed < self._flush_threshold
            and now - self._last_flush < eff_interval
        ):
            return None
        self._unflushed = 0
        self._last_flush = now
        self._flush_threshold = max(self.flush_every, len(self.events) // 2)
        return list(self.events)

    def _write_snapshot(self, snap: Optional[list]) -> None:
        if snap is None:
            return
        with self._io_lock:
            self._write(self.out, self._envelope(snap))

    @staticmethod
    def _write(path: str, envelope: dict) -> None:
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(envelope, f)
            os.replace(tmp, path)
        except OSError:
            pass  # tracing must never fail its host

    def to_json(self) -> dict:
        """The Chrome trace-event envelope (object form, the variant every
        consumer accepts)."""
        with self._lock:
            events = list(self.events)
        return self._envelope(events)

    def flush(self) -> Optional[str]:
        """Force one durability flush to `out` (None when no out path)."""
        if self.out is None:
            return None
        with self._io_lock:
            self._write(self.out, self.to_json())
        return self.out

    def save(self, path: Optional[str] = None) -> Optional[str]:
        """Write the trace JSON to `path` (default: the `out` path);
        returns the path written (load it in Perfetto). Serialized with
        the periodic flusher — a close()-time save racing a cadence flush
        must not interleave writes to the same tmp file."""
        path = path if path is not None else self.out
        if path is None:
            return None
        with self._io_lock:
            self._write(path, self.to_json())
        return path


class _NullTracer:
    """Span/instant/save no-ops; the default `tracer` everywhere."""

    annotate = False
    enabled = False
    events: list = []

    def span(self, name: str, cat: str = "host", **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "host", **args) -> None:
        pass

    def to_json(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def flush(self) -> Optional[str]:
        return None

    def save(self, path: Optional[str] = None) -> Optional[str]:
        return None


NULL_TRACER = _NullTracer()


def as_tracer(tracer: Optional[Tracer]) -> "Tracer | _NullTracer":
    return tracer if tracer is not None else NULL_TRACER
