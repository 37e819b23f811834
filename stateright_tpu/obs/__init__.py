"""Telemetry spine: device step counters, span tracing, counter registry.

Three planes, wired through every layer of the checker (engines, tiered
store, check service, HTTP servers):

1. **Device step telemetry** (`ring.py`) — each engine step appends one
   fixed-width metrics row (`STEP_COLS`) into a device-resident ring buffer
   drained to host in bulk at chunk boundaries; `StepRing.summary()` is the
   digest surfaced in `SearchResult.detail["telemetry"]` and bench rows.
2. **Span tracing** (`trace.py`) — host phases (dispatch, eviction, suspect
   resolution, checkpoint, service grants) recorded as Chrome trace-event
   JSON via the `trace_out=` knob; viewable in Perfetto, optionally aligned
   with XLA traces through `jax.profiler.TraceAnnotation`.
3. **Counter registry + export** (`registry.py`, `schema.py`) — components
   register metric providers into `REGISTRY`; both HTTP servers render it as
   Prometheus text at `GET /metrics`; `schema.py` pins the one documented
   `SearchResult.detail` vocabulary.
4. **Flight recorder** (`events.py`, `timeline.py`) — a crash-durable
   JSONL event journal with a schema'd vocabulary (`EVENT_TYPES`) and
   job-scoped `trace` ids minted at submission, plus the forensic CLI
   (`python -m stateright_tpu.obs.timeline`) that reconstructs per-job
   lifecycles across replicas, flags anomalies, and merges Chrome traces.
"""

from .ring import N_COLS, STEP_COLS, StepRing, build_detail
from .events import (
    NULL_EVENTS,
    EventJournal,
    as_events,
    merge_events,
    mint_trace_id,
    read_journal,
    read_journals,
)
from .registry import (
    REGISTRY,
    CounterRegistry,
    LogHistogram,
    flatten_metrics,
    render_prometheus,
)
from .schema import (
    DETAIL_KEYS,
    EVENT_TYPES,
    FAULTS_DETAIL_KEYS,
    PHASES_DETAIL_KEYS,
    SERVICE_DETAIL_KEYS,
    STAGES_COUNTER_KEYS,
    TELEMETRY_KEYS,
    TERMINAL_EVENT_BY_STATUS,
    TERMINAL_EVENTS,
    validate_detail,
)
from .trace import NULL_TRACER, Tracer, as_tracer

__all__ = [
    "STEP_COLS",
    "N_COLS",
    "StepRing",
    "build_detail",
    "REGISTRY",
    "CounterRegistry",
    "LogHistogram",
    "flatten_metrics",
    "render_prometheus",
    "DETAIL_KEYS",
    "EVENT_TYPES",
    "FAULTS_DETAIL_KEYS",
    "PHASES_DETAIL_KEYS",
    "SERVICE_DETAIL_KEYS",
    "STAGES_COUNTER_KEYS",
    "TELEMETRY_KEYS",
    "TERMINAL_EVENT_BY_STATUS",
    "TERMINAL_EVENTS",
    "validate_detail",
    "NULL_TRACER",
    "Tracer",
    "as_tracer",
    "NULL_EVENTS",
    "EventJournal",
    "as_events",
    "merge_events",
    "mint_trace_id",
    "read_journal",
    "read_journals",
]
