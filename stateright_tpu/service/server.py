"""HTTP front end of the check service (the Explorer server plumbing,
repointed at the multi-job scheduler).

Endpoints:

- ``GET /.status`` — service counters + one summary row per job (queue
  wait, lanes held, preemptions, per-tier store occupancy, step-telemetry
  digest — the service twin of the Explorer's `/.status`).
- ``GET /metrics`` — every registered counter source (the obs registry:
  this service, any live engines, ...) in Prometheus text exposition
  format, scrape-ready.
- ``POST /jobs`` — submit a job: ``{"model": "<registry name>", "args":
  {...}, "opts": {"target_max_depth": ..., "timeout": ..., "priority":
  ...}}`` → ``{"job": id}``. Models are named through a REGISTRY of
  builder callables (HTTP clients cannot ship Python model objects); the
  default registry carries the bundled tensor workloads.
- ``GET /jobs/<id>`` — poll one job (status, counts, discovery names,
  metrics).
- ``POST /jobs/<id>/cancel`` / ``DELETE /jobs/<id>`` — cancel.
- ``POST /jobs/<id>/withdraw`` — atomically remove a still-QUEUED job
  (the fleet work-stealing primitive, exposed over HTTP so a remote
  router can steal exactly like an in-proc one); ``{"withdrawn": bool}``.
- ``GET /jobs/<id>/discoveries`` — the reconstructed discovery paths of a
  finished job (action-label lists, the `assert_discovery` currency).
- ``GET /jobs/<id>/events?since=N&wait=S`` — live flight-recorder tail
  (obs/events.py; the service must be built with ``events``/
  ``events_out``): journal events naming the job with cursor >= ``since``,
  long-polling up to ``wait`` seconds for the first match. The response's
  ``next`` is the cursor to pass back — the dashboard follow-a-job
  primitive.

The view builders are pure functions over the service, the same
test-without-sockets strategy as explorer/server.py.
"""

from __future__ import annotations

import json
import threading
from typing import Callable, Optional
from urllib.parse import parse_qs

from ..explorer.server import ExplorerServer
from ..faults.plan import FaultError, maybe_fault
from ..obs import REGISTRY, render_prometheus
from .api import CheckService
from .tenancy import DEFAULT_TENANT, QuotaExceeded

#: `Retry-After` seconds on every 503 this plane emits (injected faults,
#: router overload) — deterministic, so load clients back off identically
#: run to run instead of hot-looping.
RETRY_AFTER_S = "1"


def default_registry() -> dict:
    """Name -> model-builder callables for the bundled tensor workloads.
    Repeat submissions of the same config build equal models, which name
    one definition (`TensorModel.definition_key()`), so they share one
    compiled step and one batch (the continuous-batching win)."""
    from ..tensor.models import (
        TensorIncrementLock,
        TensorLinearEquation,
        TensorTwoPhaseSys,
    )
    from ..tensor.paxos import TensorPaxos

    reg: dict[str, Callable] = {
        "2pc": lambda n=3, **kw: TensorTwoPhaseSys(int(n), **kw),
        "paxos": lambda n=2, **kw: TensorPaxos(client_count=int(n), **kw),
        "inclock": lambda n=3, **kw: TensorIncrementLock(int(n), **kw),
        "lineq": lambda a=2, b=10, **kw: TensorLinearEquation(
            int(a), int(b), **kw
        ),
    }
    return reg


class ModelRegistry:
    """Builder callables by name (see default_registry): (name, args) ->
    a model built from them."""

    def __init__(self, builders: Optional[dict] = None):
        self._builders = (
            dict(builders) if builders is not None else default_registry()
        )

    def names(self) -> list:
        return sorted(self._builders)

    def get(self, name: str, args: Optional[dict] = None):
        if name not in self._builders:
            raise KeyError(
                f"unknown model {name!r} (registered: {self.names()})"
            )
        return self._builders[name](**dict(args or {}))


# -- pure view builders --------------------------------------------------------


def job_view(service: CheckService, job_id: int) -> dict:
    return service.poll(job_id)


def status_view(service: CheckService) -> dict:
    """JSON for `GET /.status`: service counters + per-job rows."""
    return {
        **service.stats(),
        "job_rows": [service.poll(jid) for jid in service.job_ids()],
    }


def metrics_view(service: CheckService) -> str:
    """Prometheus text for `GET /metrics`: every source in the obs
    registry. The served (live, strongly-referenced) service is already in
    the collection under its registered name; the fallback only fires if it
    was somehow unregistered (e.g. scrape racing close())."""
    groups = REGISTRY.collect()
    if service._metrics_name not in groups:
        groups[service._metrics_name] = service.metrics()
    return render_prometheus(groups)


def submit_view(
    service: CheckService, registry: ModelRegistry, payload: dict
) -> dict:
    from ..core.discovery import HasDiscoveries

    opts = dict(payload.get("opts") or {})
    fw = opts.pop("finish_when", None)
    if fw is not None:
        opts["finish_when"] = {
            "all": HasDiscoveries.ALL,
            "any": HasDiscoveries.ANY,
            "all_failures": HasDiscoveries.ALL_FAILURES,
            "any_failures": HasDiscoveries.ANY_FAILURES,
        }[fw]
    model = registry.get(payload["model"], payload.get("args"))
    tenant = payload.get("tenant") or DEFAULT_TENANT
    handle = service.submit(model, tenant=tenant, **opts)
    return {"job": handle.id}


def events_view(service, job_id: int, query: str) -> dict:
    """JSON for `GET /jobs/<id>/events?since=N&wait=S`: the flight-recorder
    long-poll (shared by serve_service and serve_fleet — `service` is
    anything with `events_tail`). Malformed cursors degrade to defaults —
    an observability endpoint must never 500 over a bad query."""
    q = parse_qs(query)
    try:
        since = int(q.get("since", ["0"])[0])
    except ValueError:
        since = 0
    try:
        # Cap the long-poll under common proxy/client timeouts.
        wait_s = max(0.0, min(float(q.get("wait", ["0"])[0]), 25.0))
    except ValueError:
        wait_s = 0.0
    events, nxt = service.events_tail(job_id, since=since, wait_s=wait_s)
    return {"events": events, "next": nxt}


def discoveries_view(service: CheckService, job_id: int) -> dict:
    job = service._get(job_id)
    paths = service.discovery_paths(job_id)
    return {
        name: {
            "fingerprint": str(job.discoveries[name]),
            "actions": [repr(a) for a in path.actions()],
            "last_state": repr(path.last_state()),
        }
        for name, path in paths.items()
    }


# -- HTTP plumbing -------------------------------------------------------------


def serve_service(
    service: CheckService,
    address: str = "localhost:3400",
    registry: Optional[ModelRegistry] = None,
    block: bool = False,
) -> ExplorerServer:
    """Start the HTTP front end; returns the same server handle shape as
    the Explorer's `serve` (shutdown() stops it)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    reg = registry if registry is not None else ModelRegistry()
    host, _, port = address.partition(":")

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet by default
            pass

        def _json(self, obj, code=200, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _job_id(self, suffix: str = "") -> Optional[int]:
            raw = self.path.partition("?")[0][len("/jobs/"):]
            if suffix:
                if not raw.endswith(suffix):
                    return None
                raw = raw[: -len(suffix)]
            try:
                return int(raw.strip("/"))
            except ValueError:
                return None

        def _text(self, body: str, code=200):
            data = body.encode()
            self.send_response(code)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _injected_503(self, method: str) -> bool:
            """Chaos-plane boundary for the HTTP plane: an injected
            `service.http` fault degrades to a 503 (the retryable status
            clients already understand) instead of crashing the handler —
            the front end must stay up through its own faults. The
            `Retry-After` header is what lets the fleet router and load
            clients back off deterministically instead of hot-looping."""
            try:
                maybe_fault("service.http", method=method, path=self.path)
            except FaultError as e:
                self._json(
                    {"error": f"injected fault: {e}"}, 503,
                    headers={"Retry-After": RETRY_AFTER_S},
                )
                return True
            return False

        def do_GET(self):
            if self._injected_503("GET"):
                return
            path, _, query = self.path.partition("?")
            try:
                if path == "/.status":
                    self._json(status_view(service))
                    return
                if path == "/metrics":
                    self._text(metrics_view(service))
                    return
                if path.startswith("/jobs/"):
                    if path.endswith("/discoveries"):
                        jid = self._job_id("/discoveries")
                        if jid is not None:
                            self._json(discoveries_view(service, jid))
                            return
                    if path.endswith("/events"):
                        jid = self._job_id("/events")
                        if jid is not None:
                            service._get(jid)  # 404 on unknown jobs
                            self._json(events_view(service, jid, query))
                            return
                    jid = self._job_id()
                    if jid is not None:
                        self._json(job_view(service, jid))
                        return
                self._json({"error": "not found"}, 404)
            except KeyError as e:
                self._json({"error": str(e)}, 404)

        def do_POST(self):
            if self._injected_503("POST"):
                return
            try:
                if self.path == "/jobs":
                    n = int(self.headers.get("Content-Length") or 0)
                    try:
                        payload = json.loads(self.rfile.read(n) or b"{}")
                    except ValueError:
                        self._json({"error": "bad JSON body"}, 400)
                        return
                    if "model" not in payload:
                        self._json({"error": "missing 'model'"}, 400)
                        return
                    try:
                        self._json(submit_view(service, reg, payload))
                    except QuotaExceeded as e:
                        # Over-quota is retryable by contract, not a bad
                        # request: 429 + a Retry-After computed from the
                        # tenant's actual refill rate, mirroring the 503
                        # discipline (clients back off, never hot-loop).
                        self._json(
                            {
                                "error": str(e),
                                "tenant": e.tenant,
                                "reason": e.reason,
                            },
                            429,
                            headers={"Retry-After": str(e.retry_after_s)},
                        )
                    return
                if self.path.startswith("/jobs/") and self.path.endswith(
                    "/cancel"
                ):
                    jid = self._job_id("/cancel")
                    if jid is not None:
                        self._json({"cancelled": service.cancel(jid)})
                        return
                if self.path.startswith("/jobs/") and self.path.endswith(
                    "/withdraw"
                ):
                    jid = self._job_id("/withdraw")
                    if jid is not None:
                        service._get(jid)  # 404 on unknown jobs
                        self._json({"withdrawn": service.withdraw(jid)})
                        return
                self._json({"error": "not found"}, 404)
            except KeyError as e:
                self._json({"error": str(e)}, 404)
            except Exception as e:  # noqa: BLE001 — bad submits must not kill
                self._json({"error": f"{type(e).__name__}: {e}"}, 400)

        def do_DELETE(self):
            if self._injected_503("DELETE"):
                return
            jid = self._job_id()
            if jid is None:
                self._json({"error": "not found"}, 404)
                return
            try:
                self._json({"cancelled": service.cancel(jid)})
            except KeyError as e:
                self._json({"error": str(e)}, 404)

    httpd = ThreadingHTTPServer(
        (host or "localhost", int(port or 3400)), Handler
    )
    if block:
        server = ExplorerServer(httpd, service, None)
        try:
            httpd.serve_forever()
        finally:
            httpd.server_close()
        return server
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return ExplorerServer(httpd, service, thread)
