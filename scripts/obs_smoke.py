#!/usr/bin/env python
"""Observability smoke: run a pinned model with telemetry + tracing on,
assert every artifact exists and validates.

CI-shaped: exercises the whole telemetry spine in one command — device step
ring (drain totals vs golden counts), Chrome trace-event JSON (trace_out
through the builder), and the Prometheus `/metrics` plane on the service
HTTP front end. Exit code 0 iff every check passes.

    JAX_PLATFORMS=cpu python scripts/obs_smoke.py [--keep]

Artifacts land in a temp dir (kept with --keep, printed either way); load
the trace in https://ui.perfetto.dev.
"""

import json
import os
import re
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GOLD = (1_146, 288)  # 2pc-3 generated/unique (ref examples/2pc.rs:153-159)

_PROM_LINE = re.compile(
    r"^(# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eE]+)$"
)


def main(argv) -> int:
    from stateright_tpu.service import CheckService
    from stateright_tpu.service.server import serve_service
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    keep = "--keep" in argv
    outdir = tempfile.mkdtemp(prefix="obs_smoke_")
    trace_path = os.path.join(outdir, "engine.trace.json")
    svc_trace_path = os.path.join(outdir, "service.trace.json")
    failures = []

    def check(ok: bool, what: str):
        print(("ok   " if ok else "FAIL ") + what)
        if not ok:
            failures.append(what)

    model = TensorTwoPhaseSys(3)
    from stateright_tpu.tensor.frontier import seed_init

    init, _, _, n_raw = seed_init(model)
    n0 = len(init)

    # 1. Engine telemetry + tracing through the builder surface.
    checker = (
        model.checker()
        .trace_out(trace_path)
        .spawn_tpu(batch_size=256, table_log2=12)
        .join()
    )
    t = checker.telemetry_summary()
    check(checker.unique_state_count() == GOLD[1], "engine golden unique count")
    check(t is not None and t["steps"] > 0, "telemetry digest present")
    # Conservation law: every fresh claim (resp. generated state) appears
    # in exactly one drained step row, so the ring totals reconstruct the
    # golden counts from the seed.
    check(
        t["dropped_steps"] == 0
        and t["claimed_total"] == checker.unique_state_count() - n0
        and t["generated_total"] == checker.state_count() - n_raw,
        "telemetry claim/generation accounting",
    )
    check(os.path.exists(trace_path), f"trace file exists ({trace_path})")
    doc = json.load(open(trace_path))
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    check(len(events) > 0, f"trace has {len(events)} complete spans")
    check(
        all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in events),
        "trace events are Chrome trace-event shaped",
    )

    # 2. Service telemetry + /metrics scrape + service trace.
    svc = CheckService(
        batch_size=256, table_log2=14, background=False,
        trace_out=svc_trace_path,
    )
    handle = svc.submit(model)
    svc.drain(timeout=600)
    r = handle.result()
    check(
        (r.state_count, r.unique_state_count) == GOLD,
        "service job golden counts",
    )
    check(
        r.detail is not None and "telemetry" in r.detail,
        "job result carries telemetry detail",
    )
    st = svc.stats()
    check(
        st["telemetry"]["steps"] == st["device_steps"] > 0,
        "service ring saw every fused step",
    )
    server = serve_service(svc, "localhost:0")
    try:
        body = (
            urllib.request.urlopen(
                f"http://{server.address}/metrics", timeout=10
            )
            .read()
            .decode()
        )
        lines = [ln for ln in body.splitlines() if ln.strip()]
        check(
            bool(lines) and all(_PROM_LINE.match(ln) for ln in lines),
            f"/metrics parses as Prometheus text ({len(lines)} lines)",
        )
        status = json.loads(
            urllib.request.urlopen(
                f"http://{server.address}/.status", timeout=10
            ).read()
        )
        check("telemetry" in status, "/.status merged the telemetry digest")
    finally:
        server.shutdown()
    svc.close()
    check(os.path.exists(svc_trace_path), "service trace file exists")

    print(f"artifacts in {outdir}" + ("" if keep else " (temp)"))
    if failures:
        print("FAILURES:", "; ".join(failures), file=sys.stderr)
        return 1
    print("obs smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
