"""Where a cell's checks spend their time, by the program's own spans and
named scopes: a measurement for PERF.md on the chip, not a benchmark run.

After set-up as run.py makes it, runs `--plain` checks as they are
measured, `--tracer` checks with the program's span tracer on and no
profiler (seconds per span name of each, from its trace file), then a
window of `--seconds` under the profiler (as `--trace 1` runs it), and
reduces that window's trace per check: seconds per span name,
the four stages' cover of `resident.search`, device programs inside
`path.replay`, device ms per step per named scope of the search program,
the top ops with their scope, and the longest idle gaps named by the
innermost span. Prints one JSON line and writes it to `<out>/<cell>.json`.

On a TPU host, from the checkout's root:

    python3 benchmark/tests/span_breakdown.py --workload paxos3.exhaustive --out <dir>
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from harness import attribution, spec, trace  # noqa: E402
from harness.window import model_factory, run_check, run_window  # noqa: E402

STAGES = ("resident.trace", "resident.lower", "resident.compile",
          "resident.execute")
PROGRAM_SPANS = ("search.run", "resident.search", *STAGES,
                 "checker.discoveries", "path.parent_map", "path.replay")
BENCH_SPANS = ("bench.window", "bench.check", "bench.spawn", "bench.join",
               "bench.discoveries")
SCOPES = ("expand", "fingerprint", "insert", "append", "properties")


def span_totals(path: str) -> dict:
    """Seconds per span name in one check's trace file (the program's
    Chrome trace-event JSON)."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    out: dict = {}
    for e in events:
        if e.get("ph") == "X":
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur"] / 1e6
    return out


def reduce_window(xplane: str, records: list, program: str) -> dict:
    t = trace.read_xplane(xplane, span_names=set(BENCH_SPANS + PROGRAM_SPANS))
    ops = attribution.read_ops(xplane)[0]
    mods = t.modules[0]
    checks = sorted(s for s in t.spans if s[2] == "bench.check")
    (win,) = [s for s in t.spans if s[2] == "bench.window"]
    merged = trace.union(t.ops[0])
    out = []
    for (s, e, _), rec in zip(checks, records):
        inside = [x for x in t.spans if s <= x[0] and x[1] <= e]
        row = {"wall_s": (e - s) / 1e9, "steps": rec.steps,
               "busy_s": trace.covered(merged, s, e) / 1e9}
        for name in PROGRAM_SPANS:
            row[name] = attribution.span_seconds(inside, name)
        search = row["resident.search"]
        row["stage_cover"] = (sum(row[n] for n in STAGES) / search
                              if search else None)
        replays = [x for x in inside if x[2] == "path.replay"]
        row["replay_programs"] = attribution.executions_within(mods, replays)
        runs = [m for m in mods if m[2].startswith(program) and s <= m[0] < e]
        by_scope = attribution.scope_self_ns(ops, SCOPES, runs)
        program_busy = trace.covered(trace.union(trace.within(t.ops[0], runs)),
                                     s, e)
        if rec.steps:
            row["step_ms"] = program_busy / 1e6 / rec.steps
            row["kernel_ms"] = {k: v / 1e6 / rec.steps
                                for k, v in sorted(by_scope.items())}
        out.append(row)
    labeled = [(a, b, f"{n} [{attribution.scope_of(tf, SCOPES)}]")
               for (a, b, n), (_, _, tf) in zip(t.ops[0], ops)]
    top = trace.top(trace.self_times(trace.within(labeled, [win[:2]])))
    spans = [x for x in t.spans if x[2] != "bench.window"]
    idle = sorted(trace.gaps(merged, win[0], win[1]), key=lambda g: g[0] - g[1])
    gaps = [[trace.innermost(spans, (a + b) / 2), (b - a) / 1e9]
            for a, b in idle[:10]]
    window_s = (win[1] - win[0]) / 1e9
    return {"window_s": window_s,
            "idle_pct": 100 * (1 - trace.covered(merged, *win[:2]) / 1e9
                               / window_s),
            "checks": out, "device_ops": top, "idle_gaps": gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=22)
    ap.add_argument("--plain", type=int, default=2)
    ap.add_argument("--tracer", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    import jax

    from stateright_tpu.compile_cache import use_compile_cache

    assert jax.devices()[0].platform == "tpu", jax.devices()
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    config, traffic = cell.config, cell.traffic
    make = model_factory(config)
    run_check(make, config, traffic, depth=config["warmup_max_depth"])
    result = {"workload": args.workload, "setup_s": time.monotonic() - t0}
    tmp = tempfile.mkdtemp(prefix="spans_")
    try:
        plain = [run_check(make, config, traffic) for _ in range(args.plain)]
        tracer, result["tracer_spans"] = [], []
        for i in range(args.tracer):
            spans_json = os.path.join(tmp, f"tracer{i}.json")
            tracer.append(run_check(make, config, traffic,
                                    trace_out=spans_json))
            result["tracer_spans"].append(span_totals(spans_json))
        result["plain_wall_s"] = [r.wall_s for r in plain]
        result["tracer_wall_s"] = [r.wall_s for r in tracer]
        spans_json = os.path.join(tmp, "traced.json")
        prof = os.path.join(tmp, "prof")
        jax.profiler.start_trace(prof, profiler_options=trace.profile_options())
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                records = run_window(args.seconds, lambda: run_check(
                    make, config, traffic, trace_out=spans_json,
                    annotate=True))
        finally:
            jax.profiler.stop_trace()
        errors = [r.error for r in plain + tracer + records if r.error]
        if errors:
            raise RuntimeError(f"checks failed: {errors}")
        result["traced_wall_s"] = [r.wall_s for r in records]
        xplane = trace.find_xplane(prof)
        try:
            result.update(reduce_window(xplane, records, config["program"]))
        except Exception:
            # Keep the trace to reduce again off the chip.
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(xplane, os.path.join(args.out,
                                             args.workload + ".xplane.pb"))
            raise
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
