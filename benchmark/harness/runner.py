"""One run of one cell: set-up, the window, the reference, the comparison
and the metrics. run.py calls `run_cell` once it has found the chips; the
tests call it on the CPU with a small configuration."""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from . import compare, reference, roofline, trace
from .window import Events, model_factory, run_check, run_window

#: Host spans the trace reduction names idle gaps by: the benchmark's own
#: and the program's (obs.Tracer annotations via spawn_tpu(trace_out=)).
SPANS = ("bench.window", "bench.check", "bench.spawn", "bench.join",
         "bench.discoveries", "search.run", "resident.search")


@dataclass
class Reading:
    """What the per-layer readers (metrics/<name>.py) read: the traced
    window reduced, the checks in it, and set-up's compile events."""

    window_s: float
    busy_s: float  # device busy in the window, averaged over chips
    program_busy_s: float  # busy inside the search program's executions
    checks: list  # dicts: wall_s, busy_s, generated, unique, steps
    setup: dict  # Events.summary of set-up
    lanes: int
    peak_bytes_per_s: float
    breakdown: dict = field(default_factory=dict)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_record(n_chips: int) -> dict:
    import jax

    devices = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def reduce_trace(path: str, records: list, program: str):
    """(window_s, busy_s, program_busy_s, per-check busy_s, breakdown)
    from the trace, on the trace's own clock; the window is the
    benchmark's `bench.window` span."""
    t = trace.read_xplane(path, span_names=set(SPANS))
    win = [s for s in t.spans if s[2] == "bench.window"]
    if not win or not t.ops:
        raise RuntimeError(f"trace {path} holds no window span or no device ops")
    lo, hi = win[0][0], win[0][1]
    checks = sorted(s for s in t.spans if s[2] == "bench.check")
    busy = program_busy = 0.0
    per_check = [0.0] * len(checks)
    self_ns: dict = {}
    idle: list = []
    for ops, mods in zip(t.ops, t.modules):
        merged = trace.union(ops)
        busy += trace.covered(merged, lo, hi)
        runs = [m for m in mods if m[2].startswith(program)]
        program_busy += trace.covered(
            trace.union(trace.within(ops, runs)), lo, hi)
        for i, (s, e, _) in enumerate(checks):
            per_check[i] += trace.covered(merged, s, e)
        for name, ns in trace.self_times(trace.within(ops, [(lo, hi)])).items():
            self_ns[name] = self_ns.get(name, 0) + ns
        idle += trace.gaps(merged, lo, hi)
    n = len(t.ops)
    spans = [s for s in t.spans if s[2] != "bench.window"]
    idle.sort(key=lambda g: g[0] - g[1])
    breakdown = {
        "device_ops": [[k, v / n] for k, v in trace.top(self_ns)],
        "idle_gaps": [[trace.innermost(spans, (s + e) / 2), (e - s) / 1e9 / n]
                      for s, e in idle[:10]],
    }
    if len(checks) != len(records):
        raise RuntimeError(f"trace holds {len(checks)} check spans for "
                           f"{len(records)} checks")
    return ((hi - lo) / 1e9, busy / n / 1e9, program_busy / n / 1e9,
            [b / n / 1e9 for b in per_check], breakdown)


def run_cell(cell, seed: int, seconds: float, traced: bool, t0: float) -> dict:
    """The run's result line (a dict) for `cell`. `t0` is the monotonic
    time at which the process started. `seed` changes nothing: a check's
    work is fixed by the configuration and the mix (PERF.md)."""
    from stateright_tpu.compile_cache import use_compile_cache

    config, traffic = cell.config, cell.traffic
    log(f"cell {cell.name}: seed {seed} (the work does not depend on it), "
        f"{seconds} s, trace {int(traced)}")
    log(f"compile cache: {use_compile_cache() or 'JAX_COMPILATION_CACHE_DIR'}")
    # Keep every program in the persistent cache, the small eager ones of
    # path replay too, so that a warm set-up compiles nothing.
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    make_model = model_factory(config)
    with Events() as events:
        warm = run_check(make_model, config, traffic,
                         depth=config["warmup_max_depth"])
        setup_end = time.monotonic()
        setup_s = setup_end - t0
        if warm.error or not warm.paths:
            log(f"WARNING: the warm-up check found no discovery to "
                f"reconstruct ({warm.error}), so path replay may compile "
                "inside the window; raise warmup_max_depth")
        setup_events = events.summary(0.0, setup_end)
        log(f"set-up: {setup_s} s; warm-up check to depth "
            f"{config['warmup_max_depth']}: {warm.wall_s} s; events "
            f"{setup_events}")
        tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
        try:
            records = _window(make_model, config, traffic, seconds, tmp)
            w0, w1 = records[0].start, records[-1].end
            win_events = events.summary(w0, w1)
            device = device_record(cell.chips)
            reading = None
            if traced:
                reading = Reading(
                    **_reduce(tmp, records, config["program"]),
                    setup=setup_events, lanes=config["lanes"],
                    peak_bytes_per_s=roofline.peak_bytes_per_s(device["kind"]))
        finally:
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)
    window_s = w1 - w0
    for i, r in enumerate(records):
        log(f"check {i}: {r.wall_s} s generated={r.generated} "
            f"unique={r.unique} depth={r.depth} steps={r.steps} "
            f"discoveries={ {k: len(v) for k, v in r.paths.items()} }"
            + (f" error={r.error}" if r.error else ""))
    log(f"window: {window_s} s, {len(records)} checks; in the window: "
        f"{win_events}")
    if win_events["cache_misses"]:
        log(f"HARNESS FAULT: {win_events['cache_misses']} persistent-cache "
            "misses inside the window (set-up did not compile everything)")

    ref = reference.bfs(config["reference"])
    invalid = reference.check_paths(config["reference"],
                                    compare.paths_of(records))
    numbers, failed = compare.compare(config, traffic, records, ref, invalid)

    done = [r for r in records if not r.error]
    metrics = {}
    if traced:
        for m in cell.per_layer:
            v = m.read(reading)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        device.update(busy_s=reading.busy_s, window_s=reading.window_s)
    else:
        values = {
            "setup_s": setup_s,
            "states_per_s": sum(r.generated for r in done) / window_s,
            "verdict_s": window_s / max(len(done), 1),
        }
        for m in cell.end_to_end:
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    result = {"correct": all(v <= lim for v, lim in numbers.values()),
              "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = reading.breakdown
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in numbers.items()}
    return result


def _window(make_model, config: dict, traffic: dict, seconds: float,
            tmp) -> list:
    """The measured window; with `tmp`, under the profiler (trace written
    there) and with the program's host spans on the trace's clock."""
    import jax

    if tmp is None:
        return run_window(seconds, lambda: run_check(make_model, config,
                                                     traffic))
    host_spans = os.path.join(tmp, "spans.json")
    jax.profiler.start_trace(tmp, profiler_options=trace.profile_options())
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            return run_window(seconds, lambda: run_check(
                make_model, config, traffic, trace_out=host_spans,
                annotate=True))
    finally:
        jax.profiler.stop_trace()


def _reduce(tmp: str, records: list, program: str) -> dict:
    window_s, busy, program_busy, per_check, breakdown = reduce_trace(
        trace.find_xplane(tmp), records, program)
    checks = [{"wall_s": r.wall_s, "busy_s": b, "generated": r.generated,
               "unique": r.unique, "steps": r.steps}
              for r, b in zip(records, per_check) if not r.error]
    return {"window_s": window_s, "busy_s": busy,
            "program_busy_s": program_busy, "checks": checks,
            "breakdown": breakdown}
