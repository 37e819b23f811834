"""span_breakdown.py for a multi-chip cell: the same run and the same JSON
line, read by the sharded engine's spans (`sharded.search` and its four
stages) and the named scopes of its step (`exchange` and `sync` besides
the one-chip step's), with device busy, program time and the self time
per scope averaged over the chips. A measurement for PERF.md on the chip,
not a benchmark run.

On a four-chip TPU host, from the checkout's root:

    python3 benchmark/tests/span_breakdown_sharded.py --workload 2pc9.sharded4 \\
        --plain 0 --tracer 0 --out <dir>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import span_breakdown  # noqa: E402
from span_breakdown import BENCH_SPANS, attribution, trace  # noqa: E402

STAGES = ("sharded.trace", "sharded.lower", "sharded.compile",
          "sharded.execute")
PROGRAM_SPANS = ("search.run", "sharded.search", *STAGES,
                 "checker.discoveries", "path.parent_map", "path.replay")
SCOPES = ("properties", "expand", "fingerprint", "exchange", "insert",
          "append", "sync")


def reduce_window(xplane: str, records: list, program: str) -> dict:
    t = trace.read_xplane(xplane, span_names=set(BENCH_SPANS + PROGRAM_SPANS))
    tf_ops = attribution.read_ops(xplane)
    n = len(t.ops)
    checks = sorted(s for s in t.spans if s[2] == "bench.check")
    (win,) = [s for s in t.spans if s[2] == "bench.window"]
    merged = [trace.union(ops) for ops in t.ops]
    out = []
    for (s, e, _), rec in zip(checks, records):
        inside = [x for x in t.spans if s <= x[0] and x[1] <= e]
        row = {"wall_s": (e - s) / 1e9, "steps": rec.steps,
               "busy_s": sum(trace.covered(m, s, e) for m in merged) / n / 1e9}
        for name in PROGRAM_SPANS:
            row[name] = attribution.span_seconds(inside, name)
        search = row["sharded.search"]
        row["stage_cover"] = (sum(row[x] for x in STAGES) / search
                              if search else None)
        replays = [x for x in inside if x[2] == "path.replay"]
        row["replay_programs"] = attribution.executions_within(t.modules[0],
                                                               replays)
        program_ns, by_scope = 0.0, {}
        for ops, mods, chip_tf in zip(t.ops, t.modules, tf_ops):
            runs = [m for m in mods
                    if m[2].startswith(program) and s <= m[0] < e]
            program_ns += trace.covered(trace.union(trace.within(ops, runs)),
                                        s, e)
            for k, v in attribution.scope_self_ns(chip_tf, SCOPES,
                                                  runs).items():
                by_scope[k] = by_scope.get(k, 0) + v
        if rec.steps:
            row["step_ms"] = program_ns / n / 1e6 / rec.steps
            row["kernel_ms"] = {k: v / n / 1e6 / rec.steps
                                for k, v in sorted(by_scope.items())}
        out.append(row)
    labeled = [(a, b, f"{name} [{attribution.scope_of(tf, SCOPES)}]")
               for (a, b, name), (_, _, tf) in zip(t.ops[0], tf_ops[0])]
    top = trace.top(trace.self_times(trace.within(labeled, [win[:2]])))
    spans = [x for x in t.spans if x[2] != "bench.window"]
    idle = sorted(trace.gaps(merged[0], win[0], win[1]),
                  key=lambda g: g[0] - g[1])
    gaps = [[trace.innermost(spans, (a + b) / 2), (b - a) / 1e9]
            for a, b in idle[:10]]
    window_s = (win[1] - win[0]) / 1e9
    busy = sum(trace.covered(m, *win[:2]) for m in merged) / n / 1e9
    return {"window_s": window_s, "chips": n,
            "idle_pct": 100 * (1 - busy / window_s),
            "checks": out, "device_ops_chip0": top, "idle_gaps_chip0": gaps}


if __name__ == "__main__":
    span_breakdown.reduce_window = reduce_window
    sys.exit(span_breakdown.main())
