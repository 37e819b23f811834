"""Reduction of a profiler trace to what the per-layer metrics read.

Copied in spirit from scripts/xplane_ops.py (PR 21's tree), with
`jax.profiler.ProfileData` as the reader so that nothing outside this
directory decides how a trace becomes a number:

- busy time: the union of the intervals in which an operation ran on the
  device (the "XLA Ops" line of each TPU plane), averaged over the chips;
- program time: the same union restricted to the executions of one jitted
  program (the "XLA Modules" line, by module name prefix);
- top operations by self time, under their HLO names;
- idle gaps inside the window, each named by the innermost host span open
  at its midpoint (the benchmark's own spans and the program's
  `TraceAnnotation`s share the host plane's clock with the device).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    ops: list = field(default_factory=list)  # per chip: [(start_ns, end_ns, name)]
    modules: list = field(default_factory=list)  # per chip: [(start, end, name)]
    spans: list = field(default_factory=list)  # host: [(start, end, name)]


def profile_options():
    """Profiler options for the traced run: the host's annotations (level
    1, where `TraceAnnotation`s are) but not the runtime's own, and no
    Python function tracer: either swamps a window of whole checks."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str, span_names=None) -> Trace:
    """Device ops and modules per chip, and host spans (all host events
    whose name is in `span_names`, or every host event when None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    t = Trace()
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if plane.name.startswith(DEVICE_PLANE):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            t.ops.append(ops)
            t.modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if span_names is None or e.name in span_names:
                        t.spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                        e.name))
    return t


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Nanoseconds of [lo, hi) that `merged` covers."""
    return float(sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged))


def within(intervals, windows) -> list:
    """The parts of `intervals` that fall inside any of `windows`."""
    wins = union(windows)
    out = []
    for s, e, *rest in intervals:
        for ws, we in wins:
            if s < we and e > ws:
                out.append((max(s, ws), min(e, we), *rest))
    return out


def gaps(merged, lo, hi) -> list:
    """[(start, end)] of [lo, hi) not covered by `merged`."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost(spans, t) -> str:
    """Name of the shortest span that contains instant `t` ("none")."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def self_times(ops) -> dict:
    """Self nanoseconds per op name: an op's duration less the parts that
    ops nested inside it cover (the trace nests a loop's body under it)."""
    out: dict = {}
    stack: list = []  # [start, end, name, child_ns]
    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            _close(stack.pop(), out)
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0])
    while stack:
        _close(stack.pop(), out)
    return out


def _close(frame, out):
    s, e, name, child = frame
    out[name] = out.get(name, 0) + max(0, (e - s) - child)


def top(d: dict, n: int = 10) -> list:
    return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda x: -x[1])[:n]]


def op_name(event_name: str) -> str:
    """An XLA op event's HLO instruction name and result shape, without
    layouts or operands: "%fusion.7 = u32[8,11]{0,1:T(8,128)} fusion(...)"
    gives "fusion.7 u32[8,11]"."""
    head, _, rest = event_name.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()[:100]
