"""jaxpr auditor: abstract-trace an engine step and pin what the compiler
will actually run.

`jax.make_jaxpr` over the engines' own jitted step kernels with
`ShapeDtypeStruct` operands (CPU-cheap, no device, no data) yields the
exact program XLA receives. This module walks that jaxpr and turns three
classes of silent regression into named, located findings:

- **forbidden ops** — host callbacks (`pure_callback`/`io_callback`/
  `debug_callback`), infeed/outfeed, and in-graph `device_put` transfers
  have no business inside a step region: each is a host round trip per
  step (SURVEY §7's device-residency argument);
- **full-carry gathers** — the r8 regression class: a gather whose
  operand is a whole carry-sized array and whose output moves most of it
  (881 KB/event over PCIe before r8 hand-profiled it). Flagged when the
  operand exceeds ``operand_budget`` bytes AND the output moves more than
  ``gather_frac`` of it — bucket-row probe gathers (big output, small
  operand) stay legal;
- **accidental f64** — any float64 intermediate (the engines are
  u32-native; an f64 is always an upcast leak).

It also accumulates per-step FLOP/byte/transfer totals that
``analysis/anchors.py`` cross-checks against `tensor/costmodel.py` and
tests pin as budgets — a future edit that re-introduces a giant gather
fails CI with an op name and source line, not a slow benchmark three
rounds later.

Accounting model (deterministic, compiler-naive by design): every eqn
reads its operands and writes its outputs once (`bytes`); `flops` uses a
small per-primitive table (elementwise = output size, reductions = input
size, sorts = n log n per operand). `while` bodies count ONCE — the
engines' search loop body is exactly one step, so "loop body once" IS the
per-step cost; `scan` bodies multiply by trip count. XLA fusion makes the
absolute byte number an over-estimate of HBM traffic — budgets pin the
*trend*, the cross-check pins the *order of magnitude*.
"""

from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass, field
from typing import Optional

import jax

from jax._src import source_info_util as _siu
from jax.extend import core as jcore

#: primitives that are host round trips — never legal inside a step.
CALLBACK_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "host_callback_call",
    "outside_call",
    "infeed",
    "outfeed",
}

#: in-graph host<->device transfers (legal at trace boundaries only).
TRANSFER_PRIMS = {"device_put", "copy_to_host_async"}

#: one-flop-per-output-element primitives.
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "abs",
    "max", "min", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "lt", "le",
    "gt", "ge", "select_n", "exp", "log", "tanh", "erf", "rsqrt", "sqrt",
    "floor", "ceil", "round", "sign", "clamp", "population_count", "clz",
    "nextafter", "logistic", "square",
}

#: input-sized primitives (reductions).
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "argmax", "argmin", "cumsum", "cummax", "cummin",
    "cumprod", "cumlogsumexp", "reduce_precision",
}


@dataclass(frozen=True)
class Violation:
    rule: str  # "callback" | "transfer" | "full-carry-gather" | "f64"
    op: str  # primitive name
    location: str  # "file.py:line" best-effort from eqn source info
    detail: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.op} at {self.location}: {self.detail}"


@dataclass
class AuditTotals:
    flops: int = 0
    hbm_bytes: int = 0
    ops: Counter = field(default_factory=Counter)

    def add(self, other: "AuditTotals") -> None:
        self.flops += other.flops
        self.hbm_bytes += other.hbm_bytes
        self.ops.update(other.ops)


@dataclass
class AuditReport:
    name: str
    totals: AuditTotals  # whole kernel, while bodies once
    step: AuditTotals  # largest while body (the search loop); == totals
    #                    when the kernel has no loop (frontier's step fn)
    violations: list
    in_bytes: int  # kernel operand footprint
    out_bytes: int  # kernel result footprint
    transfer_bytes: int  # host-resident operands re-uploaded per dispatch

    @property
    def clean(self) -> bool:
        return not self.violations

    def summary(self) -> dict:
        """Flat JSON-able row (bench / smoke output)."""
        return {
            "name": self.name,
            "step_flops": self.step.flops,
            "step_hbm_bytes": self.step.hbm_bytes,
            "total_hbm_bytes": self.totals.hbm_bytes,
            "in_bytes": self.in_bytes,
            "out_bytes": self.out_bytes,
            "transfer_bytes": self.transfer_bytes,
            "gathers": self.step.ops.get("gather", 0),
            "scatters": sum(
                n for p, n in self.step.ops.items() if p.startswith("scatter")
            ),
            "violations": [str(v) for v in self.violations],
        }


def _aval_bytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:  # tokens / abstract units
        return 0
    return int(size) * dtype.itemsize


def _loc(eqn) -> str:
    frame = _siu.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "unknown"
    return f"{frame.file_name}:{frame.start_line}"


def _sub_jaxprs(params: dict):
    """(key, ClosedJaxpr) pairs nested in an eqn's params (pjit `jaxpr`,
    while `cond_jaxpr`/`body_jaxpr`, cond `branches`, scan `jaxpr`, custom
    call wrappers)."""
    for key, val in params.items():
        if isinstance(val, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            yield key, val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    yield key, item


def _raw(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    out_size = sum(int(getattr(v.aval, "size", 0)) for v in eqn.outvars)
    in_size = sum(int(getattr(v.aval, "size", 0)) for v in eqn.invars)
    if name in _ELEMENTWISE or name == "convert_element_type":
        return out_size
    if name in _REDUCTIONS or name.startswith("reduce_"):
        return in_size
    if name == "sort":
        n = max(
            (int(getattr(v.aval, "size", 0)) for v in eqn.invars), default=0
        )
        return int(in_size * math.log2(max(n, 2)))
    if name == "dot_general":
        # 2 * output * contracted-dim; rare in this codebase.
        (contract, _), _ = eqn.params["dimension_numbers"]
        k = 1
        for d in contract:
            k *= eqn.invars[0].aval.shape[d]
        return 2 * out_size * k
    return 0


def _pallas_call_bytes(eqn) -> int:
    """HBM traffic of one `pallas_call`: per-operand
    max(array bytes, block bytes x grid steps). A PARTITIONED array
    streams itself exactly once (block x grid == array), while a
    REPLICATED block — constant index map, e.g. the fused Bloom summary
    riding into VMEM with every partition — re-streams its block every
    grid step, which the plain operand footprint would under-count by the
    grid factor. Falls back to the plain operand/result footprint when
    the (private) grid_mapping layout does not line up with the eqn's
    operands."""
    avals = [v.aval for v in eqn.invars] + [v.aval for v in eqn.outvars]
    plain = sum(_aval_bytes(a) for a in avals)
    gm = eqn.params.get("grid_mapping")
    try:
        steps = 1
        for g in gm.grid:
            steps *= int(g)
        bms = list(gm.block_mappings)
        if steps <= 1 or len(bms) != len(avals):
            return plain
        total = 0
        for aval, bm in zip(avals, bms):
            arr = _aval_bytes(aval)
            dtype = getattr(aval, "dtype", None)
            bshape = getattr(bm, "block_shape", None)
            if not bshape or dtype is None:
                total += arr
                continue
            belems = 1
            for d in bshape:
                belems *= int(d) if d is not None else 1
            total += max(arr, belems * dtype.itemsize * steps)
        return total
    except Exception:  # pragma: no cover - private-API drift tolerance
        return plain


class _Walker:
    def __init__(
        self,
        *,
        operand_budget: int,
        gather_frac: float,
        callbacks_forbidden: bool,
    ):
        self.operand_budget = operand_budget
        self.gather_frac = gather_frac
        self.callbacks_forbidden = callbacks_forbidden
        self.violations: list = []
        self.while_bodies: list = []  # AuditTotals per while body

    def walk(self, jaxpr) -> AuditTotals:
        totals = AuditTotals()
        for eqn in _raw(jaxpr).eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                # Learned op signature of the Pallas insert (r12): bill the
                # eqn as a LEAF via its grid-aware operand traffic (each
                # partitioned array streams through VMEM once per call,
                # replicated blocks like the fused Bloom summary once per
                # grid step — costmodel's `insert_stream`/`spill_probe`
                # terms; see _pallas_call_bytes). The kernel jaxpr is still
                # scanned for forbidden ops (callbacks, f64 leaks), but its
                # ref-level loads/stores are VMEM traffic — adding them to
                # the totals would double-bill every block — and its
                # internal probe/retry loops must not masquerade as the
                # engine's search-loop body in `step_mode="loop"`.
                n_wb = len(self.while_bodies)
                for _key, sub in _sub_jaxprs(eqn.params):
                    self.walk(sub)
                del self.while_bodies[n_wb:]
                totals.ops[name] += 1
                totals.hbm_bytes += _pallas_call_bytes(eqn)
                continue
            sub_totals = AuditTotals()
            is_while = name == "while"
            scale = 1
            if name == "scan":
                scale = int(eqn.params.get("length", 1))
            for key, sub in _sub_jaxprs(eqn.params):
                st = self.walk(sub)
                if is_while and key == "body_jaxpr":
                    self.while_bodies.append(st)
                sub_totals.add(st)
            if scale > 1:
                sub_totals.flops *= scale
                sub_totals.hbm_bytes *= scale
                for k in sub_totals.ops:
                    sub_totals.ops[k] *= scale
            totals.add(sub_totals)
            if any(True for _ in _sub_jaxprs(eqn.params)):
                # Container eqn (pjit/while/scan/cond): the cost lives in
                # its sub-jaxprs; counting its own operand footprint would
                # double-bill every loop-carried array — and its outvars
                # re-surface inner dtypes, so dtype checks would re-report
                # every inner f64 once per nesting level.
                self._check_forbidden(eqn, name, container=True)
                continue
            totals.ops[name] += 1
            totals.flops += _eqn_flops(eqn)
            totals.hbm_bytes += sum(
                _aval_bytes(v.aval) for v in eqn.invars
            ) + sum(_aval_bytes(v.aval) for v in eqn.outvars)
            self._check_forbidden(eqn, name)
        return totals

    def _check_forbidden(self, eqn, name: str, container: bool = False) -> None:
        if name in CALLBACK_PRIMS and self.callbacks_forbidden:
            self.violations.append(
                Violation(
                    "callback", name, _loc(eqn),
                    "host callback inside a step region — one host round "
                    "trip per step",
                )
            )
        elif name in TRANSFER_PRIMS:
            self.violations.append(
                Violation(
                    "transfer", name, _loc(eqn),
                    "in-graph host transfer inside a step region",
                )
            )
        elif name == "gather":
            operand = _aval_bytes(eqn.invars[0].aval)
            moved = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            if (
                operand >= self.operand_budget
                and moved >= self.gather_frac * operand
            ):
                self.violations.append(
                    Violation(
                        "full-carry-gather", name, _loc(eqn),
                        f"gather moves {moved} B of a {operand} B operand "
                        f"(>= {self.gather_frac:.0%}) — the r8 regression "
                        "class; gather a bounded window instead",
                    )
                )
        if container:
            return
        for v in eqn.outvars:
            dtype = getattr(v.aval, "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                self.violations.append(
                    Violation(
                        "f64", name, _loc(eqn),
                        "float64 intermediate — the engines are u32-native; "
                        "an f64 is an accidental promotion "
                        "(check jax_enable_x64 and python-float literals)",
                    )
                )


def audit_fn(
    fn,
    args: tuple,
    *,
    name: str = "step",
    kwargs: Optional[dict] = None,
    host_slots: tuple = (),
    step_mode: str = "loop",
    operand_budget: int = 1 << 20,
    gather_frac: float = 0.75,
    callbacks_forbidden: bool = True,
) -> AuditReport:
    """Abstractly trace `fn(*args)` (ShapeDtypeStruct operands — no device
    work) and audit the jaxpr. `host_slots` are indices into `args` the
    host re-uploads every dispatch (the per-step PCIe floor reported as
    `transfer_bytes`). `step_mode` picks what `report.step` means:
    "loop" (chunked engines — the largest while body IS one search step)
    or "total" (per-batch kernels like the frontier step, whose only
    internal while is the insert chain-overflow loop).
    `operand_budget`/`gather_frac` tune the full-carry gather rule (see
    module docstring)."""
    if step_mode not in ("loop", "total"):
        raise ValueError(f"step_mode must be 'loop' or 'total', got {step_mode!r}")
    jaxpr = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    walker = _Walker(
        operand_budget=operand_budget,
        gather_frac=gather_frac,
        callbacks_forbidden=callbacks_forbidden,
    )
    totals = walker.walk(jaxpr)
    step = (
        max(walker.while_bodies, key=lambda t: t.hbm_bytes, default=totals)
        if step_mode == "loop"
        else totals
    )
    flat_in, _ = jax.tree.flatten((args, kwargs or {}))
    in_bytes = sum(_aval_bytes(a) for a in flat_in)
    out_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.jaxpr.outvars)
    host = jax.tree.flatten(tuple(args[i] for i in host_slots))[0]
    return AuditReport(
        name=name,
        totals=totals,
        step=step,
        violations=walker.violations,
        in_bytes=in_bytes,
        out_bytes=out_bytes,
        transfer_bytes=sum(_aval_bytes(a) for a in host),
    )
