"""Step-region inference: which project functions run TRACED (inside `jit`
or a `lax.while_loop`/`scan` body) — the scope of srlint's no-host-sync rule.

The repo's invariant (SURVEY §7, ROADMAP r8 notes) is prose today: "nothing
host-syncs mid-loop". This module makes it mechanical. A function is a
**step-region root** when any of these hold:

- it is decorated with ``@jax.jit`` or ``@partial(jax.jit, ...)``;
- it is passed through ``jax.jit(f)`` / ``jax.vmap(f)`` / ``shard_map(f,
  ...)`` anywhere in its module (including nests like
  ``jax.jit(jax.vmap(f))`` and re-binding assignments ``f = jax.jit(f)``);
- it is passed as a function argument to ``jax.lax.while_loop`` /
  ``fori_loop`` / ``scan`` / ``cond`` / ``switch`` (lambda arguments count:
  calls made inside such a lambda are attributed to the lambda's enclosing
  function, which is how the engines' ``lambda c: body(c, ...)`` loop
  wrappers are followed);
- its ``def`` line (or the line above) carries a ``# srlint: step-region``
  marker — the explicit annotation for functions reached only through
  data-driven dispatch the static pass cannot see (e.g. the hash-table
  insert implementations selected from an ``INSERT_VARIANTS`` dict).

The full region is the transitive closure of the project call graph from
those roots. Resolution is deliberately best-effort and *under*-approximate
where precision is impossible (dynamic dispatch), with two recall helpers:

- default-argument edges: ``def expand_insert(..., insert=_insert_impl)``
  adds an edge to ``_insert_impl`` (the callee is invoked through the
  parameter);
- duck edges: an attribute call ``x.expand(...)`` links to every project
  *method* named ``expand`` unless the name is a common container/stdlib
  verb (``append``, ``get``, ...) — this is what pulls the tensor models'
  ``expand``/``within_boundary`` kernels into the region.
"""

from __future__ import annotations

import ast
import io
import tokenize
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterator, Optional

#: jax entry points whose function-valued arguments run traced.
TRACED_HOFS = {
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.scan",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.associative_scan",
    "jax.vmap",
    "jax.pmap",
    "jax.shard_map",
    "jax.checkpoint",
    "jax.remat",
}

#: wrappers where wrapper(f) means f runs traced when the result is called.
TRACED_WRAPPERS = {"jax.jit", "jax.pmap", "jax.vmap"} | TRACED_HOFS

#: attribute-call names NEVER duck-resolved (common container/stdlib verbs
#: that would otherwise alias project methods and flood the region).
DUCK_DENYLIST = {
    "append", "appendleft", "add", "get", "items", "keys", "values", "pop",
    "popleft", "close", "update", "join", "run", "read", "write", "clear",
    "copy", "extend", "sum", "mean", "max", "min", "any", "all", "reshape",
    "astype", "set", "split", "strip", "encode", "decode", "format",
    "register", "fresh", "stats", "metrics", "summary", "drain", "put",
    "insert", "search", "checkpoint", "flat", "tobytes", "item",
}

STEP_REGION_MARKER = "step-region"


@dataclass
class FuncInfo:
    module: str  # dotted module name
    qualname: str  # e.g. "FrontierSearch._build_step.step"
    node: ast.AST  # FunctionDef / AsyncFunctionDef
    cls: Optional[str]  # enclosing class name, if a method
    calls: set = field(default_factory=set)  # resolved callee ids
    duck_calls: set = field(default_factory=set)  # bare attr-call names
    is_root: bool = False
    root_reason: str = ""


@dataclass
class ModuleIndex:
    module: str
    path: Path
    tree: ast.Module
    source: str
    comments: dict  # line -> (comment text after "#", standalone?)
    import_map: dict  # local alias -> dotted target
    funcs: dict  # qualname -> FuncInfo


@dataclass
class Project:
    modules: dict  # dotted module name -> ModuleIndex
    methods_by_name: dict  # bare method name -> [(module, qualname)]

    def func(self, module: str, qualname: str) -> Optional[FuncInfo]:
        m = self.modules.get(module)
        return m.funcs.get(qualname) if m else None


def _comments_of(source: str) -> dict:
    out: dict = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                standalone = not tok.line[: tok.start[1]].strip()
                out[tok.start[0]] = (
                    tok.string.lstrip("#").strip(), standalone,
                )
    except tokenize.TokenError:  # pragma: no cover — ast.parse passed
        pass
    return out


def srlint_tokens(comments: dict, line: int) -> list:
    """`srlint:` directives attached to `line`: its own trailing comment
    plus a STANDALONE comment on the line directly above. A trailing
    comment on the previous code line annotates that line only — otherwise
    one annotation would silently allowlist its neighbour below. Returns
    the raw directive strings (text after "srlint:")."""
    out = []
    for ln, need_standalone in ((line, False), (line - 1, True)):
        c, standalone = comments.get(ln, ("", False))
        if c.startswith("srlint:") and (standalone or not need_standalone):
            out.append(c[len("srlint:"):].strip())
    return out


def module_name_for(path: Path, root: Path) -> str:
    try:
        rel = path.resolve().relative_to(root.resolve())
    except ValueError:
        return path.stem
    parts = list(rel.with_suffix("").parts)
    if parts[-1] == "__init__":
        parts = parts[:-1] or [rel.parts[0]]
    return ".".join(parts)


def _build_import_map(
    tree: ast.Module, module: str, is_pkg: bool = False,
) -> dict:
    """alias -> dotted target for every import in the module (including
    function-local imports — the engines import store helpers inside
    builder functions)."""
    out: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                full = module.split(".")
                # In a package __init__ the dotted name (with "__init__"
                # already stripped) names the package itself, so level 1
                # means "this package", not the parent.
                strip = node.level - 1 if is_pkg else node.level
                base = full[: len(full) - strip] if strip else full
                prefix = ".".join(
                    base + ([node.module] if node.module else [])
                )
            else:
                prefix = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = (
                    f"{prefix}.{a.name}" if prefix else a.name
                )
    return out


def _dotted(node: ast.AST, import_map: dict) -> Optional[str]:
    """Best-effort dotted name of an expression ('jax.lax.while_loop'),
    resolving the leading alias through the import map."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(import_map.get(node.id, node.id))
    return ".".join(reversed(parts))


def _own_defs(stmts) -> Iterator:
    """FunctionDefs belonging directly to this body: top-level defs plus
    defs nested in non-def statements (if/try/with), but NOT defs inside
    other defs (those belong to the inner scope)."""
    for st in stmts:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield st
        elif isinstance(st, ast.ClassDef):
            continue  # handled as a class scope by the caller
        else:
            stack = [st]
            while stack:
                n = stack.pop()
                for child in ast.iter_child_nodes(n):
                    if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        yield child
                    elif not isinstance(child, ast.ClassDef):
                        stack.append(child)


def _walk_stop_at_defs(node: ast.AST) -> Iterator:
    """Yield descendants of `node`, not descending into nested function
    defs (lambdas ARE descended — their calls belong to the enclosing
    function)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.append(child)


class _Collector:
    def __init__(self, mi: ModuleIndex):
        self.mi = mi

    def process(self) -> None:
        self._body(self.mi.tree.body, scopes=[], cls=None)

    def _body(self, stmts, scopes, cls) -> None:
        for node in _own_defs(stmts):
            self._func(node, scopes, cls)
        for st in stmts:
            if isinstance(st, ast.ClassDef):
                self._body(st.body, scopes, st.name)

    def _func(self, node, scopes, cls) -> None:
        prefix = ([cls] if cls else []) + scopes
        qual = ".".join(prefix + [node.name])
        fi = FuncInfo(self.mi.module, qual, node, cls)
        self.mi.funcs[qual] = fi
        # `# srlint: step-region` marker on/above the def line.
        for d in srlint_tokens(self.mi.comments, node.lineno):
            if d.split()[:1] == [STEP_REGION_MARKER]:
                fi.is_root = True
                fi.root_reason = "marker"
        # Decorators: @jax.jit / @partial(jax.jit, ...).
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            dn = _dotted(target, self.mi.import_map)
            if dn in TRACED_WRAPPERS:
                fi.is_root = True
                fi.root_reason = fi.root_reason or dn
            elif (
                isinstance(dec, ast.Call)
                and dn in ("functools.partial", "partial")
                and dec.args
            ):
                inner = _dotted(dec.args[0], self.mi.import_map)
                if inner in TRACED_WRAPPERS:
                    fi.is_root = True
                    fi.root_reason = fi.root_reason or inner
        # Default-argument edges (callee invoked through the parameter).
        args = node.args
        for default in list(args.defaults) + [
            d for d in args.kw_defaults if d is not None
        ]:
            if isinstance(default, ast.Name):
                fi.calls.add(self._resolve_name(default.id, scopes, cls))
        # Calls in this function's own statements (stopping at nested defs).
        inner_scopes = prefix + [node.name]
        for st in node.body:
            for sub in _walk_stop_at_defs(st):
                if isinstance(sub, ast.Call):
                    self._record_call(sub, fi, inner_scopes, cls)
        # Recurse into nested defs (not methods — cls does not propagate).
        self._body(node.body, inner_scopes, None)

    def _resolve_name(self, name, scopes, cls) -> str:
        # Innermost enclosing scope that defines `name` as a def wins.
        for i in range(len(scopes), -1, -1):
            qual = ".".join(scopes[:i] + [name])
            if qual in self.mi.funcs:
                return f"{self.mi.module}:{qual}"
        if cls and f"{cls}.{name}" in self.mi.funcs:
            return f"{self.mi.module}:{cls}.{name}"
        target = self.mi.import_map.get(name)
        if target:
            return target
        return f"{self.mi.module}:{name}"

    def _record_call(self, call, fi, scopes, cls) -> None:
        f = call.func
        if isinstance(f, ast.Name):
            fi.calls.add(self._resolve_name(f.id, scopes, cls))
        elif isinstance(f, ast.Attribute):
            if (
                isinstance(f.value, ast.Name)
                and f.value.id in ("self", "cls")
                and fi.cls
            ):
                fi.calls.add(f"{self.mi.module}:{fi.cls}.{f.attr}")
            else:
                dn = _dotted(f, self.mi.import_map)
                if dn:
                    fi.calls.add(dn)
                if f.attr not in DUCK_DENYLIST:
                    fi.duck_calls.add(f.attr)


def _scan_traced_uses(mi: ModuleIndex) -> None:
    """Mark functions passed through jit/vmap/shard_map/while_loop-style
    call sites anywhere in the module (re-binding assignments included)."""

    by_name: dict = {}
    for fi in mi.funcs.values():
        by_name.setdefault(fi.node.name, []).append(fi)

    def mark(arg, reason) -> None:
        if isinstance(arg, ast.Name):
            for fi in by_name.get(arg.id, ()):
                fi.is_root = True
                fi.root_reason = fi.root_reason or reason
        elif isinstance(arg, ast.Call):
            dn = _dotted(arg.func, mi.import_map)
            if dn in TRACED_WRAPPERS:
                for a in arg.args:
                    mark(a, dn)

    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        dn = _dotted(node.func, mi.import_map)
        if dn in TRACED_WRAPPERS and dn not in TRACED_HOFS:
            # jax.jit(f) / jax.jit(jax.vmap(f))
            for arg in node.args[:1]:
                mark(arg, dn)
        elif dn in TRACED_HOFS:
            for arg in node.args:
                mark(arg, dn)
        elif dn in ("functools.partial", "partial") and node.args:
            inner = _dotted(node.args[0], mi.import_map)
            if inner in TRACED_WRAPPERS:
                for arg in node.args[1:]:
                    mark(arg, inner)
        elif isinstance(node.func, ast.Call):
            # partial(jax.jit, ...)(chunk_k)
            inner_dn = _dotted(node.func.func, mi.import_map)
            if (
                inner_dn in ("functools.partial", "partial")
                and node.func.args
            ):
                wrapped = _dotted(node.func.args[0], mi.import_map)
                if wrapped in TRACED_WRAPPERS:
                    for arg in node.args:
                        mark(arg, wrapped)


def build_project(paths: list, root: Path) -> Project:
    modules: dict = {}
    for path in paths:
        path = Path(path)
        try:
            source = path.read_text()
            tree = ast.parse(source)
        except (OSError, SyntaxError):
            continue
        module = module_name_for(path, root)
        mi = ModuleIndex(
            module=module,
            path=path,
            tree=tree,
            source=source,
            comments=_comments_of(source),
            import_map=_build_import_map(
                tree, module, is_pkg=path.name == "__init__.py",
            ),
            funcs={},
        )
        _Collector(mi).process()
        _scan_traced_uses(mi)
        modules[module] = mi

    methods_by_name: dict = {}
    for mi in modules.values():
        for qual, fi in mi.funcs.items():
            if fi.cls is not None:
                methods_by_name.setdefault(fi.node.name, []).append(
                    (mi.module, qual)
                )
    return Project(modules=modules, methods_by_name=methods_by_name)


def step_region(project: Project) -> set:
    """The set of (module, qualname) pairs reachable from step-region
    roots through the project call graph."""
    region: set = set()
    work = [
        (mi.module, qual)
        for mi in project.modules.values()
        for qual, fi in mi.funcs.items()
        if fi.is_root
    ]

    def resolve(callee: str) -> list:
        out = []
        if ":" in callee:  # module-local form "pkg.mod:Qual.name"
            mod, qual = callee.split(":", 1)
            mi = project.modules.get(mod)
            if mi and qual in mi.funcs:
                out.append((mod, qual))
            return out
        # Dotted import form "stateright_tpu.tensor.frontier.expand_insert"
        parts = callee.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:cut])
            mi = project.modules.get(mod)
            if mi is None:
                continue
            qual = ".".join(parts[cut:])
            if qual in mi.funcs:
                out.append((mod, qual))
            else:
                tail = parts[-1]
                out.extend(
                    (mod, q)
                    for q, fi in mi.funcs.items()
                    if fi.node.name == tail and fi.cls is None
                )
            break
        return out

    while work:
        key = work.pop()
        if key in region:
            continue
        region.add(key)
        fi = project.func(*key)
        if fi is None:
            continue
        for callee in fi.calls:
            work.extend(c for c in resolve(callee) if c not in region)
        for duck in fi.duck_calls:
            work.extend(
                c
                for c in project.methods_by_name.get(duck, ())
                if c not in region
            )
    return region
