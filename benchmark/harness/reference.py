"""The plain reference (`reference/<source>`, C++ and independent of the
program under test): built once per checkout into `.build/`, run after the
window has closed. It gives the counts, the least depth at which each
property is discovered, and checks discovery paths step by step.

A configuration's file names its reference under "reference":
  {"source": "bfs_ref.cpp", "model": ["paxos", 3, 14],
   "encoder": "paxos", "properties": ["linearizable", ...]}
`reference/encode_<encoder>.py` turns a decoded state of the program's
answer into the reference's fields (its `encode(state) -> list[int]`)."""

from __future__ import annotations

import hashlib
import importlib.util
import os
import platform
import subprocess
from dataclasses import dataclass

from .spec import BENCH_DIR

_FLAGS = ["-std=c++17", "-O2", "-pthread"]
_BUILD = os.path.join(BENCH_DIR, ".build")


class ReferenceError(Exception):
    """The reference could not be built or run, or said something malformed."""


def binary(source: str) -> str:
    """Path of the built reference, compiled on first use. Named by a hash
    of the source, the flags and the machine type, so an edit rebuilds."""
    src = os.path.join(BENCH_DIR, "reference", source)
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS + [platform.machine()]).encode())
    out = os.path.join(_BUILD, f"{source.rsplit('.', 1)[0]}-{h.hexdigest()[:16]}")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD, exist_ok=True)
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(["g++", *_FLAGS, "-o", tmp, src],
                           capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            raise ReferenceError(f"g++ failed on {src}: {r.stderr[-2000:]}")
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


@dataclass
class Exhaustive:
    """What the reference's BFS found."""

    generated: int
    unique: int
    depth: int
    min_depth: dict  # property name -> least depth discovered (absent: never)
    levels: list  # per depth from 1: (unique at that depth, generated by expanding them)

    @property
    def discovered(self) -> set:
        return set(self.min_depth)

    def through(self, depth: int) -> tuple:
        """(generated, unique) of a BFS that has expanded every level up to
        `depth` and stopped: the init states and their successors, and
        every state up to `depth` + 1."""
        inits = self.generated - sum(g for _, g in self.levels)
        return (inits + sum(g for _, g in self.levels[:depth]),
                sum(u for u, _ in self.levels[:depth + 1]))


def _run(ref: dict, mode: str, extra: list, stdin: str = None) -> str:
    cmd = [binary(ref["source"]), mode, *map(str, ref["model"]), *map(str, extra)]
    r = subprocess.run(cmd, input=stdin, capture_output=True, text=True,
                       timeout=600)
    if r.returncode != 0:
        raise ReferenceError(f"{' '.join(cmd)} exited {r.returncode}: "
                             f"{r.stderr[-2000:]}")
    return r.stdout


def bfs(ref: dict, fp_bits: int = 64, salt: int = 0,
        threads: int = None) -> Exhaustive:
    """Exhaustive BFS. `fp_bits` < 64 dedups by a fingerprint cut to that
    width and salted by `salt`: the control."""
    threads = threads or os.cpu_count() or 1
    out = _run(ref, "bfs", [threads, fp_bits, salt])
    lines = out.split("\n")
    head = dict(kv.split("=") for kv in lines[0].split())
    names = ref["properties"]
    min_depth = {}
    levels = []
    for ln in lines[1:]:
        parts = ln.split()
        if parts[:1] == ["prop"] and int(parts[2]):
            min_depth[names[int(parts[1])]] = int(parts[2])
        elif parts[:1] == ["level"]:
            levels.append((int(parts[2]), int(parts[3])))
    return Exhaustive(int(head["generated"]), int(head["unique"]),
                      int(head["depth"]), min_depth, levels)


def encoder(ref: dict):
    """`encode` of `reference/encode_<encoder>.py`."""
    path = os.path.join(BENCH_DIR, "reference", f"encode_{ref['encoder']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_encode_{ref['encoder'].replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.encode


def check_paths(ref: dict, paths: list) -> list:
    """Invalid-step counts, one per (property name, [decoded states]) in
    `paths`: a first state that is no init state, a step that is no
    transition, a last state that does not discover the property, and a
    state that does not decode each count 1."""
    if not paths:
        return []
    encode = encoder(ref)
    names = ref["properties"]
    lines = [str(len(paths))]
    for name, states in paths:
        lines.append(f"{names.index(name) if name in names else -1} "
                     f"{len(states)}")
        for s in states:
            lines.append(" ".join(str(int(x)) for x in encode(s)))
    out = _run(ref, "path", [], stdin="\n".join(lines) + "\n")
    return [int(ln.split()[0]) for ln in out.strip().split("\n")]
