"""Native (C++) runtime components, loaded via ctypes.

The reference implements its whole runtime natively (Rust); here the compute
path is JAX/XLA on device, and the host-side hot spots that remain CPU-bound
get C++ implementations compiled on first use with the toolchain baked into
the image (no pybind11 — plain C ABI + ctypes). Everything has a pure-Python
fallback, so a missing compiler degrades performance, never correctness.

Artifacts are cached next to the sources in `build/`, named by a hash of
the source text, the compiler flags and the host (`-march=native` code
built on one machine may not run on another), so repeat imports don't pay
the compile and a tree copied to another host never reuses a stale build.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "build")
_lock = threading.Lock()
_cache: dict = {}


def _host_id() -> bytes:
    """What a native build depends on besides its source and flags: the
    machine and its CPU model and feature flags."""
    parts = [platform.machine(), platform.node()]
    try:
        with open("/proc/cpuinfo") as f:
            parts += sorted(
                {ln for ln in f if ln.startswith(("model name", "flags"))}
            )
    except OSError:
        pass
    return "\n".join(parts).encode()


def _flags(exe: bool) -> list:
    if exe:
        return ["-O3", "-march=native", "-pthread"]
    return ["-O2", "-shared", "-fPIC"]


def artifact_path(name: str, *, exe: bool = False) -> str:
    """Where `build` keeps `<name>.cpp`'s artifact: named by a hash of the
    source text, the compiler flags and `_host_id()`."""
    h = hashlib.sha256()
    with open(os.path.join(_DIR, f"{name}.cpp"), "rb") as f:
        h.update(f.read())
    h.update(" ".join(_flags(exe)).encode())
    h.update(_host_id())
    return os.path.join(
        _BUILD, f"{name}-{h.hexdigest()[:16]}" + ("" if exe else ".so")
    )


def build(name: str, *, exe: bool = False, timeout: float = 300.0) -> str:
    """Compile `<name>.cpp` into the build cache (see `artifact_path`) and
    return the artifact path. `exe=False` builds a shared object for
    ctypes; `exe=True` builds a standalone optimized executable (used by
    the bench harness for the CPU baseline checker)."""
    src = os.path.join(_DIR, f"{name}.cpp")
    out = artifact_path(name, exe=exe)
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD, exist_ok=True)
    # Per-process temp name so concurrent compiles can't interleave output;
    # os.replace makes the publish atomic.
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-std=c++17", *_flags(exe), "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=timeout,
        )
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


def _compile(name: str) -> str:
    return build(name)


def load(name: str):
    """ctypes.CDLL for `<name>.cpp`, compiled on demand; None when the
    toolchain is unavailable (callers fall back to Python)."""
    with _lock:
        if name in _cache:
            return _cache[name]
        try:
            lib = ctypes.CDLL(_compile(name))
        except (OSError, subprocess.CalledProcessError, FileNotFoundError):
            lib = None
        _cache[name] = lib
        return lib
