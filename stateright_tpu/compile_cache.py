"""The compilation layer's two caches.

- Where JAX's persistent compilation cache lives: one rule for every entry
  point that turns it on (bench.py, chip_smoke.py, the scripts). The test
  suite keeps it off (tests/conftest.py).
- The process's own cache of compiled programs (`ProgramCache`): the whole
  search of the resident and sharded engines (`EXECUTABLES`, filled by
  `tensor/search_host.py` `Stages`) and the path replay's programs
  (`REPLAYS`, `tensor/frontier.py`), kept per model definition
  (`TensorModel.definition_key()`) and engine configuration, so a new model
  instance of one definition reuses what an earlier check traced, lowered
  and loaded; and the discovery paths' parent walks (`WALKS`,
  `tensor/parent_walk.py`), which depend on no model.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Hashable, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns the directory this
    call set, or None when `JAX_COMPILATION_CACHE_DIR` is set — JAX reads
    that variable itself, so whoever set it places the cache.

    Otherwise the cache sits at a fixed path in the checkout (the path is
    part of the cache key, so a moving directory never hits):
    `.jax_cache`, or `.jax_cache_cpu` on the CPU backend, whose entries
    carry the compiling host's CPU features."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    name = ".jax_cache_cpu" if jax.default_backend() == "cpu" else ".jax_cache"
    path = os.path.join(_REPO, name)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: Entries each program cache keeps, least recently used out first. A
#: search executable holds its code on the device (65-98 MB for the
#: benchmark's configurations on a v5e), so a long-lived service that
#: checks many definitions keeps at most this many loaded.
PROGRAM_CACHE_SIZE = 8


class ProgramCache:
    """A thread-safe LRU of compiled programs, bounded by `size` entries
    (None: unbounded). A key names everything the program was traced from;
    a shared cache's values hold nothing of a model instance or an engine,
    so the cache keeps neither alive."""

    def __init__(self, size: Optional[int] = PROGRAM_CACHE_SIZE):
        self.size = size
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: Hashable) -> Any:
        with self._lock:
            value = self._entries.get(key)
            if value is not None:
                self._entries.move_to_end(key)
            return value

    def put(self, key: Hashable, value: Any) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while self.size is not None and len(self._entries) > self.size:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: Whole-search executables by (engine, definition and configuration,
#: static arguments and operand shapes, `trace_context()`).
EXECUTABLES = ProgramCache()
#: Path replay programs by model definition.
REPLAYS = ProgramCache()
#: Parent walks by table layout and shape, mesh and chain length.
WALKS = ProgramCache()


def trace_context() -> Hashable:
    """The JAX settings a trace reads besides its function and arguments
    (x64, the default device, matmul precision, and the rest of what jit's
    own cache keys on), so a setting changed between two checks of one
    definition is another program."""
    import jax
    from jax._src import config

    ctx = getattr(config, "trace_context", None)
    if ctx is not None:
        return ctx()
    return (
        jax.config.jax_enable_x64,
        str(jax.config.jax_default_device),
        str(jax.config.jax_default_matmul_precision),
    )


def clear_program_caches() -> None:
    """Forget every compiled program this process shares across checks; the
    next check of each definition traces, lowers and loads again."""
    EXECUTABLES.clear()
    REPLAYS.clear()
    WALKS.clear()
