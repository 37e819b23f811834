#!/usr/bin/env python
"""Device check on a TPU: the Paxos-3 main path, end to end, through the
checker API a user calls.

    python chip_smoke.py             # one chip: main path + Pallas insert
    python chip_smoke.py --chips 4   # four chips: sharded engine + one chip

Phases, each of which must pass:

- device: `jax.devices()` are TPUs (no TPU: exit 1, no result line);
- main path: `TensorPaxos(3).checker().spawn_tpu(batch_size=8192,
  table_log2=22)` as `examples/paxos.py check-tpu 3` runs it, at the golden
  counts, with exactly the discovery {"value chosen"} ("linearizable" is
  never violated);
- pallas: the same check with `insert_variant="pallas"`, and the lowered
  step holds the compiled kernel (`tpu_custom_call`), not the interpreter;
- with `--chips 4` only: `ShardedSearch` over a 4-chip mesh at the golden
  counts with every chip holding a share of the states, and the main path
  on one chip of the same host.

The times it prints are a builder smoke run on one device kind, not a
benchmark. The last line of stdout is the one JSON result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import GOLDEN

#: What the Paxos model must discover: "value chosen" is reachable, and
#: "linearizable" (an always-property) has no counterexample.
PAXOS_DISCOVERIES = {"value chosen"}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SmokeFailure(Exception):
    """A phase gave a wrong or missing result."""


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache loads) and the
    persistent cache's hits and misses, from `jax.monitoring` events,
    while the clock is entered."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **_kw):
        if event == _COMPILE_EVENT:
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == _CACHE_MISS_EVENT:
            self.cache_misses += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False


def require_tpu(n_chips: int) -> dict:
    """The device phase: the visible devices must be at least `n_chips`
    TPUs. Returns the device record of the result line."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX sees {len(devices)} {d.platform} device(s)"
        )
    if len(devices) < n_chips:
        raise SmokeFailure(
            f"--chips {n_chips} needs {n_chips} TPUs; JAX sees {len(devices)}"
        )
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _check_result(what: str, counts: tuple, discoveries, golden: tuple):
    if counts != golden:
        raise SmokeFailure(
            f"{what}: (generated, unique) = {counts}, golden {golden}"
        )
    if set(discoveries) != PAXOS_DISCOVERIES:
        raise SmokeFailure(
            f"{what}: discoveries {sorted(discoveries)}, expected "
            f"{sorted(PAXOS_DISCOVERIES)}"
        )


def _timing(counts: tuple, wall: float, compile_s: float) -> dict:
    return {
        "generated": counts[0],
        "unique": counts[1],
        "wall_s": wall,
        "compile_s": compile_s,
        "states_per_s_excl_compile": counts[0] / max(wall - compile_s, 1e-9),
    }


def check_main_path(model, batch_size: int, table_log2: int, golden: tuple,
                    clock: CompileClock, **engine_kwargs):
    """Run `model` through `checker().spawn_tpu(...)` and `join()`; check the
    golden counts and the discoveries. Returns (timing dict, checker)."""
    c0 = clock.seconds
    t0 = time.monotonic()
    checker = model.checker().spawn_tpu(
        batch_size=batch_size, table_log2=table_log2, **engine_kwargs
    )
    checker.join()
    wall = time.monotonic() - t0
    timing = _timing(
        (checker.state_count(), checker.unique_state_count()),
        wall, clock.seconds - c0,
    )
    _check_result("main path", (timing["generated"], timing["unique"]),
                  checker.discoveries(), golden)
    return timing, checker


def assert_compiled_kernel(checker) -> None:
    """The checker's engine step holds the compiled Pallas kernel
    (`tpu_custom_call`), not the Pallas interpreter."""
    step, operands, _ = checker._search.audit_step()
    if "tpu_custom_call" not in step.lower(*operands).as_text():
        raise SmokeFailure(
            "pallas insert: no tpu_custom_call in the lowered engine step "
            "(the Pallas interpreter ran, not the compiled kernel)"
        )


def check_sharded(model, n_chips: int, batch_size: int, table_log2: int,
                  golden: tuple, clock: CompileClock) -> dict:
    """`ShardedSearch` over an `n_chips` mesh: golden counts, the
    discoveries, and a non-zero share of the unique states on every chip."""
    from stateright_tpu.parallel import ShardedSearch, make_mesh

    mesh = make_mesh(n_chips)
    if len({d.id for d in mesh.devices.flat}) != n_chips:
        raise SmokeFailure(f"mesh holds {mesh.devices} — not {n_chips} devices")
    c0 = clock.seconds
    t0 = time.monotonic()
    r = ShardedSearch(
        model, mesh=mesh, batch_size=batch_size, table_log2=table_log2
    ).run()
    wall = time.monotonic() - t0
    compile_s = clock.seconds - c0
    counts = (r.state_count, r.unique_state_count)
    _check_result(f"sharded over {n_chips}", counts, r.discoveries, golden)
    per_chip = r.detail["per_chip_unique"]
    if len(per_chip) != n_chips or min(per_chip) <= 0:
        raise SmokeFailure(
            f"sharded over {n_chips}: per-chip unique counts {per_chip}; "
            "every chip must hold a share"
        )
    return {**_timing(counts, wall, compile_s), "per_chip_unique": per_chip}


def _report(phase: str, kind: str, rec: dict) -> None:
    print(f"{phase} (smoke run on {kind}, not a benchmark): "
          + json.dumps(rec), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded 4-chip check and its one-chip "
             "comparison",
    )
    args = ap.parse_args(argv)

    try:
        device = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    kind = device["kind"]
    print(f"device: platform={device['platform']} kind={kind!r} "
          f"count={device['count']}", flush=True)

    from stateright_tpu.compile_cache import use_compile_cache
    from stateright_tpu.tensor.paxos import TensorPaxos

    cache = use_compile_cache()
    print(f"compile cache: {cache or 'JAX_COMPILATION_CACHE_DIR'}",
          flush=True)
    golden = GOLDEN[("paxos", 3)]
    try:
        with CompileClock() as clock:
            if args.chips == 4:
                rec = check_sharded(TensorPaxos(client_count=3), 4, 2048, 20,
                                    golden, clock)
                _report("sharded over 4 chips", kind, rec)
                rec, _ = check_main_path(TensorPaxos(client_count=3), 8192,
                                         22, golden, clock)
                _report("main path on one chip", kind, rec)
            else:
                rec, _ = check_main_path(TensorPaxos(client_count=3), 8192,
                                         22, golden, clock)
                _report("main path", kind, rec)
                rec, checker = check_main_path(
                    TensorPaxos(client_count=3), 8192, 22, golden, clock,
                    insert_variant="pallas",
                )
                assert_compiled_kernel(checker)
                _report("pallas insert (compiled kernel)", kind, rec)
            print(f"persistent cache: hits={clock.cache_hits} "
                  f"misses={clock.cache_misses}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
