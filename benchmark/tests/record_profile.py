"""Records the small v5e profile that test_trace_reduction.py reads
(tests/data/v5e_small.xplane.pb), with the benchmark's own span names:
a window of two "checks", each a host-only spawn, a device program named
`step` (module jit_step) and a host-only discoveries phase.

    chiprun -- python3 benchmark/tests/record_profile.py chiprun_out/profile
"""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import trace  # noqa: E402


def main(out: str) -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def step(x):
        def body(i, x):
            return jnp.sort(x * 3 + i) % 1000003

        return jax.lax.fori_loop(0, 20, body, x)

    @jax.jit
    def other(x):
        return x.sum()

    x = jnp.arange(1 << 20, dtype=jnp.uint32)
    step(x).block_until_ready()
    other(x).block_until_ready()
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp, profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.check"):
                with jax.profiler.TraceAnnotation("bench.spawn"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("bench.join"):
                    step(x).block_until_ready()
                    other(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.discoveries"):
                    time.sleep(0.01)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp)
    shutil.copy(path, os.path.join(out, "v5e_small.xplane.pb"))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs),
                  [e.name for e in evs[:4]])
    print("bytes", os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
