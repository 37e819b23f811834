"""Records the small v5e profile that test_attribution.py reads
(tests/data/scoped/v5e_scoped.xplane.pb): like record_profile.py, a window
of two "checks", but the device program `step` (module jit_step) names its
phases with `jax.named_scope` (expand, fingerprint, insert, and one op
outside any scope), and each check ends in a `path.replay` span that runs
REPLAY_PROGRAMS small programs, 5 ms apart and 5 ms from its ends: the
device's clock and the host's differ by about a millisecond in such a
trace, so a program at a span's edge can fall outside it.

On a TPU host, from the checkout's root:

    python3 benchmark/tests/record_scoped_profile.py <out dir>
"""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import trace  # noqa: E402

#: Eager device programs each check's `path.replay` span runs.
REPLAY_PROGRAMS = 3


def main(out: str) -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def step(x):
        def body(i, x):
            with jax.named_scope("expand"):
                y = x * 3 + i
            with jax.named_scope("fingerprint"):
                y = y ^ (y >> 7)
            with jax.named_scope("insert"):
                y = jnp.sort(y)
            return y % 1000003

        return jax.lax.fori_loop(0, 20, body, x)

    @jax.jit
    def replay(x):
        return x[:8] + 1

    x = jnp.arange(1 << 20, dtype=jnp.uint32)
    step(x).block_until_ready()
    replay(x).block_until_ready()
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp, profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.check"):
                with jax.profiler.TraceAnnotation("bench.spawn"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("bench.join"):
                    step(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.discoveries"):
                    with jax.profiler.TraceAnnotation("path.replay"):
                        for _ in range(REPLAY_PROGRAMS):
                            time.sleep(0.005)
                            replay(x).block_until_ready()
                        time.sleep(0.005)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp)
    shutil.copy(path, os.path.join(out, "v5e_scoped.xplane.pb"))
    print("bytes", os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
