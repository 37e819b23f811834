"""Seconds that set-up spent compiling or loading compiled programs from
the persistent cache: the sum of jax.monitoring's backend compile
durations up to the end of the warm-up (a cache hit is timed there too)."""


def read(r):
    return r.setup["compile_s"]
