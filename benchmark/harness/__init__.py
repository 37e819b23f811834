"""The benchmark's harness: one cell per run, driven by the data files that
BENCHMARK.json names (configs/, traffic/, metrics/). Nothing here is
specific to one configuration, traffic mix or per-layer metric."""
