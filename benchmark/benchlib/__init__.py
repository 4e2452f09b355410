"""Shared arithmetic of the benchmark: the yardstick later PRs may not edit.

`spec` finds a cell's files by the names in BENCHMARK.json, `traffic` is the
one general generator, `flops` the operation and byte counts, `peaks` the
table of published peaks, `trace_reduce` the reduction from a profiler
trace to numbers. Nothing here imports JAX at import time.
"""
