"""Median device duration of the `jit_fused_step` program in the trace."""

import statistics

from perfbench import trace_reduce


def read(run):
    ds = trace_reduce.durations(run["trace"]["programs"], "fused_step")
    return 1e3 * statistics.median(ds) if ds else None
