"""The whole step's share of the chip's bf16 peak: analytic forward+backward
operations of one step over the fused step's device time."""

import statistics

from perfbench import flops, peaks, trace_reduce


def read(run):
    ds = trace_reduce.durations(run["trace"]["programs"], "fused_step")
    if not ds:
        return None
    per_chip = flops.step_flops(run["config"], run["config_file"].get("model")) / run["chips"]
    return 100.0 * per_chip / statistics.median(ds) / peaks.for_kind(run["device_kind"])["flops_bf16"]
