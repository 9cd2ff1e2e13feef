"""The whole step's share of the chip's bf16 peak, for a looped dense token
encoder: analytic forward+backward operations of one step
(`perfbench/flops_looped.py`: a layer counted once a PASS, attention at the
causal mask's density, 4 forward-equivalents a document, recomputation not
counted) over the fused step's device time."""

import statistics

from perfbench import flops_looped, peaks, trace_reduce


def read(run):
    ds = trace_reduce.durations(run["trace"]["programs"], "fused_step")
    if not ds or "total_ut_steps" not in run["config_file"]:
        return None
    per_chip = flops_looped.step_flops(run["config"], run["config_file"]) / run["chips"]
    return 100.0 * per_chip / statistics.median(ds) / peaks.for_kind(run["device_kind"])["flops_bf16"]
