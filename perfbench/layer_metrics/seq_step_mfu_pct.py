"""The whole step's share of the chip's bf16 peak, for a routed token encoder:
analytic forward+backward operations of this chip's share of one step
(`perfbench/flops_seq.py`: attention at the mask's density, the experts at the
assignments the program counted) over the fused step's device time."""

import statistics

from perfbench import flops_seq, nested_spans, peaks, trace_reduce


def read(run):
    ds = trace_reduce.durations(run["trace"]["programs"], "fused_step")
    assigned = nested_spans.counter(run, "moe_assign_per_token")
    if not ds or assigned is None:
        return None
    per_chip = flops_seq.step_flops(run["config"], run["config_file"], assigned) / run["chips"]
    return 100.0 * per_chip / statistics.median(ds) / peaks.for_kind(run["device_kind"])["flops_bf16"]
