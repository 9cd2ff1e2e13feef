"""The whole step's share of the chip's bf16 peak, for a routed token encoder
with learned sparse attention: analytic forward+backward operations of this
chip's share of one step (`perfbench/flops_sparse.py`: attention at the SELECTED
pairs, the experts at the assignments the program counted, the indexer forward
only, recomputation not counted) over the fused step's device time."""

import statistics

from perfbench import flops_sparse, nested_spans, peaks, trace_reduce


def read(run):
    ds = trace_reduce.durations(run["trace"]["programs"], "fused_step")
    assigned = nested_spans.counter(run, "moe_assign_per_token")
    if not ds or assigned is None or "sa_config" not in run["config_file"]:
        return None
    per_chip = flops_sparse.step_flops(run["config"], run["config_file"], assigned) / run["chips"]
    return 100.0 * per_chip / statistics.median(ds) / peaks.for_kind(run["device_kind"])["flops_bf16"]
