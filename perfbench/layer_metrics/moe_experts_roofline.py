"""The grouped expert products' share of their roofline: the least time the chip
could take for a step's worth of them at the counted rows (operations over the
bf16 peak, or bytes over the memory's peak, whichever is larger: operations
bound it at these widths; `perfbench/kernels/moe_experts.py`) over the device
time a traced step spends under `moe_experts`, which also holds SwiGLU's
elementwise pass and the rematerialised forward."""

from perfbench import nested_spans, peaks
from perfbench.kernels import moe_experts


def read(run):
    ms = nested_spans.scope_ms(run, moe_experts.SCOPE)
    assigned = nested_spans.counter(run, "moe_assign_per_token")
    if not ms or assigned is None:
        return None
    config = run["config"]
    rows = assigned * config.batch_size * config.seq_len / run["chips"]
    itemsize = 2 if config.compute_dtype == "bfloat16" else 4
    work = moe_experts.step_work(run["config_file"], rows, itemsize)
    peak = peaks.for_kind(run["device_kind"])
    least = max(work["flops"] / peak["flops_bf16"], work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
