"""The latent attention kernels' share of their roofline: the least time the
chip could take for a step's worth of causal latent attention (operations over
the bf16 peak, or bytes over the memory's peak, whichever is larger:
`perfbench/kernels/mla_attn.py`, the work the model defines) over the device
time a traced step spends in the two kernels, which also holds every masked
pair of a chunk on the diagonal and the zeros of the 64-wide rotary product."""

from perfbench import latent_spans
from perfbench.kernels import mla_attn


def read(run):
    config = run["config"]
    if "kv_lora_rank" not in run["config_file"]:
        return None
    itemsize = 2 if config.compute_dtype == "bfloat16" else 4
    work = mla_attn.step_work(run["config_file"], config.batch_size / run["chips"],
                              config.seq_len, itemsize)
    return latent_spans.roofline_pct(run, mla_attn.NAMES, work)
