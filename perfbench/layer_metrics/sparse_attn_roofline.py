"""The sparse attention kernels' share of their roofline: the least time the
chip could take for a step's worth of attention over the SELECTED pairs
(operations over the bf16 peak, or bytes over the memory's peak, whichever is
larger: `perfbench/kernels/sparse_attn.py`, the work the model defines) over the
device time a traced step spends in the two kernels, which also holds the
rematerialised forward and every masked pair they compute."""

from perfbench import sparse_spans
from perfbench.kernels import sparse_attn


def read(run):
    config = run["config"]
    if "sa_config" not in run["config_file"]:
        return None
    itemsize = 2 if config.compute_dtype == "bfloat16" else 4
    work = sparse_attn.step_work(run["config_file"], config.batch_size / run["chips"],
                                 config.seq_len, itemsize)
    return sparse_spans.roofline_pct(run, sparse_attn.NAMES, work)
