"""The index-score kernel's share of its roofline: the least time the chip could
take for a step's worth of a sparse-attention indexer's scores over the causal
pairs (`perfbench/kernels/sparse_index.py`: operations over the bf16 peak, or
bytes over the memory's peak, whichever is larger) over the device time a traced
step spends in the kernel, the rematerialised forward included."""

from perfbench import sparse_spans
from perfbench.kernels import sparse_index


def read(run):
    return sparse_index.roofline_pct(run, "scores", sparse_index.SCORES, sparse_spans)
