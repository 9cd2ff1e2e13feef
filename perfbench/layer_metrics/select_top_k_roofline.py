"""The selection kernel's share of its roofline: the least time the chip could
take to read a step's worth of causal index scores once and write the selections
once (`perfbench/kernels/sparse_index.py`: memory bounds an exact selection, no
product in it) over the device time a traced step spends in the kernel, the
rematerialised forward included. The bisection's passes over rows held in fast
memory are the implementation's, so this reads how far it is from one read and
one write."""

from perfbench import sparse_spans
from perfbench.kernels import sparse_index


def read(run):
    return sparse_index.roofline_pct(run, "selection", sparse_index.SELECTION, sparse_spans)
