"""The fullest held expert's load over the mean load of the held experts, the
worst layer's, in the query forward: the program's stride-gated counter
`moe_load_max_over_mean` (step records' `health` block), averaged over the
window's samples. 1 is an even load; the grouped product's groups are that
uneven."""

from perfbench import nested_spans


def read(run):
    return nested_spans.counter(run, "moe_load_max_over_mean")
