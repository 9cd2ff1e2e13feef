"""Of the 128 x 128 score tiles on or under the diagonal, the share that holds a
selected (query, key) pair, the worst layer's, in the query forward: the
program's stride-gated counter `sel_live_tile_share` (step records' `health`
block), averaged over the window's samples. What an attention kernel that
skipped dead tiles could not skip; 1 where every tile is live."""

from perfbench import nested_spans


def read(run):
    return nested_spans.counter(run, "sel_live_tile_share")
