"""Operations and bytes of a sparse-attention indexer's scores and of its exact
top-k selection, from shapes alone: the work the MODEL defines, whatever
implements it (`ops/pallas_select.py::index_scores` and `select_top_k` today).

Scores, one view through one layer: `I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])` over the causal pairs, `2 * pairs * heads * dim` operations (the relu,
the weights and the sum over heads are not counted: vector work beside the
products); qI, kI and w read once, the causal half of `[L, L]` float32 written
once.

Selection, one view through one layer: no matrix product at all. The least any
exact selection moves: the causal half of the float32 scores read once, one byte
a pair of the `[L, L]` selection written once. Memory bounds it; the passes a
bisection makes over rows that stay in fast memory are not counted (they are the
implementation's), so the share reads how far the selection is from one read and
one write.

A training step makes both twice a document and layer (key forward, query
forward); the rematerialised forward is not counted; neither has a backward pass.
"""

SCORES = ("index_scores",)
SELECTION = ("select_top_k",)
PASSES_PER_STEP = 2


def causal_pairs(seq_len: int) -> float:
    return seq_len * (seq_len + 1) / 2.0


def scores_work(seq_len: int, heads: int, dim: int, itemsize: int) -> dict:
    pairs = causal_pairs(seq_len)
    operands = seq_len * (heads * dim + dim) * itemsize + seq_len * heads * 4
    return {"flops": 2 * pairs * heads * dim, "bytes": operands + 4 * pairs}


def selection_work(seq_len: int) -> dict:
    return {"flops": 0.0, "bytes": 4 * causal_pairs(seq_len) + seq_len * seq_len}


def step_work(config_file: dict, documents: float, seq_len: int, itemsize: int) -> dict:
    """`{"scores": ..., "selection": ...}`, each `flops` and `bytes` a step."""
    sa = config_file["sa_config"]
    n = PASSES_PER_STEP * documents * config_file["num_hidden_layers"]
    one = {"scores": scores_work(seq_len, sa["indexer_num_heads"], sa["indexer_head_dim"], itemsize),
           "selection": selection_work(seq_len)}
    return {name: {k: n * v for k, v in w.items()} for name, w in one.items()}


def roofline_pct(run, which: str, names, spans):
    """A reader's whole body: `which` of `step_work` for the run's configuration
    against the device time of the kernels `names` (`spans` is
    `perfbench/sparse_spans.py`, which knows the trace)."""
    config = run["config"]
    if "sa_config" not in run["config_file"]:
        return None
    itemsize = 2 if config.compute_dtype == "bfloat16" else 4
    work = step_work(run["config_file"], config.batch_size / run["chips"], config.seq_len, itemsize)
    return spans.roofline_pct(run, names, work[which])
