"""Forward+backward operations of one training step of a routed token encoder
with learned sparse attention (the `keye` stack under the MoCo v2 step), by the
analytic count `flops_seq.py` uses: a multiply-add is two operations, backward
is twice forward, a step is 4 forward-equivalents a document (query
forward+backward 3 on one view, key forward 1 on the other). Recomputation (the
rematerialised layers) is not counted. This chip's share: attention's scores
and mix at the SELECTED pairs (`sum_t min(t + 1, topk)` a view: what the model
defines, not what a kernel that computes every causal tile executes), the
experts held here at the assignments the program COUNTED, the router over all
of its outputs. The indexer (its three projections, and its scores over every
causal pair) has no backward pass: it is counted forward only, 2
forward-equivalents a document. Widths come from the configuration's file.
"""


from perfbench.kernels.sparse_attn import selected_pairs      # sum_t min(t + 1, topk) a view
from perfbench.kernels.sparse_index import causal_pairs


def view_forward(f: dict, seq_len: int, assign_per_token: float) -> tuple[float, float]:
    """One view of `seq_len` tokens through the stack and the head, `f` the
    configuration's file: `(what has a backward pass, what has none)`."""
    d, hd = f["hidden_size"], f["head_dim"]
    heads, kv = f["num_attention_heads"], f["num_key_value_heads"]
    sa = f["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    per_token = (2 * d * (heads + 2 * kv) * hd
                 + 2 * heads * hd * d
                 + 2 * d * f["num_router_outputs"]
                 + assign_per_token * 3 * 2 * d * f["moe_intermediate_size"])
    attention = 2 * 2 * selected_pairs(seq_len, sa["topk"]) * heads * hd
    indexer = (seq_len * 2 * d * (ih * idim + sa["indexer_num_kv_heads"] * idim + ih)
               + 2 * causal_pairs(seq_len) * ih * idim)
    head = 2 * d * d + 2 * d * f["trainer"]["embed_dim"]
    layers = f["num_hidden_layers"]
    return layers * (seq_len * per_token + attention) + head, layers * indexer


def step_flops(config, config_file: dict, assign_per_token: float) -> float:
    trained, constant = view_forward(config_file, config.seq_len, assign_per_token)
    return config.batch_size * (4 * trained + 2 * constant)
