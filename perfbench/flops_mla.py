"""Forward+backward operations of one training step of a routed token encoder
with latent attention and shared experts (the `kanana` stack under the MoCo v2
step), by the analytic count `flops_seq.py` uses: a multiply-add is two
operations, backward is twice forward, a step is 4 forward-equivalents a
document (query forward+backward 3 on one view, key forward 1 on the other).
Recomputation (the rematerialised layers) is not counted. This chip's share:
the latent attention's five projections and its scores and mix at every causal
pair (`perfbench/kernels/mla_attn.py`), the leading dense layers' SwiGLU, and in
every other layer the router over all of its outputs, the shared experts' SwiGLU
and the experts held here at the assignments the program COUNTED. Widths come
from the configuration's file.
"""

from perfbench.kernels.mla_attn import causal_pairs


def view_forward(f: dict, seq_len: int, assign_per_token: float) -> float:
    """One view of `seq_len` tokens through the stack and the head, `f` the
    configuration's file."""
    d, heads, rank = f["hidden_size"], f["num_attention_heads"], f["kv_lora_rank"]
    nope, rope, v = f["qk_nope_head_dim"], f["qk_rope_head_dim"], f["v_head_dim"]
    projections = 2 * d * (heads * (nope + rope) + rank + rope) + 2 * rank * heads * (nope + v) \
        + 2 * heads * v * d
    attention = 2 * causal_pairs(seq_len) * heads * (nope + rope + v)
    width = f["moe_intermediate_size"]
    dense = 3 * 2 * d * f["intermediate_size"]
    routed = (2 * d * f["n_routed_experts"] + 3 * 2 * d * f["n_shared_experts"] * width
              + assign_per_token * 3 * 2 * d * width)
    layers = f["num_hidden_layers"]
    n_dense = min(f["first_k_dense_replace"], layers)
    head = 2 * d * d + 2 * d * f["trainer"]["embed_dim"]
    return (layers * (seq_len * projections + attention)
            + seq_len * (n_dense * dense + (layers - n_dense) * routed) + head)


def step_flops(config, config_file: dict, assign_per_token: float) -> float:
    return config.batch_size * 4 * view_forward(config_file, config.seq_len, assign_per_token)
