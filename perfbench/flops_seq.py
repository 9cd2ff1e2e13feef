"""Forward+backward operations of one training step of a routed token encoder
(`sdar_moe` stack under the MoCo v2 step), by the analytic count `flops.py`
uses: a multiply-add is two operations, backward is twice forward, a step is 4
forward-equivalents a document (query forward+backward 3 on one view, key
forward 1 on the other). Recomputation (the rematerialised layers) is not
counted. This chip's share: attention's scores and mix at the block-causal
mask's density, the router over all of its outputs, the experts held here at
the assignments the program COUNTED (its `moe_assign_per_token` counter), not
at what uniform routing would send. Widths come from the configuration's file.
"""


def mask_density(seq_len: int, block_length: int) -> float:
    """Share of the `L x L` scores a block-causal mask keeps."""
    blocks = -(-seq_len // block_length)
    return (blocks + 1) / (2.0 * blocks)


def view_forward(f: dict, seq_len: int, assign_per_token: float) -> float:
    """One view of `seq_len` tokens through the stack and the head; `f` is the
    configuration's file."""
    d, hd = f["hidden_size"], f["head_dim"]
    heads, kv = f["num_attention_heads"], f["num_key_value_heads"]
    per_token = (2 * d * (heads + 2 * kv) * hd
                 + 2 * 2 * seq_len * mask_density(seq_len, f["block_length"]) * heads * hd
                 + 2 * heads * hd * d
                 + 2 * d * f["num_router_outputs"]
                 + assign_per_token * 3 * 2 * d * f["moe_intermediate_size"])
    head = 2 * d * d + 2 * d * f["trainer"]["embed_dim"]
    return f["num_hidden_layers"] * seq_len * per_token + head


def step_flops(config, config_file: dict, assign_per_token: float) -> float:
    return 4 * config.batch_size * view_forward(config_file, config.seq_len, assign_per_token)
