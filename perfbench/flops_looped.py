"""Forward+backward operations of one training step of a looped dense token
encoder (the `ouro` stack under the MoCo v2 step), by the analytic count
`flops.py` uses: a multiply-add is two operations, backward is twice forward, a
step is 4 forward-equivalents a document (query forward+backward 3 on one view,
key forward 1 on the other). Recomputation (the rematerialised layers) is not
counted. The weights are shared by the passes, the work is not: a layer's
operations are counted once a PASS, `total_ut_steps` times a view. Attention's
scores and mix are counted at the causal mask's density. Widths come from the
configuration's file.
"""


def mask_density(seq_len: int) -> float:
    """Share of the `S x S` scores a causal mask keeps."""
    return (seq_len + 1) / (2.0 * seq_len)


def layer_forward(f: dict, seq_len: int) -> float:
    """One application of one layer to one token of a view of `seq_len`."""
    d, hd = f["hidden_size"], f["head_dim"]
    heads, kv = f["num_attention_heads"], f["num_key_value_heads"]
    return (2 * d * (heads + 2 * kv) * hd                            # q, k, v
            + 2 * 2 * seq_len * mask_density(seq_len) * heads * hd   # scores, mix
            + 2 * heads * hd * d                                     # o
            + 3 * 2 * d * f["intermediate_size"])                    # gate, up, down


def view_forward(f: dict, seq_len: int) -> float:
    """One view of `seq_len` tokens through every pass of the stack and the
    head; `f` is the configuration's file."""
    d = f["hidden_size"]
    head = 2 * d * d + 2 * d * f["trainer"]["embed_dim"]
    return f["total_ut_steps"] * f["num_hidden_layers"] * seq_len * layer_forward(f, seq_len) + head


def step_flops(config, config_file: dict) -> float:
    return 4 * config.batch_size * view_forward(config_file, config.seq_len)
