"""Operations and bytes of attention over the pairs a selection keeps, from
shapes and the selection's size alone: the work the MODEL defines, whatever
implements it (`ops/pallas_attention.py::masked_attention` today, which computes
every key chunk that reaches under the diagonal and masks: it cannot read over
the selected share of the causal pairs, 44 % at 8 192 tokens and 2 048 keys a
query; a kernel that skips dead tiles rises toward its MXU share).

One forward pass of one layer over one view of `seq_len` tokens: the scores and
the mix at the selected pairs, `4 * pairs * heads * head_dim` operations; q, k,
v read and o written once, and the selection (one byte a (query, key) pair of
the view) read once. The backward pass is 2.5 forwards of operations (scores
again, dp, dv, dk, dq); it reads q, k, v, o, do and the selection and writes
dq, dk, dv.

A training step makes the key forward and the query forward (2 forward passes a
document and layer) and one backward pass. The rematerialised forward is not
counted.
"""

NAMES = ("masked_attention_fwd", "masked_attention_bwd")


def selected_pairs(seq_len: int, topk: int) -> float:
    n = min(topk, seq_len)
    return n * (n + 1) / 2.0 + (seq_len - n) * topk


def work(seq_len: int, topk: int, heads: int, kv_heads: int, head_dim: int, itemsize: int) -> dict:
    """One view through one layer: `(forward, backward)` as `flops` and `bytes`."""
    forward = 4 * selected_pairs(seq_len, topk) * heads * head_dim
    q_bytes = seq_len * heads * head_dim * itemsize
    kv_bytes = seq_len * kv_heads * head_dim * itemsize
    live = seq_len * seq_len
    return {"fwd": {"flops": forward, "bytes": 2 * q_bytes + 2 * kv_bytes + live},
            "bwd": {"flops": 2.5 * forward, "bytes": 4 * q_bytes + 4 * kv_bytes + live}}


def step_work(config_file: dict, documents: float, seq_len: int, itemsize: int) -> dict:
    f = config_file
    one = work(seq_len, f["sa_config"]["topk"], f["num_attention_heads"],
               f["num_key_value_heads"], f["head_dim"], itemsize)
    n = documents * f["num_hidden_layers"]
    return {k: n * (2 * one["fwd"][k] + one["bwd"][k]) for k in ("flops", "bytes")}
