"""Operations and bytes of causal latent attention, from shapes alone: the work
the MODEL defines, whatever implements it (`ops/pallas_mla.py`'s pair today,
which computes every key chunk that reaches under the diagonal whole and takes
the 64-wide rotary product at the cost of a 128-wide one: it cannot read over
about 83 % of its MXU's peak at 4 096 tokens).

One forward pass of one layer over one view of `seq_len` tokens: at every
causal pair, scores `nope + rope` wide and a mix `v_dim` wide, `2 * pairs *
heads * (nope + rope + v_dim)` operations; q (`nope + rope` a head), k_nope, v
and the one rotary key head read once, o written once. The backward pass is 2.5
forwards of operations (scores again, dp, dv, dk, dq); it reads q, k, v, o and
do and writes dq, dk and dv.

A training step makes the key forward and the query forward (2 forward passes a
document and layer) and one backward pass. The rematerialised layer keeps the
forward kernel's results, so no forward runs again there; none is counted.
"""

NAMES = ("mla_attention_fwd", "mla_attention_bwd")


def causal_pairs(seq_len: int) -> float:
    return seq_len * (seq_len + 1) / 2.0


def work(seq_len: int, heads: int, nope: int, rope: int, v_dim: int, itemsize: int) -> dict:
    """One view through one layer: `(forward, backward)` as `flops` and `bytes`."""
    forward = 2 * causal_pairs(seq_len) * heads * (nope + rope + v_dim)
    q = seq_len * heads * (nope + rope) * itemsize
    k = seq_len * (heads * nope + rope) * itemsize
    v = seq_len * heads * v_dim * itemsize
    return {"fwd": {"flops": forward, "bytes": q + k + v + v},
            "bwd": {"flops": 2.5 * forward, "bytes": 2 * (q + k + v) + 2 * v}}


def step_work(config_file: dict, documents: float, seq_len: int, itemsize: int) -> dict:
    f = config_file
    one = work(seq_len, f["num_attention_heads"], f["qk_nope_head_dim"], f["qk_rope_head_dim"],
               f["v_head_dim"], itemsize)
    n = documents * f["num_hidden_layers"]
    return {k: n * (2 * one["fwd"][k] + one["bwd"][k]) for k in ("flops", "bytes")}
