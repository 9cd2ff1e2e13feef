"""Plain float32 forward pass of a routed stack with learned sparse attention
as a sequence encoder: what `moco_sparse.py` trains. The layer is `seq_nets.py`'s
(RMSNorm, grouped-query attention with per-head RMSNorm and rotary on q and k,
the routed expert layer's held share) with ONE difference: WHICH keys a query
attends to is chosen by an indexer, per token and batch row, for all heads.

The equations (ISSUE 33; `sa_config` of the model's public `config.json`, read
as DeepSeek Sparse Attention: DeepSeek-V3.2-Exp report, lightning indexer eq. 1
and top-k token selection). With `h = RMSNorm(x; g1)`, positions `0..L-1`:

  - indexer: `qI = h Wq` as `index_heads` heads of `index_dim`; `kI =
    LayerNorm(h Wk)` (scale and bias), ONE head shared by all; `w = h Ww`,
    `index_heads` numbers; rotate-half rotary over the whole `index_dim` of
    `qI` and `kI`; `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`, `s <= t`.
  - selection: `S_t` = the `min(index_topk, t + 1)` keys `s <= t` of largest
    `I[t, s]`, equal scores to the lower `s` (`lax.top_k`'s order; -0.0 is
    0.0). Nothing flows into `I` (`stop_gradient`).
  - attention: `seq_nets.attention`'s q, k, v; `softmax_{s in S_t}(q_t . k_s /
    sqrt(head_dim)) v_s`; `Wo`.

Then the routed layer, the final RMSNorm, the mean over positions and the MoCo
v2 head, all `seq_nets.py`'s. Left out, as in the program: the vision tower, the
output head, the loss that would train the indexer (its leaves are constants).

Nothing of shape `[heads, L, L]` is formed: attention and index scores are
computed for `BLOCK` query rows at a time (32 x 512 x 8192 x 4 B = 0.5 GB of
scores at the published sizes), each block rematerialised in the backward pass.
That changes no number. Products go through `nets.Ops`; the router's product,
the softmaxes, the norms, the index scores' relu and sum, and the head stay
float32. `fault` plants one departure, for the readings the limits are set
against.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference import seq_nets
from perfbench.reference.nets import Ops, layernorm
from perfbench.reference.seq_nets import rmsnorm, rope

# published sizes (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json)
SIZES = {
    "keye_vl2_30b_a3b": dict(hidden=2048, layers=48, heads=32, kv_heads=4, head_dim=128,
                             experts=128, top_k=8, expert_width=768, vocab=151936,
                             rope_theta=1e7, eps=1e-6, index_heads=16, index_dim=64,
                             index_topk=2048),
    "keye_tiny": dict(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=16,
                      top_k=4, expert_width=32, vocab=512, rope_theta=1e7, eps=1e-6,
                      index_heads=4, index_dim=8, index_topk=16),
}
BLOCK = 512     # query rows whose scores are in memory at once
# the selection left out (every causal key visible); the best half for the whole;
# the most recent keys in place of the indexer's choice; the indexer without its relu
FAULTS = ("select_all", "topk_half", "recent", "no_relu")
INDEXER = "/indexer/"


def sizes_for(cfg: dict) -> dict:
    """The arch's published sizes with the configuration's cut laid over them."""
    z = dict(SIZES[cfg["arch"]])
    z["layers"] = cfg.get("num_hidden_layers") or z["layers"]
    z["held"] = cfg.get("num_experts") or z["experts"]
    z["vocab"] = cfg.get("vocab_size") or z["vocab"]
    return z


def spec(z: dict, embed_dim: int) -> list:
    """`seq_nets.spec`'s leaves and the indexer's five a layer."""
    d, out = z["hidden"], list(seq_nets.spec(dict(z, block_length=1), embed_dim))
    for i in range(z["layers"]):
        p = f"layer_{i}{INDEXER}"
        out += [(p + "q/kernel", (d, z["index_heads"] * z["index_dim"]), "normal", d),
                (p + "k/kernel", (d, z["index_dim"]), "normal", d),
                (p + "w/kernel", (d, z["index_heads"]), "normal", d),
                (p + "k_norm/scale", (z["index_dim"],), "ones", 0),
                (p + "k_norm/bias", (z["index_dim"],), "zeros", 0)]
    return out


def select(scores, position, topk: int, fault=None):
    """`[B, rows, L]` bool: the keys each query selects. `scores` are `I[t, :]`
    for the queries at `position` (`[rows]`)."""
    key_at = jnp.arange(scores.shape[-1])
    causal = key_at[None, :] <= position[:, None]
    if fault == "select_all":
        return jnp.broadcast_to(causal, scores.shape)
    if fault == "recent":
        return jnp.broadcast_to(causal & (key_at[None, :] > position[:, None] - topk), scores.shape)
    k = min(topk // 2 if fault == "topk_half" else topk, scores.shape[-1])
    ranked = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    kth = jax.lax.top_k(ranked, k)[0][..., -1:]       # -inf where a query has fewer keys
    above = ranked > kth
    equal = (ranked == kth) & causal
    room = k - jnp.sum(above, -1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, -1) <= room))


def attention(ops: Ops, p: dict, layer: str, h, z: dict, fault=None):
    """`(Attn(h), the selection [B, L, L] bool)` of the layer named `layer`."""
    name, indexer = layer + "/attn", layer + "/indexer"
    b, length, _ = h.shape
    hd, heads, kv = z["head_dim"], z["heads"], z["kv_heads"]
    ih, idim = z["index_heads"], z["index_dim"]
    q = ops.dot(h, p[name + "/q/kernel"]).reshape(b, length, heads, hd)
    k = ops.dot(h, p[name + "/k/kernel"]).reshape(b, length, kv, hd)
    v = ops.a(ops.dot(h, p[name + "/v/kernel"])).reshape(b, length, kv, hd)
    q = ops.a(rope(rmsnorm(q, p[name + "/q_norm/scale"], z["eps"]), z["rope_theta"]))
    k = ops.a(rope(rmsnorm(k, p[name + "/k_norm/scale"], z["eps"]), z["rope_theta"]))
    k, v = jnp.repeat(k, heads // kv, 2), jnp.repeat(v, heads // kv, 2)   # head j reads kv head j // group

    hi = jax.lax.stop_gradient(h)
    qi = ops.dot(hi, p[indexer + "/q/kernel"]).reshape(b, length, ih, idim)
    ki = layernorm(ops.dot(hi, p[indexer + "/k/kernel"]), p[indexer + "/k_norm/scale"],
                   p[indexer + "/k_norm/bias"], z["eps"])
    wi = ops.dot(hi, p[indexer + "/w/kernel"])
    qi = ops.a(rope(qi, z["rope_theta"]))
    ki = ops.a(rope(ki[:, :, None, :], z["rope_theta"])[:, :, 0])
    qi, ki, wi = jax.lax.stop_gradient((qi, ki, wi))

    rows = min(BLOCK, length)

    @jax.checkpoint
    def block(q_rows, qi_rows, wi_rows, first):
        position = first + jnp.arange(rows)
        each = ops.einsum("bthd,bsd->bths", qi_rows, ki)
        if fault != "no_relu":
            each = jax.nn.relu(each)
        picked = select(jnp.sum(each * wi_rows[..., None], 2), position, z["index_topk"], fault)
        s = ops.einsum("bqhd,bkhd->bhqk", q_rows, k) / math.sqrt(hd)
        s = jnp.where(picked[:, None], s, -jnp.inf)
        return ops.einsum("bhqk,bkhd->bqhd", ops.a(jax.nn.softmax(s, -1)), v), picked

    def by_blocks(x):     # [B, L, ...] -> [L / rows, B, rows, ...]
        return jnp.moveaxis(x.reshape(b, length // rows, rows, *x.shape[2:]), 1, 0)

    o, picked = jax.lax.map(lambda a: block(*a), (by_blocks(q), by_blocks(qi), by_blocks(wi),
                                                  jnp.arange(0, length, rows)))
    o = ops.a(jnp.moveaxis(o, 0, 1).reshape(b, length, heads * hd))
    return ops.dot(o, p[name + "/o/kernel"]), jnp.moveaxis(picked, 0, 1).reshape(b, length, length)


def forward(ops: Ops, p: dict, ids, z: dict, fault=None, picked_out=None):
    """Token ids `[B, L]` -> `[B, embed]`. `picked_out`, a list, takes each
    layer's selection `[B, L, L]`."""
    x = ops.a(p["embed/embedding"][ids])

    def layer(x, name):
        h = ops.a(rmsnorm(x, p[name + "/norm1/scale"], z["eps"]))
        a, picked = attention(ops, p, name, h, z, fault)
        x = ops.a(x + a)
        b, length, d = x.shape
        u = rmsnorm(x, p[name + "/norm2/scale"], z["eps"]).reshape(b * length, d)
        weights, _ = seq_nets.routing(p, name + "/moe", u, z)
        y = seq_nets.experts(ops, p, name + "/moe", ops.a(u), weights, slice(0, z["held"]))
        return ops.a(x + y.reshape(b, length, d)), picked

    for i in range(z["layers"]):
        x, picked = jax.checkpoint(lambda x_, n=f"layer_{i}": layer(x_, n))(x)
        if picked_out is not None:
            picked_out.append(picked)
    f = jnp.mean(rmsnorm(x, p["norm/scale"], z["eps"]), 1)
    f = jax.nn.relu(jnp.matmul(f, p["fc_hidden/kernel"], precision=seq_nets.HI) + p["fc_hidden/bias"])
    return jnp.matmul(f, p["fc/kernel"], precision=seq_nets.HI) + p["fc/bias"]
