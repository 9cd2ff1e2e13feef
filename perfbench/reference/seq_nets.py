"""Plain float32 forward pass of the `sdar_moe` stack as a sequence encoder,
and the token views it is fed: what `moco_seq.py` trains.

The layer equations (ISSUE 27, from the model's public `config.json` and the
family's modelling code). `x0 = E[ids]`. For each layer, `h = RMSNorm(x; g1)`:
`q = h Wq`, `k = h Wk`, `v = h Wv` without bias; RMSNorm over each head's
numbers on q and k; rotary embedding over the whole head (rotate-half,
positions 0..L-1); each key/value head serves `heads / kv_heads` query heads;
scores `q k^T / sqrt(head_dim)`, position i sees j iff `j // block_length <=
i // block_length`; softmax; `x += (softmax(s) v) Wo`. Then `u = RMSNorm(x;
g2)`, `r = softmax(u Wr)` over ALL experts, `S` the `top_k` largest, `w_e =
r_e / sum_{S} r`, and `x += sum_{e in S and held} w_e (silu(u Wg_e) * (u
Wu_e)) Wd_e`: the held experts are the first `held`; what the others would add
is left out. After the last layer RMSNorm, the mean over positions, the MoCo
v2 head (Linear, ReLU, Linear).

Every expert here runs over every token and is weighted by `w_e` (0 where the
token did not choose it): no sort, no gather, no grouped product. Products go
through `nets.Ops` (the configuration's `compute_dtype`, as for the other
references); the router's product, both softmaxes, the norms and the head stay
float32. `fault` plants one departure, for the readings the limits are set
against.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference.nets import HI, Ops, _dense_spec

# published sizes (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json);
# `block_length` is the family's default, which the config does not give
SIZES = {
    "sdar_30b_a3b": dict(hidden=2048, layers=48, heads=32, kv_heads=4, head_dim=128,
                         experts=128, top_k=8, expert_width=768, vocab=151936,
                         rope_theta=1e6, eps=1e-6, block_length=4),
    "sdar_tiny": dict(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=16,
                      top_k=4, expert_width=32, vocab=512, rope_theta=1e6, eps=1e-6,
                      block_length=2),
}
MASK_PROB = 0.1
FAULTS = ("causal", "top_half", "renorm_held")


def sizes_for(cfg: dict) -> dict:
    """The arch's published sizes with the configuration's cut laid over them."""
    z = dict(SIZES[cfg["arch"]])
    z["layers"] = cfg.get("num_hidden_layers") or z["layers"]
    z["held"] = cfg.get("num_experts") or z["experts"]
    z["vocab"] = cfg.get("vocab_size") or z["vocab"]
    return z


def spec(z: dict, embed_dim: int) -> list:
    d, hd = z["hidden"], z["head_dim"]
    out = [("embed/embedding", (z["vocab"], d), "normal", 2)]    # unit variance
    for i in range(z["layers"]):
        p = f"layer_{i}"
        out += [(p + "/norm1/scale", (d,), "ones", 0)]
        out += [(f"{p}/attn/q/kernel", (d, z["heads"] * hd), "normal", d),
                (f"{p}/attn/k/kernel", (d, z["kv_heads"] * hd), "normal", d),
                (f"{p}/attn/v/kernel", (d, z["kv_heads"] * hd), "normal", d),
                (f"{p}/attn/q_norm/scale", (hd,), "ones", 0),
                (f"{p}/attn/k_norm/scale", (hd,), "ones", 0),
                (f"{p}/attn/o/kernel", (z["heads"] * hd, d), "normal", z["heads"] * hd)]
        out += [(p + "/norm2/scale", (d,), "ones", 0),
                (p + "/moe/router/kernel", (d, z["experts"]), "normal", d),
                (p + "/moe/gate", (z["held"], d, z["expert_width"]), "normal", d),
                (p + "/moe/up", (z["held"], d, z["expert_width"]), "normal", d),
                (p + "/moe/down", (z["held"], z["expert_width"], d), "normal", z["expert_width"])]
    out += [("norm/scale", (d,), "ones", 0)]
    return out + _dense_spec("fc_hidden", d, d) + _dense_spec("fc", d, embed_dim)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """`x`: `[B, L, H, D]`; rotate-half over all of D."""
    length, dim = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def attention(ops: Ops, p: dict, name: str, h, z: dict, fault=None):
    b, length, _ = h.shape
    hd, heads, kv = z["head_dim"], z["heads"], z["kv_heads"]
    q = ops.dot(h, p[name + "/q/kernel"]).reshape(b, length, heads, hd)
    k = ops.dot(h, p[name + "/k/kernel"]).reshape(b, length, kv, hd)
    v = ops.a(ops.dot(h, p[name + "/v/kernel"])).reshape(b, length, kv, hd)
    q = ops.a(rope(rmsnorm(q, p[name + "/q_norm/scale"], z["eps"]), z["rope_theta"]))
    k = ops.a(rope(rmsnorm(k, p[name + "/k_norm/scale"], z["eps"]), z["rope_theta"]))
    k, v = jnp.repeat(k, heads // kv, 2), jnp.repeat(v, heads // kv, 2)   # head j reads kv head j // group
    s = ops.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    block = jnp.arange(length) // (1 if fault == "causal" else z["block_length"])
    s = jnp.where(block[None, :] <= block[:, None], s, -jnp.inf)
    o = ops.a(ops.einsum("bhqk,bkhd->bqhd", ops.a(jax.nn.softmax(s, -1)), v))
    return ops.dot(o.reshape(b, length, heads * hd), p[name + "/o/kernel"])


def routing(p: dict, name: str, u, z: dict, fault=None):
    """`[T, experts]` weights: `w_e` on the chosen experts, 0 elsewhere; and the
    chosen sets `[T, top_k]`."""
    r = jax.nn.softmax(jnp.matmul(u, p[name + "/router/kernel"], precision=HI), -1)
    top = z["top_k"] // 2 if fault == "top_half" else z["top_k"]
    value, chosen = jax.lax.top_k(r, top)
    if fault == "renorm_held":     # the weights of the experts held here made to sum to 1
        value = jnp.where(chosen < z["held"], value, 0.0)
    value = value / jnp.maximum(jnp.sum(value, -1, keepdims=True), 1e-30)
    weights = jnp.sum(jax.nn.one_hot(chosen, z["experts"]) * value[..., None], -2)
    return weights, chosen


def experts(ops: Ops, p: dict, name: str, u, weights, held: slice):
    """The part of the layer's result that the experts `held` give."""
    g = ops.einsum("td,edf->etf", u, p[name + "/gate"][held])
    y = ops.a(jax.nn.silu(g) * ops.einsum("td,edf->etf", u, p[name + "/up"][held]))
    y = ops.einsum("etf,efd->etd", y, p[name + "/down"][held])
    return jnp.einsum("etd,te->td", y, weights[:, held], precision=HI)


def forward(ops: Ops, p: dict, ids, z: dict, fault=None, chosen_out=None):
    """Token ids `[B, L]` -> `[B, embed]`. `chosen_out`, a list, takes each
    layer's chosen sets."""
    x = ops.a(p["embed/embedding"][ids])

    def layer(x, name):
        h = ops.a(rmsnorm(x, p[name + "/norm1/scale"], z["eps"]))
        x = ops.a(x + attention(ops, p, name + "/attn", h, z, fault))
        b, length, d = x.shape
        u = rmsnorm(x, p[name + "/norm2/scale"], z["eps"]).reshape(b * length, d)
        weights, chosen = routing(p, name + "/moe", u, z, fault)
        y = experts(ops, p, name + "/moe", ops.a(u), weights, slice(0, z["held"]))
        return ops.a(x + y.reshape(b, length, d)), chosen

    for i in range(z["layers"]):
        x, chosen = jax.checkpoint(lambda x_, n=f"layer_{i}": layer(x_, n))(x)
        if chosen_out is not None:
            chosen_out.append(chosen)
    f = jnp.mean(rmsnorm(x, p["norm/scale"], z["eps"]), 1)
    f = jax.nn.relu(jnp.matmul(f, p["fc_hidden/kernel"], precision=HI) + p["fc_hidden/bias"])
    return jnp.matmul(f, p["fc/kernel"], precision=HI) + p["fc/bias"]


def token_views(rows, lengths, key, step, seq_len: int, mask_id: int):
    """Two views of each document: independent contiguous crops of `seq_len`
    tokens, start uniform over the document's length, then a tenth of the
    positions set to `mask_id`. Keys: `fold_in(key, step)`, split by view,
    `fold_in` by the row's index in the batch, split into start and mask."""
    key_q, key_k = jax.random.split(jax.random.fold_in(key, step))

    def one(row, n, i, view_key):
        k_start, k_mask = jax.random.split(jax.random.fold_in(view_key, i))
        start = jax.random.randint(k_start, (), 0, jnp.maximum(n - seq_len, 0) + 1)
        ids = jax.lax.dynamic_slice_in_dim(row, start, seq_len)
        return jnp.where(jax.random.bernoulli(k_mask, MASK_PROB, (seq_len,)), mask_id, ids)

    index = jnp.arange(rows.shape[0])
    return tuple(jax.vmap(lambda r, n, i, vk=vk: one(r, n, i, vk))(rows, lengths[:, 0], index)
                 for vk in (key_q, key_k))
