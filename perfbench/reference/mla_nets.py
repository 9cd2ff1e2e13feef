"""Plain float32 forward pass of a routed stack with latent attention and
shared experts as a sequence encoder: what `moco_mla.py` trains.

The equations (the model's public `config.json`, `model_type` `deepseek_v3`,
read with DeepSeek-V3's published attention and router). `x0 = E[ids]`; for
each layer, `h = RMSNorm(x; g1)`, positions `0..L-1`:

  - `q = h Wq`, `heads` heads of `nope + rope`; `[c | k_pe] = h Wkva`, `c` of
    `kv_rank`, `k_pe` ONE head of `rope` for all; `c = RMSNorm(c; gc)`;
    `[k_nope | v] = c Wkvb`, `heads` heads of `nope` and `v_dim`. Interleaved
    rotary (pairs `(2i, 2i + 1)`, theta `rope_theta`) on `q_pe` and `k_pe`.
    `s = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)`, causal; softmax;
    `x += (softmax(s) v) Wo`.
  - `u = RMSNorm(x; g2)`. The first `dense_layers` layers: `x += SwiGLU(u)` of
    `dense_width`. The others: `s_r = sigmoid(u Wr)`, `T` the `top_k` largest
    of `s_r + b` (`b` the router's correction bias), `w_e = routed_scale *
    s_r[e] / sum_T s_r`, and `x += Shared(u) + sum_{e in T and held} w_e
    SwiGLU_e(u)`: the held experts are the first `held`, what the others would
    add is left out, the shared experts' SwiGLU of `shared_width` is whole.

After the last layer RMSNorm, the mean over positions, the MoCo v2 head. The
weights' column order is the program's (`Wq`: every head's nope part, then
every head's rotary part; `Wkvb`: every head's k_nope, then every v; `Wkva`:
the latent, then `k_pe`): a permutation of the published checkpoint's columns.

Departures, none of which changes a number: every expert here runs over every
token and is weighted by `w_e` (0 where the token did not choose it); the
scores are computed for `BLOCK` query rows at a time, each block rematerialised
in the backward pass. Products go through `nets.Ops`; the router's product, the
sigmoid, the softmax, the norms and the head stay float32. `fault` plants one
departure, for the readings the limits are set against.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference import seq_nets
from perfbench.reference.nets import HI, Ops, _dense_spec
from perfbench.reference.seq_nets import rmsnorm

# published sizes (https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json)
SIZES = {
    "kanana_2_30b_a3b": dict(hidden=2048, layers=48, heads=32, nope=128, rope=64, v_dim=128,
                             kv_rank=512, dense_layers=1, dense_width=6144, experts=128, top_k=6,
                             expert_width=768, shared_width=1536, routed_scale=2.448,
                             vocab=128256, rope_theta=1e6, eps=1e-6),
    "kanana_tiny": dict(hidden=64, layers=3, heads=4, nope=16, rope=8, v_dim=16, kv_rank=32,
                        dense_layers=1, dense_width=96, experts=16, top_k=3, expert_width=32,
                        shared_width=48, routed_scale=2.448, vocab=512, rope_theta=1e6, eps=1e-6),
}
BLOCK = 512     # query rows whose scores are in memory at once
# rotate-half for the interleaved rotary; the latent's RMSNorm left out; the shared
# experts left out; the correction bias carried into the weights; the scores scaled
# by 1 / sqrt(nope) for 1 / sqrt(nope + rope)
FAULTS = ("rotate_half", "no_kv_norm", "no_shared", "bias_in_weights", "scale_128")
# the correction bias's draw: `harness.make_weights` gives a "normal" leaf the
# deviation sqrt(2 / fan_in), 0.01 here: about the gap between the router's
# scores at the cut (the 6th and 7th best of 128 lie 0.006 apart), so that the
# bias decides near-ties, as a bias that evens the load does. At 0.1 it alone
# picks among the top twenty and crowds the held experts (the configuration's
# `assumed` has the readings)
BIAS_FAN_IN = 20000


def sizes_for(cfg: dict) -> dict:
    """The arch's published sizes with the configuration's cut laid over them."""
    z = dict(SIZES[cfg["arch"]])
    z["layers"] = cfg.get("num_hidden_layers") or z["layers"]
    z["held"] = cfg.get("num_experts") or z["experts"]
    z["vocab"] = cfg.get("vocab_size") or z["vocab"]
    return z


def _swiglu_spec(p: str, d: int, width: int) -> list:
    return [(p + "/gate/kernel", (d, width), "normal", d), (p + "/up/kernel", (d, width), "normal", d),
            (p + "/down/kernel", (width, d), "normal", width)]


def spec(z: dict, embed_dim: int) -> list:
    d, h, rank = z["hidden"], z["heads"], z["kv_rank"]
    out = [("embed/embedding", (z["vocab"], d), "normal", 2)]    # unit variance
    for i in range(z["layers"]):
        p = f"layer_{i}"
        out += [(p + "/norm1/scale", (d,), "ones", 0),
                (p + "/attn/q/kernel", (d, h * (z["nope"] + z["rope"])), "normal", d),
                (p + "/attn/kv_a/kernel", (d, rank + z["rope"]), "normal", d),
                (p + "/attn/kv_a_norm/scale", (rank,), "ones", 0),
                (p + "/attn/kv_b/kernel", (rank, h * (z["nope"] + z["v_dim"])), "normal", rank),
                (p + "/attn/o/kernel", (h * z["v_dim"], d), "normal", h * z["v_dim"]),
                (p + "/norm2/scale", (d,), "ones", 0)]
        if i < z["dense_layers"]:
            out += _swiglu_spec(p + "/mlp", d, z["dense_width"])
            continue
        w = z["expert_width"]
        out += [(p + "/moe/router/kernel", (d, z["experts"]), "normal", d),
                (p + "/moe/router/correction_bias", (z["experts"],), "normal", BIAS_FAN_IN),
                (p + "/moe/gate", (z["held"], d, w), "normal", d),
                (p + "/moe/up", (z["held"], d, w), "normal", d),
                (p + "/moe/down", (z["held"], w, d), "normal", w)]
        out += _swiglu_spec(p + "/moe/shared", d, z["shared_width"])
    out += [("norm/scale", (d,), "ones", 0)]
    return out + _dense_spec("fc_hidden", d, d) + _dense_spec("fc", d, embed_dim)


def interleaved_rope(x, theta):
    """`x`: `[B, L, H, D]`; pairs `(2i, 2i + 1)` rotated by `pos * theta ** (-2i / D)`."""
    length, dim = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = (jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :])[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    return out.reshape(x.shape)


def swiglu(ops: Ops, p: dict, name: str, u):
    y = ops.a(jax.nn.silu(ops.dot(u, p[name + "/gate/kernel"])) * ops.dot(u, p[name + "/up/kernel"]))
    return ops.dot(y, p[name + "/down/kernel"])


def attention(ops: Ops, p: dict, name: str, h, z: dict, fault=None):
    b, length, _ = h.shape
    heads, dn, dr, dv, rank = z["heads"], z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    q = ops.dot(h, p[name + "/q/kernel"])
    ckv = ops.dot(h, p[name + "/kv_a/kernel"])
    c = ckv[..., :rank]
    if fault != "no_kv_norm":
        c = rmsnorm(c, p[name + "/kv_a_norm/scale"], z["eps"])
    kv = ops.dot(ops.a(c), p[name + "/kv_b/kernel"])
    rot = ((lambda x: seq_nets.rope(x, z["rope_theta"])) if fault == "rotate_half"
           else (lambda x: interleaved_rope(x, z["rope_theta"])))
    q_nope = ops.a(q[..., :heads * dn]).reshape(b, length, heads, dn)
    q_pe = ops.a(rot(q[..., heads * dn:].reshape(b, length, heads, dr)))
    k_nope = ops.a(kv[..., :heads * dn]).reshape(b, length, heads, dn)
    k_pe = ops.a(rot(ckv[..., rank:][:, :, None, :]))[:, :, 0]
    v = ops.a(kv[..., heads * dn:]).reshape(b, length, heads, dv)
    scale = 1.0 / math.sqrt(dn if fault == "scale_128" else dn + dr)
    rows = min(BLOCK, length)

    @jax.checkpoint
    def block(qn_rows, qp_rows, first):
        position = first + jnp.arange(rows)
        s = (ops.einsum("bqhd,bkhd->bhqk", qn_rows, k_nope)
             + ops.einsum("bqhd,bkd->bhqk", qp_rows, k_pe)) * scale
        s = jnp.where((jnp.arange(length)[None, :] <= position[:, None])[None, None], s, -jnp.inf)
        return ops.einsum("bhqk,bkhd->bqhd", ops.a(jax.nn.softmax(s, -1)), v)

    def by_blocks(x):     # [B, L, ...] -> [L / rows, B, rows, ...]
        return jnp.moveaxis(x.reshape(b, length // rows, rows, *x.shape[2:]), 1, 0)

    o = jax.lax.map(lambda a: block(*a), (by_blocks(q_nope), by_blocks(q_pe),
                                          jnp.arange(0, length, rows)))
    o = ops.a(jnp.moveaxis(o, 0, 1).reshape(b, length, heads * dv))
    return ops.dot(o, p[name + "/o/kernel"])


def routing(p: dict, name: str, u, z: dict, fault=None):
    """`[T, experts]` weights: `w_e` on the chosen experts, 0 elsewhere; and the
    chosen sets `[T, top_k]`."""
    s = jax.nn.sigmoid(jnp.matmul(u, p[name + "/router/kernel"], precision=HI))
    bias = p[name + "/router/correction_bias"]
    _, chosen = jax.lax.top_k(s + bias, z["top_k"])
    value = jnp.take_along_axis(s + bias if fault == "bias_in_weights" else s, chosen, -1)
    value = value / (jnp.sum(value, -1, keepdims=True) + 1e-20) * z["routed_scale"]
    weights = jnp.sum(jax.nn.one_hot(chosen, z["experts"]) * value[..., None], -2)
    return weights, chosen


def routed(ops: Ops, p: dict, name: str, u, z: dict, fault=None, chosen_out=None):
    """The part of the layer's result that the held and the shared experts give."""
    weights, chosen = routing(p, name, u, z, fault)
    if chosen_out is not None:
        chosen_out.append(chosen)
    y = seq_nets.experts(ops, p, name, ops.a(u), weights, slice(0, z["held"]))
    return y if fault == "no_shared" else y + swiglu(ops, p, name + "/shared", ops.a(u))


def forward(ops: Ops, p: dict, ids, z: dict, fault=None, chosen_out=None):
    """Token ids `[B, L]` -> `[B, embed]`. `chosen_out`, a list, takes each
    routed layer's chosen sets."""
    x = ops.a(p["embed/embedding"][ids])

    def layer(x, name, dense):
        h = ops.a(rmsnorm(x, p[name + "/norm1/scale"], z["eps"]))
        x = ops.a(x + attention(ops, p, name + "/attn", h, z, fault))
        b, length, d = x.shape
        u = rmsnorm(x, p[name + "/norm2/scale"], z["eps"]).reshape(b * length, d)
        taps: list = []
        y = (swiglu(ops, p, name + "/mlp", ops.a(u)) if dense
             else routed(ops, p, name + "/moe", u, z, fault, taps))
        return ops.a(x + y.reshape(b, length, d)), taps

    for i in range(z["layers"]):
        x, taps = jax.checkpoint(
            lambda x_, n=f"layer_{i}", dense=i < z["dense_layers"]: layer(x_, n, dense))(x)
        if chosen_out is not None:
            chosen_out.extend(taps)
    f = jnp.mean(rmsnorm(x, p["norm/scale"], z["eps"]), 1)
    f = jax.nn.relu(jnp.matmul(f, p["fc_hidden/kernel"], precision=HI) + p["fc_hidden/bias"])
    return jnp.matmul(f, p["fc/kernel"], precision=HI) + p["fc/bias"]
