"""A routed decoder stack with LATENT ATTENTION and SHARED EXPERTS as a MoCo
text encoder: the `kanana` family.

The published stack of kanana-2-30b-a3b-instruct-2601 (kakaocorp,
`config.json`: `model_type` `deepseek_v3`), read as an encoder of token
sequences. `x` is `[B, L, hidden]`, positions `0..L-1`:

  - `x = E[ids]`. Layer: `a = x + MLA(N1(x))`; `y = a + F(N2(a))`. `N*` RMSNorm.
  - `MLA(h)` (`q_lora_rank` null): `q = h Wq`, `heads` heads of `nope + rope`,
    each split into `q_nope` and `q_pe`; `[c | k_pe] = h Wkva`, `c` of
    `kv_rank`, `k_pe` ONE head of `rope` shared by all; `c = RMSNorm(c)`
    (`kv_a_layernorm`); `[k_nope | v] = c Wkvb`, `heads` heads of `nope` and of
    `v_dim`. Interleaved rotary (pairs `(2i, 2i + 1)`) on `q_pe` and `k_pe`
    only. `o = softmax_causal((q_nope . k_nope + q_pe . k_pe) / sqrt(nope +
    rope)) v`, softmax in float32; `Wo`.
  - `F` on the first `dense_layers` layers (`first_k_dense_replace`): a SwiGLU
    of `dense_width`. On the others: `Shared(u) + sum_{e held, e in T(u)} g_e
    Expert_e(u)` with `s = sigmoid(u Wr)` in float32, `T(u)` the `top_k`
    largest of `s + b` (`b` the router's `correction_bias`; one group, so no
    group stage), `g_e = routed_scale * s_e / sum_T s` (the weights from `s`,
    not `s + b`). `Shared` is one SwiGLU of `shared_width` (`n_shared_experts *
    moe_intermediate_size`), each expert a SwiGLU of `expert_width`: the routed
    layer is `models/sdar.py::Experts` under its `sigmoid` rule.
  - Output: RMSNorm, the mean over the positions, the v2 MLP head (the encoder
    IS `sdar.SDAREncoder`, handed this attention and this feed-forward).

The weights' column order is this module's, the same in the plain reference:
`Wq`'s columns are every head's nope part, then every head's rotary part;
`Wkvb`'s every head's k_nope, then every head's v; `Wkva`'s the latent, then
`k_pe`. A published checkpoint interleaves them by head, which is a
permutation of columns and changes no number.

Constants of the step: a share's router kernel (`sdar.py` has the reason) and
the correction bias, whatever the share: its gradient is nought (it moves which
experts are chosen and no weight), and the rule that moves it in DeepSeek-V3
reads the load over every chip (`CONSTANT_MODULES` keeps the weight decay off
it too).

Where the attention happens: on a TPU, with `nope` and `v_dim` whole lane
tiles, two rotary parts to a lane tile and a view of whole key chunks (the
published sizes at 4 096 tokens), in `ops/pallas_mla.py`'s kernel pair, the
scores in VMEM, the rotary key read once a key chunk for all heads. Any other
backend or shape (`kanana_tiny`, every CPU test) takes `latent_einsum_attention`,
the float32 scores through memory: the kernels' oracle. The rule is
`ops/pallas_mla.py::mla_plan`; the `setup` event's `attn` block says which path
was built, beside the qk and v widths and the latent rank.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.models import sdar
from moco_tpu.models.sdar import (MOE_STATS, RMSNorm, SDAREncoder, SwiGLU,  # noqa: F401
                                  dispatch_path, health)
from moco_tpu.ops.pallas_mla import causal_latent_attention, mla_plan
from moco_tpu.telemetry import scopes

# the published sizes by arch (config.json's keys in the comments); the cut to
# one chip (layers, experts held, vocabulary slice) is the config's
KANANA_SIZES = {
    # https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
    "kanana_2_30b_a3b": dict(
        hidden=2048,            # hidden_size
        layers=48,              # num_hidden_layers
        heads=32,               # num_attention_heads (num_key_value_heads 32: MLA)
        nope=128,               # qk_nope_head_dim
        rope=64,                # qk_rope_head_dim
        v_dim=128,              # v_head_dim
        kv_rank=512,            # kv_lora_rank (q_lora_rank: null)
        dense_layers=1,         # first_k_dense_replace
        dense_width=6144,       # intermediate_size
        experts=128,            # n_routed_experts
        top_k=6,                # num_experts_per_tok (norm_topk_prob: true; n_group 1)
        expert_width=768,       # moe_intermediate_size
        shared_width=1536,      # n_shared_experts 2 x moe_intermediate_size
        routed_scale=2.448,     # routed_scaling_factor (scoring_func sigmoid, noaux_tc)
        vocab=128256,           # vocab_size
        rope_theta=1e6,         # rope_theta (rope_interleave: true, rope_scaling: null)
        eps=1e-6,               # rms_norm_eps
    ),
    # test size: the same mechanisms, nothing else
    "kanana_tiny": dict(
        hidden=64, layers=3, heads=4, nope=16, rope=8, v_dim=16, kv_rank=32, dense_layers=1,
        dense_width=96, experts=16, top_k=3, expert_width=32, shared_width=48,
        routed_scale=2.448, vocab=512, rope_theta=1e6, eps=1e-6,
    ),
}
SIZES = KANANA_SIZES
STAT_COLLECTIONS = (MOE_STATS,)
CONSTANT_MODULES = ("correction_bias",)    # the door's `constant_modules` reads it


def interleaved_rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over pairs `(2i, 2i + 1)` of the last axis, positions
    `0..L-1` on axis 1: `x'[2i] = x[2i] cos - x[2i+1] sin`, `x'[2i+1] = x[2i+1]
    cos + x[2i] sin` at angle `pos * theta ** (-2i / D)`. `x` float32."""
    length, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, length) + (1,) * (x.ndim - 3) + (dim // 2,))
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def latent_einsum_attention(q_nope, q_pe, k_nope, k_pe, v) -> jax.Array:
    """Causal latent attention as plain einsums, the float32 scores through
    memory: q_nope, k_nope `[B, L, heads, nope]`, q_pe `[B, L, heads, rope]`,
    k_pe `[B, L, rope]`, v `[B, L, heads, v_dim]` -> `[B, L, heads, v_dim]`.
    What runs wherever `ops/pallas_mla.py` does not, and its kernels' oracle."""
    length = q_nope.shape[1]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32))
    s = s / jnp.sqrt(jnp.float32(q_nope.shape[-1] + q_pe.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class LatentAttention(nn.Module):
    """`MLA(h)`: see the module's docstring. The latent's way (down-projection,
    its norm, up-projection, and k's rotary) is `mla_latent`, inside `attn`."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, length, hidden = h.shape
        heads, nope, rope, v_dim = self.heads, self.nope, self.rope, self.v_dim
        kernels = mla_plan(length, nope, rope, v_dim)["path"] == "tiled"

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                            name=name)

        q = dense(heads * (nope + rope), "q")(h)
        with jax.named_scope(scopes.MLA_LATENT):
            ckv = dense(self.kv_rank + rope, "kv_a")(h)
            c = RMSNorm(self.eps, name="kv_a_norm")(ckv[..., :self.kv_rank]).astype(self.dtype)
            kv = dense(heads * (nope + v_dim), "kv_b")(c)
            k_pe = interleaved_rotary(ckv[..., self.kv_rank:].astype(jnp.float32)[:, :, None],
                                      self.rope_theta)[:, :, 0].astype(self.dtype)
        q_nope, k_nope, v = q[..., :heads * nope], kv[..., :heads * nope], kv[..., heads * nope:]
        q_pe = interleaved_rotary(q[..., heads * nope:].astype(jnp.float32).reshape(
            b, length, heads, rope), self.rope_theta).astype(self.dtype)
        if kernels:
            o = causal_latent_attention(q_nope, q_pe.reshape(b, length, heads * rope), k_nope,
                                        k_pe, v, heads=heads)
        else:
            def split(x, d):
                return x.reshape(b, length, heads, d)

            o = latent_einsum_attention(split(q_nope, nope), q_pe, split(k_nope, nope), k_pe,
                                        split(v, v_dim)).reshape(b, length, heads * v_dim)
        return dense(hidden, "o")(o)


def latent_attention(z: dict, dtype, h: jax.Array) -> jax.Array:
    """`sdar.Layer`'s `attention`: `MLA(h)` under `attn`."""
    with jax.named_scope(scopes.ATTN):
        return LatentAttention(z["heads"], z["nope"], z["rope"], z["v_dim"], z["kv_rank"],
                               z["rope_theta"], z["eps"], dtype, name="attn")(h)


def feed_forward(z: dict, dtype, held: int, index: int, x: jax.Array) -> jax.Array:
    """`sdar.Layer`'s `ffn`: the leading dense layers' SwiGLU under `mlp`, then
    the routed layer with the shared experts and the sigmoid router."""
    if index < z["dense_layers"]:
        with jax.named_scope(scopes.MLP):
            u = RMSNorm(z["eps"], name="norm2")(x).astype(dtype)
            return x + SwiGLU(z["dense_width"], dtype, name="mlp")(u).astype(x.dtype)
    return sdar.routed_ffn(z, dtype, held, index, x, rule="sigmoid", scale=z["routed_scale"],
                           shared=z["shared_width"])


def attention_block(z: dict, length: int) -> dict:
    """The `attn` block of the `setup` event: `mla_plan`'s path and tiles, who
    prepares q and k (XLA: the interleaved rotary), the q.k and v widths and
    the latent rank."""
    return {**mla_plan(length, z["nope"], z["rope"], z["v_dim"]), "qk_prep": "xla",
            "qk_dim": z["nope"] + z["rope"], "v_dim": z["v_dim"], "latent_rank": z["kv_rank"]}


def kept_by_remat(z: dict, path: str, batch: int, length: int, dtype) -> dict | None:
    """`sdar.kept_by_remat` for this attention: the kernel's `o` is `v_dim` wide."""
    return sdar.kept_by_remat(dict(z, head_dim=z["v_dim"]), path, batch, length, dtype)


def build(arch: str, num_classes: int | None = None, *, layers: int = 0, held: int = 0,
          vocab: int = 0, **kwargs) -> SDAREncoder:
    """`layers` / `held` / `vocab`: 0 is the arch's own (published) number."""
    if arch not in KANANA_SIZES:
        raise ValueError(f"unknown kanana arch {arch!r}; choose from {sorted(KANANA_SIZES)}")
    z = KANANA_SIZES[arch]
    held = held or z["experts"]
    if not 0 < held <= z["experts"]:
        raise ValueError(f"experts held must be in 1..{z['experts']}, got {held}")
    return SDAREncoder(tuple(sorted(z.items())), layers or z["layers"], held,
                       vocab or z["vocab"], num_classes=num_classes,
                       attention=latent_attention, ffn=feed_forward, **kwargs)
