"""A routed decoder stack with LEARNED SPARSE ATTENTION as a MoCo text encoder
for long documents: the `keye` family.

The language stack of Keye-VL-2.0-30B-A3B (Kwai-Keye, `config.json`:
`model_type` `KeyeVL2`; `sa_config`: a DeepSeek-Sparse-Attention indexer,
DeepSeek-V3.2-Exp report, lightning indexer eq. 1 and top-k token selection),
read as an encoder of token sequences. `x` is `[B, L, hidden]`, positions
`0..L-1`:

  - `x = E[ids]`.
  - Layer: `h = N1(x)`; `a = x + Attn(h)`; `y = a + MoE(N2(a))`. `N*` RMSNorm.
    `MoE` is `models/sdar.py::Experts` unchanged (the router over all of its
    outputs in float32, softmax, `top_k`, renormalised over those, the held
    experts' part of the sum, no capacity, no drop): the layer IS `sdar.Layer`,
    handed another attention.
  - Indexer (reads the same `h`): `qI = h Wq` as `index_heads` heads of
    `index_dim`; `kI = LN(h Wk)`, ONE head of `index_dim` shared by all, `LN` a
    LayerNorm with scale and bias; `w = h Ww`, `index_heads` numbers; rotate-half
    rotary over the whole `index_dim` of `qI` and `kI`.
    `I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])` for `s <= t`: operands in
    `dtype`, float32 accumulation, float32 scores. No scale on `w` or on the
    product: a positive factor does not change the order.
  - Selection: `S_t` = the `min(index_topk, t + 1)` keys `s <= t` of largest
    `I[t, s]`, equal scores to the lower `s` (what `lax.top_k` returns). Per
    token, per batch row, shared by all heads, and under `stop_gradient`:
    nothing flows into `I`.
  - `Attn(h)`: `q = h Wq'` as `heads` heads of `head_dim`, `k`, `v` as `kv_heads`,
    no bias; per-head RMSNorm on q and k; rotate-half rotary over the whole head;
    `softmax_{s in S_t}(q_t . k_s / sqrt(head_dim)) v_s`, softmax in float32, a
    query head with its group's key/value head; `Wo`.
  - Output: RMSNorm, the mean over the positions, the v2 MLP head (as
    `models/sdar.py`'s: the encoder IS `SDAREncoder`).

Constants of the step: the indexer's five leaves a layer (`Wq`, `Wk`, `Ww`,
`LN`'s scale and bias). The selection passes them no gradient, and the loss
that would train them is left out, so they stay what they were made (the
optimizer's mask keeps the weight decay off them too: `sdar.trainable_mask`,
beside a share's router). The momentum copy still follows them.

Left out: the vision tower and its projector (no image enters a text encoder;
with text alone `mrope_section`'s three position axes hold the same index,
which is the plain rotary above); the untied output head (an encoder has no
token logits); the loss that aligns an indexer with the attention it serves (KL
between the head-summed attention weights over `S_t` and `softmax(I[t, S_t])`,
the sparse training stage of the DeepSeek-V3.2-Exp report): it needs the
attention weights out of the kernel, which no kernel of this package hands out.
`sa_config`'s `q_chunk_size` / `kv_chunk_size` are read as the released code's
tiling of the score and top-k computation: selection is per query token, so
they change no result and nothing here reads them.

Where the work happens. Nothing of shape `[.., L, L]` a head reaches memory on
a TPU at the published sizes: the indexer's per-head products and the attention
scores live in VMEM (`ops/pallas_select.py::index_scores`,
`ops/pallas_attention.py::masked_attention`, an online-softmax pair that takes
WHICH pairs are live as an int8 operand). What does reach memory: the
head-summed index scores `[B, L, L]` float32, one layer at a time under remat,
and the selection `[B, L, L]` int8, which a rematerialised layer KEEPS from the
query forward to the backward pass with the attention kernel's output and
log-sum-exp (it carries `KEPT_LIVE`, one of `sdar.KEPT`'s names: the backward
pass runs no indexer and no selection again, and holds a byte a pair a layer for
it; the key forward keeps nothing). The selection itself is exact and sorts nothing
(`ops/pallas_select.py::select_top_k`: bisection on the scores' bits). Any other
backend or shape (`keye_tiny`, every CPU test) takes an einsum, `lax.top_k` and
`sdar.einsum_attention` under the same mask: the kernels' oracle. The rules are
`ops/pallas_select.py::select_plan` and `attention_plan`, on what the code can
observe; the `setup` event's `attn` block says which was built. The attention
kernels compute every key chunk that reaches under the diagonal, whatever the
selection holds there: `sel_live_tile_share` counts what a kernel that skipped
dead tiles would still have to do.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from moco_tpu.models import sdar
from moco_tpu.models.sdar import (MOE_STATS, Attention, SDAREncoder, dispatch_path,  # noqa: F401
                                  kept_by_remat, rotary)
from moco_tpu.ops.pallas_attention import KEPT_LIVE, TILE
from moco_tpu.ops.pallas_select import index_scores, select_plan, select_top_k
from moco_tpu.telemetry import scopes

# the published sizes by arch (config.json's keys in the comments); the cut to
# one chip (layers, experts held, vocabulary slice) is the config's
KEYE_SIZES = {
    # https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
    "keye_vl2_30b_a3b": dict(
        hidden=2048,            # hidden_size
        layers=48,              # num_hidden_layers
        heads=32,               # num_attention_heads
        kv_heads=4,             # num_key_value_heads
        head_dim=128,           # head_dim
        experts=128,            # num_experts
        top_k=8,                # num_experts_per_tok (norm_topk_prob: true)
        expert_width=768,       # moe_intermediate_size
        vocab=151936,           # vocab_size
        rope_theta=1e7,         # rope_theta
        eps=1e-6,               # rms_norm_eps
        block_length=1,         # a causal mask: blocks of one position
        index_heads=16,         # sa_config.indexer_num_heads
        index_dim=64,           # sa_config.indexer_head_dim (indexer_num_kv_heads: 1)
        index_topk=2048,        # sa_config.topk
    ),
    # test size: the same mechanisms, nothing else
    "keye_tiny": dict(
        hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=16, top_k=4,
        expert_width=32, vocab=512, rope_theta=1e7, eps=1e-6, block_length=1,
        index_heads=4, index_dim=8, index_topk=16,
    ),
}
SIZES = KEYE_SIZES
# what the selection counted on the way, beside the router's counts
SEL_STATS = "sel_stats"
STAT_COLLECTIONS = (MOE_STATS, SEL_STATS)
CONSTANT_MODULES = ("indexer",)    # the door's `constant_modules` reads it
# each layer's selection `[B, L, L]`, for whoever asks by making the collection
# mutable (perfbench/calibrate_sparse.py: the share of pairs that differ from the
# float32 reference's); never in the step
SEL_CHOICES = "sel_choices"


def health(counted, tokens: int) -> dict:
    """The family's counters for the step's stride-gated `health` block: the
    routed layer's (`sdar.health`), the mean size of a query's selection over
    queries and layers (`sum_t min(t + 1, topk) / L` where the selection is
    exact), and the share of the `TILE x TILE` score tiles on or under the
    diagonal that hold a selected pair, the worst layer's: what a kernel that
    skipped dead tiles could not skip."""
    layers = [layer["indexer"] for layer in counted[SEL_STATS].values()]
    return {**sdar.health(counted, tokens),
            "h_sel_keys_per_query": jnp.mean(jnp.stack([c["keys_per_query"] for c in layers])),
            "h_sel_live_tile_share": jnp.max(jnp.stack([c["live_tile_share"] for c in layers]))}


def causal_scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """`I[t, s]` as plain einsums, the per-head products through memory: q
    `[B, L, heads, dim]`, k `[B, L, dim]`, w `[B, L, heads]` -> float32
    `[B, L, L]`. `ops/pallas_select.py::index_scores`' oracle."""
    s = jnp.einsum("bthd,bsd->bths", q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None], 2)


def top_k_selection(scores: jax.Array, topk: int) -> jax.Array:
    """int8 `[B, L, L]`: 1 where query `t` selects key `s`, by `lax.top_k` over
    each row's causal keys. `ops/pallas_select.py::select_top_k`'s oracle."""
    b, length, _ = scores.shape
    causal = jnp.tril(jnp.ones((length, length), bool))
    # -0.0 as 0.0: the two are one score, and `top_k` sees a float's bits
    ranked = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, chosen = lax.top_k(ranked, min(topk, length))
    picked = jnp.zeros((b, length, length), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(length)[None, :, None], chosen].set(True)
    return (picked & causal).astype(jnp.int8)


def live_tile_share(live: jax.Array) -> jax.Array:
    """Of the `TILE x TILE` tiles of `live` on or under the diagonal, the share
    that hold a selected pair (a view shorter than a tile is one tile)."""
    b, length, _ = live.shape
    tile = TILE if length % TILE == 0 else length
    side = length // tile
    any_live = jnp.max(live.reshape(b, side, tile, side, tile), (2, 4)).astype(jnp.float32)
    return jnp.sum(any_live) / (b * side * (side + 1) // 2)


class Indexer(nn.Module):
    """`h -> live`: the keys each query selects, int8 `[B, L, L]`, a constant of
    the step. `index` (the projections, LayerNorm, rotary and scores) and
    `select` (the top-k) are `attn`'s siblings in the trace."""

    heads: int
    dim: int
    topk: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, length, _ = h.shape
        kernels = select_plan(length, self.topk, self.dim) == "kernels"
        h = lax.stop_gradient(h)     # nothing flows into the scores

        def proj(n, name):
            # float32 out as `sdar.Attention`'s projections hand theirs on
            return nn.Dense(n, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                            name=name)(h).astype(jnp.float32)

        with jax.named_scope(scopes.INDEX):
            q = proj(self.heads * self.dim, "q").reshape(b, length, self.heads, self.dim)
            k = nn.LayerNorm(epsilon=self.eps, dtype=jnp.float32, param_dtype=jnp.float32,
                             use_fast_variance=False, name="k_norm")(proj(self.dim, "k"))
            q = rotary(q, self.rope_theta).astype(self.dtype)
            k = rotary(k[:, :, None, :], self.rope_theta)[:, :, 0].astype(self.dtype)
            q, k, w = lax.stop_gradient((q, k, proj(self.heads, "w")))
            scores = index_scores(q, k, w) if kernels else causal_scores(q, k, w)
        with jax.named_scope(scopes.SELECT):
            # by name, for a rematerialised layer's policy to keep (`sdar.KEPT`): the
            # counters below and the attention read the named value, so nothing of a
            # backward pass asks for the scores or the selection again
            live = checkpoint_name((select_top_k if kernels else top_k_selection)(
                scores, self.topk), KEPT_LIVE)
            for name, value in (("keys_per_query", jnp.sum(live, dtype=jnp.float32) / (b * length)),
                                ("live_tile_share", live_tile_share(live))):
                self.sow(SEL_STATS, name, value, reduce_fn=lambda _, new: new,
                         init_fn=lambda: None)
        self.sow(SEL_CHOICES, "live", live, reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return live


def selecting_attention(z: dict, dtype, h: jax.Array) -> jax.Array:
    """`sdar.Layer`'s `attention`: `Attn(h)` from the layer's sizes, as two
    modules of the layer: the `indexer`, and `sdar.Attention` under `attn` over
    the pairs it selected."""
    live = Indexer(z["index_heads"], z["index_dim"], z["index_topk"], z["rope_theta"], z["eps"],
                   dtype, name="indexer")(h)
    with jax.named_scope(scopes.ATTN):
        return Attention(z["heads"], z["kv_heads"], z["head_dim"], z["block_length"],
                         z["rope_theta"], z["eps"], dtype, name="attn")(h, live)


def build(arch: str, num_classes: int | None = None, *, layers: int = 0, held: int = 0,
          vocab: int = 0, **kwargs) -> SDAREncoder:
    """`layers` / `held` / `vocab`: 0 is the arch's own (published) number."""
    if arch not in KEYE_SIZES:
        raise ValueError(f"unknown keye arch {arch!r}; choose from {sorted(KEYE_SIZES)}")
    z = KEYE_SIZES[arch]
    held = held or z["experts"]
    if not 0 < held <= z["experts"]:
        raise ValueError(f"experts held must be in 1..{z['experts']}, got {held}")
    return SDAREncoder(tuple(sorted(z.items())), layers or z["layers"], held,
                       vocab or z["vocab"], num_classes=num_classes,
                       attention=selecting_attention, **kwargs)
