"""A routed decoder stack as a MoCo text encoder: the `sdar_moe` family.

The published stack of SDAR-30B-A3B-Chat (JetLM, `config.json`: `model_type`
`sdar_moe`), read as an encoder of token sequences: token embedding, pre-norm
blocks with RMSNorm, grouped-query attention with per-head RMSNorm on q and k
and rotary positions, a BLOCK-CAUSAL mask (bidirectional inside a block of
`block_length` positions, causal from block to block: how SDAR's forward pass
sees a clean sequence), and in every block a routed expert layer. After the
last block: RMSNorm, the mean over the positions, the v2 MLP head.

The expert layer is told WHICH experts it holds (`experts_held`, the first
`n` of the router's `num_experts`): it routes over all of them, keeps
`top_k` a token, renormalises over those, and computes the part of the
result that its own experts give. What the other experts would add is left
out; on one chip of an expert-parallel deployment that is this chip's share
of the layer, without the exchange. No capacity factor: the groups are of
uneven size (`lax.ragged_dot` over rows sorted by expert) and no assignment
to a held expert is dropped: a pass works on a static buffer of twice the rows
that uniform routing sends here (the grouped product runs over all of it, the
unassigned rows as zeros); what a router sends beyond it first meets a pass
an eighth that size, and whole passes after that.

A share does not train its router. The router is replicated over the chips
that share the layer, and its gradient is whole only once every chosen
expert's output has come back through the exchange; from its own experts
alone a chip sees the absent ones as experts that add nothing, and Adam then
walks the held experts' logits down together until the chip stands empty (at
lr 1e-4 a token met 0.14 held experts by step 17 where 1.0 is uniform). So
where `held < experts` the router's kernel is a constant of the step
(`stop_gradient` here, `trainable_mask` for the optimizer's decay: the
frozen patch projection's pattern, `v3_step.patch_embed_trainable_mask`); the
gradient still flows through the logits into the layer's input. The whole
layer trains its router.

float32 whatever `dtype` is: the router's product and softmax, the
attention softmax, every RMSNorm, the head. Parameters are float32.

Where the attention softmax happens, and q and k's norm and rotary: on a TPU,
with `head_dim` and the view's length multiples of 128 and `block_length` a
divisor of 128 (the published arch at 512 tokens), in VMEM, inside
`ops/pallas_attention.py`'s kernels; the scores never reach memory, the tiles
the mask empties are not computed, and q, k, v and o keep the projections'
`[B, L, heads * head_dim]` from `Dense` to `Dense` (`norm_rotary` reads a
projection's output once and writes the finished q or k once). Any other
backend or shape (`sdar_tiny`, every CPU test) takes `RMSNorm`, `rotary` and
`einsum_attention` on `[B, L, heads, head_dim]`, the float32 scores through
memory: the kernels' oracle. One rule, `attention_plan`, on what the code can
observe; the run's `setup` event says which path was built.

Where the routed layer's rows move between the token order `[tokens, hidden]`
and the expert-sorted buffer `[n, hidden]`: on a TPU, with `hidden` a multiple
of 128, every pass's `n` and `tokens` multiples of 128 (the published
arch at 32 views of 512 tokens), by asynchronous copies inside
`ops/pallas_dispatch.py`'s two kernels, each the other's transpose: `dispatch`
fetches a tile of buffer rows by their tokens and masks the rows past the last
assignment in VMEM; `combine` sums each token's rows, weighted in float32, into
a float32 `[tokens, hidden]` written once (a gather in token order over a
second, small sort of the pass's rows: no zero tensor, no float32 `[n, hidden]`
product in memory). Any other backend or shape takes `ub[token]`, two `where`s,
`y.astype(float32) * w` and a scatter-add: the kernels' oracle. One rule again,
`dispatch_plan`, asked of every pass's size (`dispatch_path`: one answer a
layer); the `setup` event's `moe` block says which. The grouped products, the
sort by expert and the arithmetic (float32 weights, float32 sums) are the same
on both paths.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.ops.pallas_attention import (KEPT_LIVE, KEPT_LSE, KEPT_OUT, attention_plan,
                                            block_causal_attention, masked_attention, norm_rotary)
from moco_tpu.ops.pallas_dispatch import combine, dispatch, dispatch_plan, listing
from moco_tpu.telemetry import scopes

# the published sizes by arch (config.json's keys in the comments); the
# cut to one chip (layers, experts held, vocabulary slice) is the
# config's, not the table's
SDAR_SIZES = {
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
    "sdar_30b_a3b": dict(
        hidden=2048,            # hidden_size
        layers=48,              # num_hidden_layers
        heads=32,               # num_attention_heads
        kv_heads=4,             # num_key_value_heads
        head_dim=128,           # head_dim
        experts=128,            # num_experts
        top_k=8,                # num_experts_per_tok (norm_topk_prob: true)
        expert_width=768,       # moe_intermediate_size
        vocab=151936,           # vocab_size
        rope_theta=1e6,         # rope_theta
        eps=1e-6,               # rms_norm_eps
        block_length=4,         # not in config.json: the family's default
    ),
    # test size: the same mechanisms, nothing else
    "sdar_tiny": dict(
        hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=16,
        top_k=4, expert_width=32, vocab=512, rope_theta=1e6, eps=1e-6,
        block_length=2,
    ),
}
SIZES = SDAR_SIZES    # what `models/__init__.py`'s door for token encoders reads
# a collection of its own for what the router counted on the way: read by
# the step's stride-gated counters, never by the forward pass
MOE_STATS = "moe_stats"
STAT_COLLECTIONS = (MOE_STATS,)


def health(counted, tokens: int) -> dict:
    """The family's counters for the step's stride-gated `health` block, from
    what the forward pass sowed (each layer's assignments by held expert):
    assignments to held experts a token, over all layers (1.0 where routing is
    uniform and an eighth of the experts live here), and the fullest held
    expert's load over the mean load, the worst layer's."""
    counts = jnp.stack([c.astype(jnp.float32)
                        for c in jax.tree.leaves(counted[MOE_STATS])])   # [layers, held]
    mean = jnp.maximum(jnp.mean(counts, -1), 1e-9)
    return {"h_moe_assign_per_token": jnp.mean(jnp.sum(counts, -1)) / tokens,
            "h_moe_load_max_over_mean": jnp.max(jnp.max(counts, -1) / mean)}
# each layer's chosen experts `[tokens, top_k]`, for whoever asks by making the
# collection mutable (perfbench/calibrate_reference.py: the share of sets that
# differ from the float32 reference's); never in the step
MOE_CHOICES = "moe_choices"


def trainable_mask(params, constant=("router",)) -> Any:
    """Optimizer mask: False for every leaf under a module named in `constant`
    (the modules whose leaves are constants of the step: a share's `router`; a
    sparse-attention `indexer`, `models/keye.py`)."""

    def is_trainable(path, _leaf):
        return not any(getattr(entry, "key", None) in constant for entry in path)

    return jax.tree_util.tree_map_with_path(is_trainable, params)



def block_causal_mask(length: int, block_length: int) -> jax.Array:
    """`[L, L]` bool: position i sees j iff j's block is not after i's."""
    blocks = jnp.arange(length) // block_length
    return blocks[None, :] <= blocks[:, None]


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over the whole head, positions 0..L-1.
    `x` is `[B, L, H, D]` float32."""
    length, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def einsum_attention(q: jax.Array, k: jax.Array, v: jax.Array, block_length: int,
                     live: jax.Array | None = None) -> jax.Array:
    """Block-causal grouped-query attention as plain einsums, the float32
    scores through memory: q `[B, L, heads, D]`, k and v `[B, L, kv_heads, D]`
    -> `[B, L, heads, D]`. What runs wherever `ops/pallas_attention.py` does
    not, and those kernels' oracle. `live` (`[B or 1, L, L]`, nonzero where
    query `t` sees key `s`) stands in for the mask of positions."""
    b, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    # each key/value head serves `group` query heads
    q = q.reshape(b, length, kv_heads, heads // kv_heads, dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(dim))
    seen = (block_causal_mask(length, block_length) if live is None
            else live[:, None, None].astype(bool))
    s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, length, heads, dim)


class HeadScale(nn.Module):
    """An `RMSNorm`'s parameter without its arithmetic, under the same name in
    the tree: where `norm_rotary` does the norm, it takes the scale from here."""

    dim: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.dim,), jnp.float32)


class Attention(nn.Module):
    """`qk_norm` off leaves q and k's per-head RMSNorm out (and its two scales
    out of the tree): rotary alone, for an encoder that has none
    (`models/ouro.py`)."""

    heads: int
    kv_heads: int
    head_dim: int
    block_length: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.float32
    qk_norm: bool = True

    @nn.compact
    def __call__(self, h, live=None):
        """`live` (int8 `[B, L, L]`: which pairs a selection keeps,
        `ops/pallas_attention.py::masked_attention`) stands in for the mask of
        positions (`models/keye.py`)."""
        b, length, _ = h.shape
        path = (attention_plan(length, self.head_dim, self.block_length) if live is None else
                attention_plan(length, self.head_dim, self.block_length, masked=True))["path"]
        fused = path != "einsum"

        def proj(name, n):
            y = nn.Dense(n * self.head_dim, use_bias=False, dtype=self.dtype,
                         param_dtype=jnp.float32, name=name)(h)
            # fused, a head stays `head_dim` lanes of the projection's last axis
            # from here to the `o` projection: nothing is reshaped, nothing relaid
            return y if fused else y.reshape(b, length, n, self.head_dim)

        q, k, v = proj("q", self.heads), proj("k", self.kv_heads), proj("v", self.kv_heads)
        if fused:
            # float32 in as `RMSNorm` casts: see `norm_rotary`
            q, k = (norm_rotary(x.astype(jnp.float32),
                                HeadScale(self.head_dim, name=name)() if self.qk_norm else None,
                                dtype=self.dtype, theta=self.rope_theta, eps=self.eps,
                                head_dim=self.head_dim)
                    for x, name in ((q, "q_norm"), (k, "k_norm")))
            if path == "fused":
                o = block_causal_attention(q, k, v, heads=self.heads, kv_heads=self.kv_heads,
                                           block_length=self.block_length)
            else:
                # a view longer than a VMEM row of scores: the mask of positions
                # as the operand that a selection is, one for every batch row
                if live is None:
                    live = block_causal_mask(length, self.block_length).astype(jnp.int8)[None]
                o = masked_attention(q, k, v, live, heads=self.heads, kv_heads=self.kv_heads)
        else:
            def prep(x, name):
                x = RMSNorm(self.eps, name=name)(x) if self.qk_norm else x.astype(jnp.float32)
                return rotary(x, self.rope_theta).astype(self.dtype)

            q, k = prep(q, "q_norm"), prep(k, "k_norm")
            o = einsum_attention(q, k, v, self.block_length, live)
            o = o.reshape(b, length, self.heads * self.head_dim)
        return nn.Dense(h.shape[-1], use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, name="o")(o)


def held_rows(tokens: int, top_k: int, experts: int, held: int) -> int:
    """Rows of the buffer that the first pass of the held experts works on:
    twice what uniform routing sends here, or every assignment where the
    layer is whole. What a skewed router sends beyond it takes further,
    smaller passes (`Experts`): nothing is dropped."""
    if held == experts:
        return tokens * top_k
    return tokens * min(top_k, 2 * -(-top_k * held // experts))


def pass_sizes(tokens: int, top_k: int, experts: int, held: int) -> tuple[int, int, int]:
    """The static shape of a call of `Experts`: the first pass's `rows`, the
    rows of the small pass that what a skewed router sends beyond them meets
    first (an eighth of `rows`, so that a small spill costs about what it
    weighs; 0 where the buffer holds every assignment), and how many whole
    passes can follow that."""
    rows = held_rows(tokens, top_k, experts, held)
    spill = -(-rows // 8) if tokens * top_k > rows else 0
    return rows, spill, -(-max(tokens * top_k - rows - spill, 0) // rows)


def dispatch_path(tokens: int, hidden: int, top_k: int, experts: int, held: int,
                  backend: str | None = None) -> dict:
    """How the routed layer moves its rows for `tokens` rows a call on this
    backend (`ops/pallas_dispatch.py::dispatch_plan`, asked of every pass's
    size: one answer a layer), beside the passes' static sizes: the `moe` block
    of the run's `setup` event."""
    rows, spill, passes = pass_sizes(tokens, top_k, experts, held)
    plans = {dispatch_plan(tokens, hidden, n, backend) for n in (rows, spill) if n}
    return {"dispatch": "kernels" if plans == {"kernels"} else "xla", "rows": rows,
            "spill_rows": spill, "passes": passes}


class Router(nn.Module):
    """`u -> logits` over every expert, float32 at `highest`; `trains` False
    makes the kernel a constant of the step."""

    experts: int
    trains: bool = True

    @nn.compact
    def __call__(self, u):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (u.shape[-1], self.experts), jnp.float32)
        if not self.trains:
            kernel = lax.stop_gradient(kernel)
        return jnp.matmul(u.astype(jnp.float32), kernel, precision=lax.Precision.HIGHEST)


class Experts(nn.Module):
    """The routed expert layer's share: see the module's docstring."""

    experts: int
    held: int
    top_k: int
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        tokens, hidden = u.shape
        with jax.named_scope(scopes.MOE_ROUTER):
            logits = Router(self.experts, self.held == self.experts, name="router")(u)
            weight, expert = lax.top_k(jax.nn.softmax(logits, -1), self.top_k)
            weight = weight / jnp.sum(weight, -1, keepdims=True)

        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2,
                                                out_axis=-1, batch_axis=0)
        gate = self.param("gate", init, (self.held, hidden, self.width), jnp.float32)
        up = self.param("up", init, (self.held, hidden, self.width), jnp.float32)
        down = self.param("down", init, (self.held, self.width, hidden), jnp.float32)

        path = dispatch_path(tokens, hidden, self.top_k, self.experts, self.held)
        rows, spill, passes = path["rows"], path["spill_rows"], path["passes"]
        kernels = path["dispatch"] == "kernels"
        with jax.named_scope(scopes.MOE_DISPATCH):
            # assignments sorted by expert, those of experts held elsewhere last
            flat = jnp.where(expert < self.held, expert, self.held).reshape(-1)
            order = jnp.pad(jnp.argsort(flat, stable=True),
                            (0, rows + spill + passes * rows - flat.size))
            # a count by comparison: `bincount` is a scatter-add of 131 072 ones
            # into 17 bins, 1.1 ms a call on the chip (my chip run, PR 32)
            sizes = jnp.sum(flat[:, None] == jnp.arange(self.held)[None, :], 0, dtype=jnp.int32)
            ends = jnp.cumsum(sizes)
            assigned = ends[-1]
            ub, flat_weight = u.astype(self.dtype), weight.reshape(-1)
        self.sow(MOE_STATS, "held_counts", sizes, reduce_fn=lambda _, new: new,
                 init_fn=lambda: None)
        self.sow(MOE_CHOICES, "chosen", expert, reduce_fn=lambda _, new: new,
                 init_fn=lambda: None)

        def nothing():
            # typed like a pass's result: inside a shard_map region that varies
            # over the mesh axes, and scan and cond demand equal types
            zeros, vma = jnp.zeros((tokens, hidden), jnp.float32), tuple(jax.typeof(u).vma)
            return lax.pcast(zeros, vma, to="varying") if vma else zeros

        def one_pass(first, n):
            """The held experts' part for the sorted assignments `first ..
            first + n`: rows to the buffer, three grouped products, rows back
            to their tokens summed in float32. The rows move by
            `ops/pallas_dispatch.py`'s kernels or, where `dispatch_plan` says
            `xla`, by a gather and a scatter-add: the kernels' oracle."""
            with jax.named_scope(scopes.MOE_DISPATCH):
                take = lax.dynamic_slice_in_dim(order, first, n)
                token = take // self.top_k
                valid = (first + jnp.arange(n) < assigned)[:, None]
                w = jnp.where(valid, flat_weight[take][:, None], 0)
                if kernels:
                    count = jnp.clip(assigned - first, 0, n)
                    by_token = listing(token, count, w, tokens)
                    x = dispatch(ub, token, count, by_token, dtype=self.dtype)
                else:
                    x = jnp.where(valid, ub[token], 0)
                # each group's rows that fall inside this pass. The rows past the
                # last assignment are zero rows and ride in the last group: every
                # row of the static buffer lies in a group, so the product is
                # zero there by arithmetic (forward and transposed) and not by
                # what a kernel leaves outside its groups, and a step's time
                # does not depend on the router's luck with this chip's experts
                hi = jnp.clip(ends, first, first + n).at[-1].set(first + n)
                lo = jnp.clip(ends - sizes, first, first + n)
            with jax.named_scope(scopes.MOE_EXPERTS):
                g = lax.ragged_dot(x, gate.astype(self.dtype), hi - lo)
                y = jax.nn.silu(g) * lax.ragged_dot(x, up.astype(self.dtype), hi - lo)
                y = lax.ragged_dot(y, down.astype(self.dtype), hi - lo)
            with jax.named_scope(scopes.MOE_DISPATCH):
                if kernels:
                    return combine(y, w, token, count, by_token)
                return nothing().at[token].add(y.astype(jnp.float32) * w)

        def spilled(first, n):
            # a pass that runs keeps nothing for the backward pass but its
            # place (it is computed again there)
            return jax.checkpoint(lambda f: one_pass(f, n))(first)

        def overflow(acc, first):
            return lax.cond(first < assigned, lambda acc: acc + spilled(first, rows),
                            lambda acc: acc, acc), None

        y = one_pass(jnp.int32(0), rows)
        if spill:
            # where routing is near uniform nothing is left after the first pass,
            # and the passes after it cost neither time nor memory
            with jax.named_scope(scopes.MOE_DISPATCH):
                firsts = rows + spill + jnp.arange(passes, dtype=jnp.int32) * rows
                y = lax.cond(
                    assigned > rows,
                    lambda y: lax.scan(overflow, y + spilled(jnp.int32(rows), spill), firsts)[0],
                    lambda y: y, y)
        return y


class Layer(nn.Module):
    """`attention`: `None` for `Attention`, or a family's own `(sizes, dtype, h)
    -> Attn(h)`, which builds its modules in this layer and opens its own scopes
    (`models/keye.py`: an indexer and a selection beside `attn`, never inside
    it); the routed layer is this one."""

    sizes: Any            # an SDAR_SIZES entry as a tuple of items (hashable)
    held: int
    dtype: Any = jnp.float32
    attention: Any = None

    @nn.compact
    def __call__(self, x):
        z = dict(self.sizes)
        b, length, hidden = x.shape
        with jax.named_scope(scopes.ATTN):
            h = RMSNorm(z["eps"], name="norm1")(x).astype(self.dtype)
        if self.attention is None:
            with jax.named_scope(scopes.ATTN):
                a = Attention(z["heads"], z["kv_heads"], z["head_dim"], z["block_length"],
                              z["rope_theta"], z["eps"], self.dtype, name="attn")(h)
        else:
            a = self.attention(z, self.dtype, h)
        with jax.named_scope(scopes.ATTN):
            x = x + a
        with jax.named_scope(scopes.MOE_ROUTER):
            u = RMSNorm(z["eps"], name="norm2")(x).reshape(b * length, hidden)
        y = Experts(z["experts"], self.held, z["top_k"], z["expert_width"], self.dtype,
                    name="moe")(u)
        with jax.named_scope(scopes.MOE_DISPATCH):
            return x + y.reshape(b, length, hidden).astype(x.dtype)


class SDAREncoder(nn.Module):
    """Token ids `[B, L]` -> the pooled feature (`num_classes=None`) or the v2 MLP head's
    embedding. `layers`, `held` and `vocab` are the cut: how deep, which experts (the first
    `held`) and which slice of the vocabulary (its first `vocab` ids) live here. `remat`: the
    backward pass makes each layer again from its input and from what `KEPT` (below) names."""

    sizes: Any
    layers: int
    held: int
    vocab: int
    num_classes: int | None = None
    mlp_head: bool = True
    remat: bool = False
    dtype: Any = jnp.float32
    attention: Any = None      # `Layer`'s

    @nn.compact
    def __call__(self, ids, train: bool = True):
        z = dict(self.sizes)
        with jax.named_scope(scopes.EMBED_POOL):
            x = nn.Embed(self.vocab, z["hidden"], dtype=self.dtype, param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(1.0), name="embed")(
                ids.astype(jnp.int32))
        layer_cls = nn.remat(Layer, policy=kept_policy()) if self.remat else Layer
        for i in range(self.layers):
            x = layer_cls(self.sizes, self.held, self.dtype, self.attention,
                          name=f"layer_{i}")(x)
        with jax.named_scope(scopes.EMBED_POOL):
            feat = jnp.mean(RMSNorm(z["eps"], name="norm")(x), axis=1)
            if self.num_classes is None:
                return feat
            if self.mlp_head:
                feat = nn.relu(nn.Dense(z["hidden"], param_dtype=jnp.float32,
                                        name="fc_hidden")(feat))
            return nn.Dense(self.num_classes, param_dtype=jnp.float32, name="fc")(feat)


# What a rematerialised layer keeps from the query forward to the backward pass,
# beside its input. The rule: a value stays if it is dear to make again and cheap
# to hold, by the milliseconds of a step that a GB held buys. In the long-document
# cell (2 views of 8 192 tokens a pass, 4 layers, a v5e; `PERF.md` section 6, PR 34:
# each row kept alone on the chip, a step of 646.7 ms without either):
#
#   kept a layer                          bytes     not run again     ms a step  ms a GB
#   the tiled attention's output and      134.2 MB  4 forward kernels    62.5      115
#     log-sum-exp (`KEPT_OUT`, `_LSE`)   + 2.1 MB
#   the selection, int8 (`KEPT_LIVE`)     134.2 MB  the indexer and      37.5       70
#                                                   4 + 4 of its kernels
#   not kept: q, k, v (reckoned)          167.8 MB  3 projections,       12         18
#                                                   8 `norm_rotary`
#
# The names are `ops/pallas_attention.py`'s: its tiled pair names its two results,
# `models/keye.py::Indexer` its selection. A program that builds neither (the
# whole-row kernel, every einsum path) carries no such name and keeps its input
# alone, as under a plain `nn.remat`. No setting: the `setup` event's `attn.kept`
# says what a built program keeps, `kept_by_remat` from the shapes. The backward
# pass then reads the forward pass's own bits of the three, not a second making's.
KEPT = (KEPT_OUT, KEPT_LSE, KEPT_LIVE)


def kept_policy():
    """`nn.remat`'s policy for a layer: what carries a name of `KEPT` stays."""
    return jax.checkpoint_policies.save_only_these_names(*KEPT)


def kept_by_remat(z: dict, path: str, batch: int, length: int, dtype) -> dict | None:
    """The names of `KEPT` that a rematerialised layer carries and their bytes a
    layer, from the shapes: the arch's sizes `z`, its attention on `path` (the
    `attn` block's) over `batch` views of `length` tokens in `dtype`. `None`
    where the layer carries none."""
    sizes = {KEPT_LIVE: batch * length * length} if "index_topk" in z else {}
    if path == "tiled":
        sizes[KEPT_OUT] = batch * length * z["heads"] * z["head_dim"] * jnp.dtype(dtype).itemsize
        sizes[KEPT_LSE] = batch * z["heads"] * length * 4
    names = [name for name in KEPT if name in sizes]
    return {"names": names, "bytes_per_layer": sum(sizes[n] for n in names)} if names else None


def build_sdar(arch: str, num_classes: int | None = None, *, layers: int = 0, held: int = 0,
               vocab: int = 0, **kwargs) -> SDAREncoder:
    """`layers` / `held` / `vocab`: 0 is the arch's own (published) number."""
    if arch not in SDAR_SIZES:
        raise ValueError(f"unknown sdar arch {arch!r}; choose from {sorted(SDAR_SIZES)}")
    z = SDAR_SIZES[arch]
    held = held or z["experts"]
    if not 0 < held <= z["experts"]:
        raise ValueError(f"experts held must be in 1..{z['experts']}, got {held}")
    return SDAREncoder(tuple(sorted(z.items())), layers or z["layers"], held,
                       vocab or z["vocab"], num_classes=num_classes, **kwargs)


build = build_sdar    # the door's name for a family's builder
