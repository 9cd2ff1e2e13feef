"""A weight-shared, looped decoder stack as a MoCo text encoder: the `ouro`
family (LoopLM, arXiv:2510.25741).

The published stack of Ouro-2.6B (ByteDance, `config.json`: `model_type`
`ouro`, `total_ut_steps` 4), read as an encoder of token sequences. The whole
stack is run `total_ut_steps` times WITH THE SAME WEIGHTS. With `u` of
`[B, S, hidden]`:

  - `h = E[ids]`.
  - For pass `t = 1..T`, the same parameters every pass: for layer `l = 1..L`:
    `a = x + N2(Attn(N1(x)))`, `y = a + N4(MLP(N3(a)))`; after layer `L`:
    `h = N_f(y)`, and that normed `h` is what pass `t + 1` starts from. `N*`
    are RMSNorms, each with its own scale: four a layer (a sandwich norm: one
    before and one after each of attention and MLP) and one closing norm.
  - `Attn(u)`: `q = u Wq`, `k = u Wk`, `v = u Wv`, no bias, as many key/value
    heads as query heads; rotate-half rotary over the whole head at positions
    `0..S-1`, the same positions in every pass; NO per-head norm;
    `softmax(q k^T / sqrt(head_dim) + causal mask) v`, softmax in float32; `Wo`.
  - `MLP(u) = (silu(u Wg) * (u Wu)) Wd`.
  - The encoder's output: the last pass's `h`, the mean over the positions,
    the v2 MLP head, as `models/sdar.py`'s.

Left out: the untied output head (an encoder has no token logits) and the exit
gate (`Linear(hidden, 1)` on each pass's `h`): at the published
`early_exit_threshold` 1 no pass but the last is chosen, and the gate enters no
tensor of the loss.

The loop is ONE pass (L layers and the closing norm) traced and compiled once
and run `T` times by `nn.scan` with the parameters broadcast, each layer
rematerialised inside it where `remat` is on: the step program holds L layer
bodies, not `T * L`, and the backward pass of the scan adds the weight
cotangents of all `T` uses of every kernel. Attention is `models/sdar.py`'s
module with `block_length` 1 (a plain causal mask) and `qk_norm` off, so on a
TPU at the published shapes it runs `ops/pallas_attention.py`'s kernels, under
`scan` and `remat`; elsewhere `rotary` and `einsum_attention`. The residual
stream is `dtype`; every RMSNorm, the attention softmax and the head are
float32, the parameters too.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.models.sdar import Attention, RMSNorm
from moco_tpu.telemetry import scopes

# the published sizes by arch (config.json's keys in the comments); the cut to
# one chip (layers) is the config's, not the table's
OURO_SIZES = {
    # https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
    "ouro_2p6b": dict(
        hidden=2048,            # hidden_size
        layers=48,              # num_hidden_layers
        heads=16,               # num_attention_heads
        kv_heads=16,            # num_key_value_heads
        head_dim=128,           # head_dim
        width=5632,             # intermediate_size
        vocab=49152,            # vocab_size
        rope_theta=1e6,         # rope_theta
        eps=1e-6,               # rms_norm_eps
        ut_steps=4,             # total_ut_steps
        block_length=1,         # a causal mask: blocks of one position
        qk_norm=False,          # no key of config.json asks for one
    ),
    # test size: the same mechanisms, nothing else
    "ouro_tiny": dict(
        hidden=64, layers=2, heads=4, kv_heads=4, head_dim=16, width=160, vocab=512,
        rope_theta=1e6, eps=1e-6, ut_steps=3, block_length=1, qk_norm=False,
    ),
}
SIZES = OURO_SIZES
# what the loop counted on the way, a row a pass: read by the step's
# stride-gated counters, never by the forward pass
LOOP_STATS = "loop_stats"
STAT_COLLECTIONS = (LOOP_STATS,)


def health(counted, tokens: int) -> dict:
    """The family's counters for the step's stride-gated `health` block, from
    what the loop sowed (a row a pass, how far the pass moved the state): the
    LAST pass's, which is what an exit gate would act on, and the number of
    passes the program ran (the rows the scan stacked, not the table's number)."""
    (delta,) = jax.tree.leaves(counted[LOOP_STATS])          # [passes]
    passes, vma = jnp.float32(delta.shape[0]), tuple(jax.typeof(delta).vma)
    return {"h_ut_pass_delta": delta[-1].astype(jnp.float32),
            # typed like the delta: the step's pmean takes varying values
            "h_ut_passes": jax.lax.pcast(passes, vma, to="varying") if vma else passes}


class MLP(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                            name=name)

        y = nn.silu(dense(self.width, "gate")(u)) * dense(self.width, "up")(u)
        return dense(u.shape[-1], "down")(y)


class Layer(nn.Module):
    sizes: Any            # an OURO_SIZES entry as a tuple of items (hashable)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        z = dict(self.sizes)

        def norm(name, v):
            return RMSNorm(z["eps"], name=name)(v).astype(self.dtype)

        with jax.named_scope(scopes.NORM):
            h = norm("norm1", x)
        with jax.named_scope(scopes.ATTN):
            h = Attention(z["heads"], z["kv_heads"], z["head_dim"], z["block_length"],
                          z["rope_theta"], z["eps"], self.dtype, qk_norm=z["qk_norm"],
                          name="attn")(h)
        with jax.named_scope(scopes.NORM):
            x = x + norm("norm2", h)
            h = norm("norm3", x)
        with jax.named_scope(scopes.MLP):
            h = MLP(z["width"], self.dtype, name="mlp")(h)
        with jax.named_scope(scopes.NORM):
            return x + norm("norm4", h)


class Pass(nn.Module):
    """One pass of the loop as a scan body: the L layers and the closing norm.
    Sows how far the pass moved the state, mean over tokens of
    `|h_out - h_in| / |h_in|`."""

    sizes: Any
    layers: int
    remat: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, _):
        layer_cls = nn.remat(Layer) if self.remat else Layer
        x = h
        for i in range(self.layers):
            x = layer_cls(self.sizes, self.dtype, name=f"layer_{i}")(x)
        with jax.named_scope(scopes.NORM):
            out = RMSNorm(dict(self.sizes)["eps"], name="norm")(x).astype(self.dtype)
            a, b = out.astype(jnp.float32), h.astype(jnp.float32)
            moved = jnp.linalg.norm(a - b, axis=-1) / jnp.maximum(jnp.linalg.norm(b, axis=-1), 1e-30)
            self.sow(LOOP_STATS, "pass_delta", jnp.mean(moved), reduce_fn=lambda _, new: new,
                     init_fn=lambda: None)
        return out, None


def looped(body, steps: int):
    """`body` (a module whose call is `(h, None) -> (h, None)`) run `steps` times
    on its own output WITH THE SAME PARAMETERS: traced and compiled once, under
    `scan`; what it sows into `LOOP_STATS` comes out a row a pass."""
    return nn.scan(body, variable_broadcast="params", split_rngs={"params": False},
                   variable_axes={LOOP_STATS: 0}, length=steps)


class OuroEncoder(nn.Module):
    """Token ids `[B, S]` -> the pooled feature (`num_classes=None`) or the v2
    MLP head's embedding. `layers` is the cut (how many of the stack's layers
    live here), `ut_steps` how often they are run."""

    sizes: Any
    layers: int
    ut_steps: int
    vocab: int
    num_classes: int | None = None
    mlp_head: bool = True
    remat: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = True):
        z = dict(self.sizes)
        with jax.named_scope(scopes.EMBED_POOL):
            h = nn.Embed(self.vocab, z["hidden"], dtype=self.dtype, param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(1.0), name="embed")(
                ids.astype(jnp.int32))
        # the loop's own hand-over of the residual stream (the scan's carry, the
        # stack of layer inputs it keeps for the backward pass) is under none of
        # the four names: it reads under `k_fwd` / `q_fwd_bwd` alone
        h, _ = looped(Pass, self.ut_steps)(self.sizes, self.layers, self.remat, self.dtype,
                                           name="loop")(h, None)
        with jax.named_scope(scopes.EMBED_POOL):
            feat = jnp.mean(h.astype(jnp.float32), axis=1)
            if self.num_classes is None:
                return feat
            if self.mlp_head:
                feat = nn.relu(nn.Dense(z["hidden"], param_dtype=jnp.float32,
                                        name="fc_hidden")(feat))
            return nn.Dense(self.num_classes, param_dtype=jnp.float32, name="fc")(feat)


def build(arch: str, num_classes: int | None = None, *, layers: int = 0, held: int = 0,
          vocab: int = 0, **kwargs) -> OuroEncoder:
    """`layers` / `vocab`: 0 is the arch's own (published) number. `held` is a
    routed encoder's; a dense stack has no experts to hold."""
    if arch not in OURO_SIZES:
        raise ValueError(f"unknown ouro arch {arch!r}; choose from {sorted(OURO_SIZES)}")
    if held:
        raise ValueError(f"{arch} is dense: it holds no experts (num_experts={held})")
    z = OURO_SIZES[arch]
    return OuroEncoder(tuple(sorted(z.items())), layers or z["layers"], z["ut_steps"],
                       vocab or z["vocab"], num_classes=num_classes, **kwargs)
