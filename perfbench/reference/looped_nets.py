"""Plain float32 forward pass of the `ouro` stack (a weight-shared stack run
several times) as a sequence encoder: what `moco_looped.py` trains. The token
views are `seq_nets.token_views`.

The layer equations (ISSUE 31, from the model's public `config.json` and the
family's modelling code; `u` is `[B, S, hidden]`). `h = E[ids]`. For pass
`t = 1..T`, the SAME parameters every pass: for layer `l = 1..L`:
`a = x + N2(Attn(N1(x)))`, `y = a + N4(MLP(N3(a)))`; after layer `L`:
`h = N_f(y)`, and that normed `h` is what pass `t + 1` starts from. `N*` are
RMSNorms, each with its own scale: four a layer (sandwich norm) and one closing
norm. `Attn(u)`: `q = u Wq`, `k = u Wk`, `v = u Wv` without bias, as many
key/value heads as query heads; rotate-half rotary over the whole head at
positions 0..S-1, the same in every pass; no per-head norm; `softmax(q k^T /
sqrt(head_dim) + causal mask) v`; `Wo`. `MLP(u) = (silu(u Wg) * (u Wu)) Wd`.
The encoder's output: the last pass's `h`, the mean over the positions, the
MoCo v2 head (Linear, ReLU, Linear). Left out, as in the program: the untied
output head and the exit gate (at `early_exit_threshold` 1 no pass but the last
is chosen, and the gate enters no tensor of the loss).

A Python loop over passes and layers: no scan, no rematerialisation, no
kernels. Products go through `nets.Ops` (the configuration's `compute_dtype`,
as for the other references); the softmax, the norms and the head stay float32.
`fault` plants one departure, for the readings the limits are set against.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference.nets import HI, Ops, _dense_spec
from perfbench.reference.seq_nets import rmsnorm, rope

# published sizes (https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json)
SIZES = {
    "ouro_2p6b": dict(hidden=2048, layers=48, heads=16, kv_heads=16, head_dim=128, width=5632,
                      vocab=49152, rope_theta=1e6, eps=1e-6, ut_steps=4),
    "ouro_tiny": dict(hidden=64, layers=2, heads=4, kv_heads=4, head_dim=16, width=160,
                      vocab=512, rope_theta=1e6, eps=1e-6, ut_steps=3),
}
# one pass fewer; the closing norm after the last pass only; the gradient of the
# last pass alone (the shared weights miss the cotangents of their earlier uses)
FAULTS = ("pass_short", "norm_once", "last_pass_grad")


def sizes_for(cfg: dict) -> dict:
    """The arch's published sizes with the configuration's cut laid over them."""
    z = dict(SIZES[cfg["arch"]])
    z["layers"] = cfg.get("num_hidden_layers") or z["layers"]
    z["vocab"] = cfg.get("vocab_size") or z["vocab"]
    return z


def spec(z: dict, embed_dim: int) -> list:
    d, hd, w = z["hidden"], z["head_dim"], z["width"]
    out = [("embed/embedding", (z["vocab"], d), "normal", 2)]    # unit variance
    for i in range(z["layers"]):
        p = f"loop/layer_{i}"
        out += [(f"{p}/attn/q/kernel", (d, z["heads"] * hd), "normal", d),
                (f"{p}/attn/k/kernel", (d, z["kv_heads"] * hd), "normal", d),
                (f"{p}/attn/v/kernel", (d, z["kv_heads"] * hd), "normal", d),
                (f"{p}/attn/o/kernel", (z["heads"] * hd, d), "normal", z["heads"] * hd),
                (f"{p}/mlp/gate/kernel", (d, w), "normal", d),
                (f"{p}/mlp/up/kernel", (d, w), "normal", d),
                (f"{p}/mlp/down/kernel", (w, d), "normal", w)]
        out += [(f"{p}/norm{j}/scale", (d,), "ones", 0) for j in (1, 2, 3, 4)]
    out += [("loop/norm/scale", (d,), "ones", 0)]
    return out + _dense_spec("fc_hidden", d, d) + _dense_spec("fc", d, embed_dim)


def attention(ops: Ops, p: dict, name: str, h, z: dict):
    b, length, _ = h.shape
    hd, heads, kv = z["head_dim"], z["heads"], z["kv_heads"]
    q = ops.dot(h, p[name + "/q/kernel"]).reshape(b, length, heads, hd)
    k = ops.dot(h, p[name + "/k/kernel"]).reshape(b, length, kv, hd)
    v = ops.a(ops.dot(h, p[name + "/v/kernel"])).reshape(b, length, kv, hd)
    q, k = ops.a(rope(q, z["rope_theta"])), ops.a(rope(k, z["rope_theta"]))
    k, v = jnp.repeat(k, heads // kv, 2), jnp.repeat(v, heads // kv, 2)
    s = ops.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    at = jnp.arange(length)
    s = jnp.where(at[None, :] <= at[:, None], s, -jnp.inf)
    o = ops.a(ops.einsum("bhqk,bkhd->bqhd", ops.a(jax.nn.softmax(s, -1)), v))
    return ops.dot(o.reshape(b, length, heads * hd), p[name + "/o/kernel"])


def mlp(ops: Ops, p: dict, name: str, u):
    y = ops.a(jax.nn.silu(ops.dot(u, p[name + "/gate/kernel"])) * ops.dot(u, p[name + "/up/kernel"]))
    return ops.dot(y, p[name + "/down/kernel"])


def layer(ops: Ops, p: dict, name: str, x, z: dict):
    def norm(j, v):
        return ops.a(rmsnorm(v, p[f"{name}/norm{j}/scale"], z["eps"]))

    a = ops.a(x + norm(2, attention(ops, p, name + "/attn", norm(1, x), z)))
    return ops.a(a + norm(4, mlp(ops, p, name + "/mlp", norm(3, a))))


def embed(ops: Ops, p: dict, ids):
    return ops.a(p["embed/embedding"][ids])


def one_pass(ops: Ops, p: dict, h, z: dict, closing: bool = True):
    """Every layer once, then the closing norm (`closing` off: a planted fault)."""
    for i in range(z["layers"]):
        h = layer(ops, p, f"loop/layer_{i}", h, z)
    return ops.a(rmsnorm(h, p["loop/norm/scale"], z["eps"])) if closing else h


def head(p: dict, h):
    f = jnp.mean(h, 1)
    f = jax.nn.relu(jnp.matmul(f, p["fc_hidden/kernel"], precision=HI) + p["fc_hidden/bias"])
    return jnp.matmul(f, p["fc/kernel"], precision=HI) + p["fc/bias"]


def plan(z: dict, fault=None) -> list:
    """For each pass that is run: whether its closing norm is applied, and
    whether the gradient is cut at its input. Sound: `ut_steps` times `(True,
    False)`."""
    steps = z["ut_steps"] - (fault == "pass_short")
    return [(fault != "norm_once" or t == steps - 1, fault == "last_pass_grad" and t == steps - 1)
            for t in range(steps)]


def passes_of(ops: Ops, p: dict, ids, z: dict, fault=None) -> list:
    """Token ids `[B, S]` -> the state after the embedding and after every pass."""
    states = [embed(ops, p, ids)]
    for closing, cut in plan(z, fault):
        h = jax.lax.stop_gradient(states[-1]) if cut else states[-1]
        states.append(one_pass(ops, p, h, z, closing))
    return states


def forward(ops: Ops, p: dict, ids, z: dict, fault=None):
    """Token ids `[B, S]` -> `[B, embed]`."""
    return head(p, passes_of(ops, p, ids, z, fault)[-1])
