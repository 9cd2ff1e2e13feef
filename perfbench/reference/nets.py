"""Plain float32 forward passes: ResNet-50 (v1.5 bottleneck) with the MoCo v2
MLP head, ViT-S/16 as moco-v3 builds it, and the v3 projector / predictor.

Straightforward `jax.numpy` / `lax.conv`, float32 at `highest` matmul
precision, BatchNorm in training mode (batch statistics, biased variance),
no kernels, no batching tricks. Parameters are a flat dict from
`"module/sub/leaf"` to array; each network also gives its `spec`: the list
of `(path, shape, init, fan_in)` that the benchmark makes the weights from.
Backward passes come from `jax.grad` of these functions. Each residual or
transformer block is rematerialised so that batch 256 in float32 fits one
16 GB chip; that changes no number.

`Ops(precision)` is the one switch, and stands for the configurations'
`compute_dtype`: the type of the picture between the augmentation's
transforms, of the backbone's activations and of the operands of its matrix
products and convolutions (parameters, normalisation statistics, heads and
loss stay float32, as in the program). `"float32"` is the reference;
`"float8"` rounds those activations and operands to float8_e4m3 with one scale
per tensor (the control: the nearest precision below the bfloat16 that the
configurations state); `"bfloat16"` rounds them to bfloat16 (what the
configurations state; the calibration reads it to say how much of the
program's gap that rounding explains). Gradients pass straight through a
rounding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def _round_fp8(x):
    """To the nearest float8_e4m3 value under one scale per tensor (its largest
    magnitude maps to 448, e4m3's largest), in float32 arithmetic: three
    mantissa bits, exponents down to 2**-6 and subnormals below, ties to even."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = x / scale
    _, e = jnp.frexp(jnp.maximum(jnp.abs(y), 2.0 ** -6))      # |y| = m * 2**e, m in [0.5, 1)
    quantum = jnp.ldexp(jnp.ones_like(y), e - 4)               # 2**(floor(log2|y|) - 3)
    return jnp.clip(jnp.round(y / quantum) * quantum, -448.0, 448.0) * scale


_round_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_round_bf16.defvjp(lambda x: (_round_bf16(x), None), lambda _, g: (g,))


class Ops:
    def __init__(self, precision: str = "float32"):
        rounders = {"float32": lambda x: x, "float8": _round_fp8,
                    "bfloat16": _round_bf16}
        if precision not in rounders:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.r = rounders[precision]      # operands of a product
        self.a = rounders[precision]      # an activation the backbone keeps

    def conv(self, x, w, stride=1, pad=0):
        return jax.lax.conv_general_dilated(
            self.r(x), self.r(w), (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)

    def dot(self, x, w):
        return jnp.matmul(self.r(x), self.r(w), precision=HI)

    def einsum(self, eq, a, b):
        return jnp.einsum(eq, self.r(a), self.r(b), precision=HI)


BN_MOMENTUM = 0.9   # torch's momentum 0.1: running = 0.9 * running + 0.1 * batch


def batchnorm(x, scale=None, bias=None, eps=1e-5, seen=None, name=None):
    """`seen[name]` takes the batch's (biased) variance: what the running
    statistics are updated with, and one of the numbers `correct` compares."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes)
    var = jnp.mean(jnp.square(x - mean), axes)
    if seen is not None:
        seen[name] = var
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale + bias
    return y


def layernorm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def l2_normalize(x):
    return x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))


# -- ResNet ------------------------------------------------------------------

RESNET_STAGES = {"resnet50": ((3, 4, 6, 3), True), "resnet18": ((2, 2, 2, 2), False)}


def _conv_spec(path, kh, kw, cin, cout):
    return (path + "/kernel", (kh, kw, cin, cout), "normal", kh * kw * cin)


def _bn_spec(path, c):
    return [(path + "/scale", (c,), "ones", 0), (path + "/bias", (c,), "zeros", 0)]


def _dense_spec(path, din, dout, bias=True):
    out = [(path + "/kernel", (din, dout), "normal", din)]
    if bias:
        out.append((path + "/bias", (dout,), "zeros", 0))
    return out


def resnet_spec(arch: str, embed_dim: int, width: int = 64, stem: int = 7):
    stages, bottleneck = RESNET_STAGES[arch]
    spec = [_conv_spec("conv1", stem, stem, 3, width)] + _bn_spec("bn1", width)
    cin = width
    for i, blocks in enumerate(stages):
        f = width * 2 ** i
        out = f * 4 if bottleneck else f
        for j in range(blocks):
            p = f"layer{i + 1}_{j}"
            if bottleneck:
                spec += [_conv_spec(p + "/conv1", 1, 1, cin, f)] + _bn_spec(p + "/bn1", f)
                spec += [_conv_spec(p + "/conv2", 3, 3, f, f)] + _bn_spec(p + "/bn2", f)
                spec += [_conv_spec(p + "/conv3", 1, 1, f, out)] + _bn_spec(p + "/bn3", out)
            else:
                spec += [_conv_spec(p + "/conv1", 3, 3, cin, f)] + _bn_spec(p + "/bn1", f)
                spec += [_conv_spec(p + "/conv2", 3, 3, f, f)] + _bn_spec(p + "/bn2", f)
            if cin != out or (i > 0 and j == 0):
                spec += [_conv_spec(p + "/downsample_conv", 1, 1, cin, out)]
                spec += _bn_spec(p + "/downsample_bn", out)
            cin = out
    spec += _dense_spec("fc_hidden", cin, cin) + _dense_spec("fc", cin, embed_dim)
    return spec


def resnet_forward(ops: Ops, p: dict, x, arch: str, stem: int = 7, seen=None):
    """Images `[B,S,S,3]` -> `[B,embed]`: stem, stages, pool, MLP head. `seen`,
    a dict, takes every BatchNorm's batch variance by the layer's name."""
    stages, bottleneck = RESNET_STAGES[arch]
    seen = {} if seen is None else seen

    def bn(name, y, into=None):
        return ops.a(batchnorm(ops.a(y), p[name + "/scale"], p[name + "/bias"],
                               seen=seen if into is None else into, name=name))

    x = ops.a(x)
    if stem == 7:
        x = jax.nn.relu(bn("bn1", ops.conv(x, p["conv1/kernel"], 2, 3)))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)))
    else:  # the CIFAR stem of the small test presets: 3x3/1, no pool
        x = jax.nn.relu(bn("bn1", ops.conv(x, p["conv1/kernel"], 1, 1)))

    def block(x, name, stride):
        var = {}     # a rematerialised block hands its variances out as results
        if bottleneck:
            y = jax.nn.relu(bn(name + "/bn1", ops.conv(x, p[name + "/conv1/kernel"]), var))
            y = jax.nn.relu(bn(name + "/bn2", ops.conv(y, p[name + "/conv2/kernel"], stride, 1), var))
            y = bn(name + "/bn3", ops.conv(y, p[name + "/conv3/kernel"]), var)
        else:
            y = jax.nn.relu(bn(name + "/bn1", ops.conv(x, p[name + "/conv1/kernel"], stride, 1), var))
            y = bn(name + "/bn2", ops.conv(y, p[name + "/conv2/kernel"], 1, 1), var)
        if name + "/downsample_conv/kernel" in p:
            x = bn(name + "/downsample_bn",
                   ops.conv(x, p[name + "/downsample_conv/kernel"], stride), var)
        return ops.a(jax.nn.relu(x + y)), var

    for i, blocks in enumerate(stages):
        for j in range(blocks):
            name = f"layer{i + 1}_{j}"
            stride = 2 if i > 0 and j == 0 else 1
            x, var = jax.checkpoint(lambda x_, n=name, s=stride: block(x_, n, s))(x)
            seen.update(var)
    x = jnp.mean(x, (1, 2))
    x = jax.nn.relu(ops.dot(x, p["fc_hidden/kernel"]) + p["fc_hidden/bias"])
    return ops.dot(x, p["fc/kernel"]) + p["fc/bias"]


# -- ViT and the v3 heads ----------------------------------------------------

VIT_SIZES = {"vit_small": (384, 12, 12), "vit_tiny": (64, 2, 2)}  # width, depth, heads


def vit_spec(arch: str, prefix: str = "backbone", patch: int = 16):
    width, depth, heads = VIT_SIZES[arch]
    hd = width // heads
    spec = [(f"{prefix}/patch_embed/kernel", (patch, patch, 3, width), "normal", patch * patch * 3),
            (f"{prefix}/patch_embed/bias", (width,), "zeros", 0),
            (f"{prefix}/cls_token", (1, 1, width), "tiny", 0)]

    def ln(path):
        return [(path + "/scale", (width,), "ones", 0), (path + "/bias", (width,), "zeros", 0)]

    for i in range(depth):
        b = f"{prefix}/block{i}"
        spec += ln(b + "/norm1")
        for name in ("query", "key", "value"):
            spec += [(f"{b}/attn/{name}/kernel", (width, heads, hd), "normal", width),
                     (f"{b}/attn/{name}/bias", (heads, hd), "zeros", 0)]
        spec += [(f"{b}/attn/out/kernel", (heads, hd, width), "normal", width),
                 (f"{b}/attn/out/bias", (width,), "zeros", 0)]
        spec += ln(b + "/norm2")
        spec += _dense_spec(b + "/mlp_fc1", width, 4 * width)
        spec += _dense_spec(b + "/mlp_fc2", 4 * width, width)
    return spec + ln(f"{prefix}/norm")


def sincos_positions(h: int, w: int, dim: int):
    """moco-v3's fixed 2-D sin-cos position embedding, temperature 10000."""
    gw, gh = np.meshgrid(np.arange(w, dtype=np.float32), np.arange(h, dtype=np.float32))
    omega = 1.0 / (10000 ** (np.arange(dim // 4, dtype=np.float32) / (dim // 4)))
    ow = (gw[..., None] * omega).reshape(h * w, -1)
    oh = (gh[..., None] * omega).reshape(h * w, -1)
    return np.concatenate([np.sin(ow), np.cos(ow), np.sin(oh), np.cos(oh)], 1)[None]


def vit_forward(ops: Ops, p: dict, x, arch: str, prefix: str = "backbone", patch: int = 16):
    """Images -> the class token's feature. The patch projection is frozen
    (moco-v3's stability trick): no gradient flows into it."""
    width, depth, heads = VIT_SIZES[arch]
    b, h, w, _ = x.shape
    gh, gw = h // patch, w // patch
    pre = prefix + "/"
    patches = x.reshape(b, gh, patch, gw, patch, 3).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, gh * gw, patch * patch * 3)
    tok = ops.dot(ops.a(patches), p[pre + "patch_embed/kernel"].reshape(-1, width))
    tok = jax.lax.stop_gradient(ops.a(tok + p[pre + "patch_embed/bias"]))
    tok = tok + sincos_positions(gh, gw, width)
    cls = jnp.broadcast_to(p[pre + "cls_token"], (b, 1, width))
    x = ops.a(jnp.concatenate([cls, tok], 1))

    def block(x, name):
        y = ops.a(layernorm(x, p[name + "/norm1/scale"], p[name + "/norm1/bias"]))

        def proj(which):
            return ops.a(ops.einsum("bld,dhk->blhk", y, p[f"{name}/attn/{which}/kernel"])
                         + p[f"{name}/attn/{which}/bias"])

        q, k, v = proj("query"), proj("key"), proj("value")
        att = ops.einsum("bqhk,bmhk->bhqm", q / math.sqrt(q.shape[-1]), k)
        att = ops.a(jax.nn.softmax(att, -1))
        y = ops.a(ops.einsum("bhqm,bmhk->bqhk", att, v))
        y = (jnp.einsum("bqhk,hkd->bqd", ops.r(y), ops.r(p[name + "/attn/out/kernel"]),
                        precision=HI) + p[name + "/attn/out/bias"])
        x = ops.a(x + y)
        y = ops.a(layernorm(x, p[name + "/norm2/scale"], p[name + "/norm2/bias"]))
        y = ops.a(ops.dot(y, p[name + "/mlp_fc1/kernel"]) + p[name + "/mlp_fc1/bias"])
        y = ops.a(jax.nn.gelu(y, approximate=False))
        y = ops.dot(y, p[name + "/mlp_fc2/kernel"]) + p[name + "/mlp_fc2/bias"]
        return ops.a(x + y)

    for i in range(depth):
        x = jax.checkpoint(lambda x_, n=f"{pre}block{i}": block(x_, n))(x)
    x = ops.a(layernorm(x, p[pre + "norm/scale"], p[pre + "norm/bias"]))
    return x[:, 0]


def v3_head_spec(feat: int, hidden: int = 4096, out: int = 256):
    spec = []
    for i, (din, dout) in enumerate([(feat, hidden), (hidden, hidden), (hidden, out)]):
        spec += _dense_spec(f"projector/mlp/fc{i}", din, dout, bias=False)
        if i < 2:
            spec += _bn_spec(f"projector/mlp/bn{i}", dout)
    spec += _dense_spec("predictor/mlp/fc0", out, hidden, bias=False)
    spec += _bn_spec("predictor/mlp/bn0", hidden)
    spec += _dense_spec("predictor/mlp/fc1", hidden, out, bias=False)
    return spec


def v3_project(ops: Ops, p: dict, f):
    for i in range(2):
        f = ops.dot(f, p[f"projector/mlp/fc{i}/kernel"])
        f = jax.nn.relu(batchnorm(f, p[f"projector/mlp/bn{i}/scale"],
                                  p[f"projector/mlp/bn{i}/bias"]))
    return batchnorm(ops.dot(f, p["projector/mlp/fc2/kernel"]))


def v3_predict(ops: Ops, p: dict, z):
    z = ops.dot(z, p["predictor/mlp/fc0/kernel"])
    z = jax.nn.relu(batchnorm(z, p["predictor/mlp/bn0/scale"], p["predictor/mlp/bn0/bias"]))
    return ops.dot(z, p["predictor/mlp/fc1/kernel"])
