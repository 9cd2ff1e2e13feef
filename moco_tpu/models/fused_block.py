"""Fused bn→relu→1x1-conv tail for the Bottleneck block (custom VJP).

The Bottleneck's `bn2 → relu → conv3` sequence materializes the normalized
activation in HBM twice (write after normalize, read by the conv). On the
HBM-bound MoCo step that's pure waste: a 1x1 conv is a matmul, and the
normalize+ReLU is an affine-plus-clamp that can run in-register while tiles
stream into the MXU (`ops/pallas_fused_conv.py`). This module packages that
kernel with

- parameter/variable declaration that EXACTLY mirrors the unfused modules
  (`bn2/{scale,bias}`, `batch_stats bn2/{mean,var}`, `conv3/kernel` of shape
  [1,1,K,N]) so checkpoints/exports are byte-compatible either way, and
- a custom VJP whose backward recomputes z = relu(x̂) inside the dW matmul
  operand (one extra streaming read of x instead of a stored z) and reuses
  FastBatchNorm's closed-form BN chain (`pallas_stats` reductions on TPU).

Off-TPU the SAME params drive a plain `lax.conv`-based path (flax op order),
so golden tests and CPU training are unchanged; the Pallas path engages on
TPU only. SyncBN (`axis_name`) is not supported here — the caller falls back
to the unfused modules (MoCo's BN is per-device by design, SURVEY §7).

Reference equivalent: cuDNN fused conv+BN epilogues (SURVEY §2.10).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.models.fast_bn import _batch_stats, _normalize
from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul, bn_relu_matmul_dw
from moco_tpu.ops.pallas_fused_conv3x3 import (
    bn_relu_conv3x3,
    bn_relu_conv3x3_s2,
    conv3x3_dw,
)
from moco_tpu.ops.pallas_stats import channel_grad_sums


def _use_pallas() -> bool:
    """Gate for the fused-conv kernel family — a block only reaches this
    module when `config.fused_bn_conv=True` routed it here, so this is
    deliberately INDEPENDENT of fast_bn's BN-stats opt-in
    (MOCO_TPU_PALLAS_BN): the r5 A/B that turned the stats kernels off by
    default must not silently disable the separately-validated fused
    family's documented config switch (review, r5). The global
    MOCO_TPU_DISABLE_PALLAS kill-switch (tools/_perf_ab.py) still applies; off
    TPU the blocks fall back to `_plain_apply`."""
    from moco_tpu.utils.envflags import env_flag

    return (jax.default_backend() == "tpu"
            and not env_flag("MOCO_TPU_DISABLE_PALLAS"))


def norm_train_flag(norm) -> bool:
    """Train-mode sniff shared by the fused blocks: the ResNet passes its
    norm as a `functools.partial` carrying `use_running_average=not train`.
    A bare module class (no `keywords`) yields train=True, matching
    `nn.BatchNorm`'s own `use_running_average=False` default."""
    return not getattr(norm, "keywords", {}).get("use_running_average", False)


def _plain_apply(x, mean, var, scale, bias, w4d, eps, dtype):
    """The unfused math in flax's exact op order: f32 normalize cast to
    `dtype`, ReLU, then the 1x1 conv as `lax.conv` in `dtype` (what
    `nn.Conv(use_bias=False, dtype=...)` lowers to)."""
    z = nn.relu(_normalize(x, mean, var, scale, bias, eps, dtype))
    return jax.lax.conv_general_dilated(
        z,
        w4d.astype(dtype),
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _train_impl(x, scale, bias, w4d, eps, dtype):
    mean, var = _batch_stats(x, _use_pallas())
    if _use_pallas():
        k, n = w4d.shape[-2], w4d.shape[-1]
        rstd = jax.lax.rsqrt(var + eps)
        a = scale * rstd
        y = bn_relu_matmul(
            x.reshape(-1, k),
            a,
            bias - mean * a,
            w4d.reshape(k, n).astype(dtype),
            out_dtype=dtype,
        ).reshape(*x.shape[:-1], n)
    else:
        y = _plain_apply(x, mean, var, scale, bias, w4d, eps, dtype)
    return y, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_relu_conv_train(x, scale, bias, w4d, eps, dtype):
    return _train_impl(x, scale, bias, w4d, eps, dtype)


def _fwd(x, scale, bias, w4d, eps, dtype):
    y, mean, var = _train_impl(x, scale, bias, w4d, eps, dtype)
    return (y, mean, var), (x, mean, var, scale, bias, w4d)


def _bwd(eps, dtype, res, cts):
    x, mean, var, scale, bias, w4d = res
    dy, _dmean, _dvar = cts  # stats feed the (non-differentiated) running
    #                          stats; their cotangents are zero
    k, n = w4d.shape[-2], w4d.shape[-1]
    m_rows = x.size // k
    xr = x.reshape(m_rows, k)
    dyr = dy.reshape(m_rows, n)
    rstd = jax.lax.rsqrt(var + eps)  # f32
    a = (scale * rstd).astype(jnp.float32)
    shift = (bias - mean * a).astype(jnp.float32)
    if _use_pallas():
        # ẑ recomputed inside the Pallas dW kernel's VMEM tiles — x streams
        # once, the normalized activation never exists in HBM in the
        # backward either (no bet on XLA operand fusion)
        dw = bn_relu_matmul_dw(xr, a, shift, dyr).reshape(
            w4d.shape).astype(w4d.dtype)
        zpre = xr.astype(jnp.float32) * a + shift  # XLA fuses into the mask
    else:
        zpre = xr.astype(jnp.float32) * a + shift
        z = jnp.maximum(zpre, 0.0).astype(dtype)
        dw = jnp.einsum(
            "mk,mn->kn", z, dyr, preferred_element_type=jnp.float32
        ).reshape(w4d.shape).astype(w4d.dtype)
    # gradient at the normalize output, ReLU-masked
    g = jnp.einsum(
        "mn,kn->mk", dyr, w4d.reshape(k, n).astype(dyr.dtype),
        preferred_element_type=jnp.float32,
    ) * (zpre > 0)
    g = g.reshape(x.shape)
    # BN chain (FastBatchNorm's closed form): dγ = Σg·x̂, dβ = Σg,
    # dx = γ·r·(g − (x̂·Σ(g·x̂) + Σg)/N)
    if _use_pallas():
        dsum, dxh = channel_grad_sums(g, x, mean, rstd)
    else:
        gf = g.reshape(m_rows, k)
        xh = (xr.astype(jnp.float32) - mean) * rstd
        dsum = jnp.sum(gf, axis=0)
        dxh = jnp.sum(gf * xh, axis=0)
    nelem = m_rows
    xh_full = (x.astype(jnp.float32) - mean) * rstd
    dx = (scale * rstd) * (
        g.astype(jnp.float32) - (xh_full * (dxh / nelem) + dsum / nelem)
    )
    return (
        dx.astype(x.dtype),
        dxh.astype(scale.dtype),
        dsum.astype(bias.dtype),
        dw,
    )


_bn_relu_conv_train.defvjp(_fwd, _bwd)


def _conv3x3(z, w4d, dtype):
    return jax.lax.conv_general_dilated(
        z, w4d.astype(dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _plain_apply3x3(x, mean, var, scale, bias, w4d, eps, dtype):
    z = nn.relu(_normalize(x, mean, var, scale, bias, eps, dtype))
    return _conv3x3(z, w4d, dtype)


def _train3x3_impl(x, scale, bias, w4d, eps, dtype):
    mean, var = _batch_stats(x, _use_pallas())
    if _use_pallas():
        rstd = jax.lax.rsqrt(var + eps)
        a = scale * rstd
        y = bn_relu_conv3x3(x, a, bias - mean * a, w4d, out_dtype=dtype)
    else:
        y = _plain_apply3x3(x, mean, var, scale, bias, w4d, eps, dtype)
    return y, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_relu_conv3x3_train(x, scale, bias, w4d, eps, dtype):
    return _train3x3_impl(x, scale, bias, w4d, eps, dtype)


def _fwd3x3(x, scale, bias, w4d, eps, dtype):
    y, mean, var = _train3x3_impl(x, scale, bias, w4d, eps, dtype)
    return (y, mean, var), (x, mean, var, scale, bias, w4d)


def _bn_chain(g, x, mean, rstd, scale):
    """The closed-form BN backward shared by every fused conv: given the
    ReLU-masked gradient g at the normalize output, return (dx, dγ, dβ)."""
    k = x.shape[-1]
    if _use_pallas():
        dsum, dxh = channel_grad_sums(g, x, mean, rstd)
    else:
        gf = g.reshape(-1, k)
        xh = (x.reshape(-1, k).astype(jnp.float32) - mean) * rstd
        dsum = jnp.sum(gf, axis=0)
        dxh = jnp.sum(gf * xh, axis=0)
    nelem = x.size // k
    xh_full = (x.astype(jnp.float32) - mean) * rstd
    dx = (scale * rstd) * (g - (xh_full * (dxh / nelem) + dsum / nelem))
    return dx, dxh, dsum


def _bwd3x3(eps, dtype, res, cts):
    x, mean, var, scale, bias, w4d = res
    dy, _dmean, _dvar = cts
    rstd = jax.lax.rsqrt(var + eps)
    a = (scale * rstd).astype(jnp.float32)
    shift = (bias - mean * a).astype(jnp.float32)
    zpre = x.astype(jnp.float32) * a + shift
    # the input-gradient never reads z's VALUE — it is the transposed conv
    # of dy with the spatially-flipped, channel-transposed taps, already an
    # optimal MXU conv as plain XLA on every backend
    dz = _conv3x3(dy, w4d[::-1, ::-1].transpose(0, 1, 3, 2), dtype)
    if _use_pallas():
        # filter gradient with ẑ recomputed in VMEM (conv3x3_dw): z now
        # never exists in HBM in the backward either; the ReLU mask below
        # fuses into g's multiply
        dw = conv3x3_dw(x, a, shift, dy).astype(w4d.dtype)
    else:
        z = jnp.maximum(zpre, 0.0).astype(dtype)
        _, conv_vjp = jax.vjp(lambda w_: _conv3x3(z, w_, dtype), w4d)
        (dw,) = conv_vjp(dy)
    g = dz.astype(jnp.float32) * (zpre > 0)
    dx, dxh, dsum = _bn_chain(g, x, mean, rstd, scale)
    return (
        dx.astype(x.dtype),
        dxh.astype(scale.dtype),
        dsum.astype(bias.dtype),
        dw.astype(w4d.dtype),
    )


_bn_relu_conv3x3_train.defvjp(_fwd3x3, _bwd3x3)


def _conv3x3s2(z, w4d, dtype):
    return jax.lax.conv_general_dilated(
        z, w4d.astype(dtype), (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _plain_apply3x3s2(x, mean, var, scale, bias, w4d, eps, dtype):
    z = nn.relu(_normalize(x, mean, var, scale, bias, eps, dtype))
    return _conv3x3s2(z, w4d, dtype)


def _train3x3s2_impl(x, scale, bias, w4d, eps, dtype):
    mean, var = _batch_stats(x, _use_pallas())
    if _use_pallas():
        rstd = jax.lax.rsqrt(var + eps)
        a = scale * rstd
        y = bn_relu_conv3x3_s2(x, a, bias - mean * a, w4d, out_dtype=dtype)
    else:
        y = _plain_apply3x3s2(x, mean, var, scale, bias, w4d, eps, dtype)
    return y, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_relu_conv3x3s2_train(x, scale, bias, w4d, eps, dtype):
    return _train3x3s2_impl(x, scale, bias, w4d, eps, dtype)


def _fwd3x3s2(x, scale, bias, w4d, eps, dtype):
    y, mean, var = _train3x3s2_impl(x, scale, bias, w4d, eps, dtype)
    return (y, mean, var), (x, mean, var, scale, bias, w4d)


def _bwd3x3s2(eps, dtype, res, cts):
    """Stride-2 backward: z is recomputed (not stored — the forward kernel
    never wrote it) and materialized ONCE here for the two conv VJPs; the
    fusion still nets one HBM round-trip vs the unfused block, whose
    forward writes z AND whose backward reads it back."""
    x, mean, var, scale, bias, w4d = res
    dy, _dmean, _dvar = cts
    rstd = jax.lax.rsqrt(var + eps)
    a = (scale * rstd).astype(jnp.float32)
    shift = (bias - mean * a).astype(jnp.float32)
    zpre = x.astype(jnp.float32) * a + shift
    z = jnp.maximum(zpre, 0.0).astype(dtype)
    _, conv_vjp = jax.vjp(lambda z_, w_: _conv3x3s2(z_, w_, dtype), z, w4d)
    dz, dw = conv_vjp(dy)
    g = dz.astype(jnp.float32) * (zpre > 0)
    dx, dxh, dsum = _bn_chain(g, x, mean, rstd, scale)
    return (
        dx.astype(x.dtype),
        dxh.astype(scale.dtype),
        dsum.astype(bias.dtype),
        dw.astype(w4d.dtype),
    )


_bn_relu_conv3x3s2_train.defvjp(_fwd3x3s2, _bwd3x3s2)


def _fused_bn_relu_conv(
    mdl: nn.Module,
    x: jax.Array,
    bn_name: str,
    conv_name: str,
    kshape: tuple,
    train: bool,
    momentum: float,
    eps: float,
    dtype,
    plain_fn,
    train_fn,
) -> jax.Array:
    """Shared scaffolding for both fusions: declare bn+conv params/stats
    under `mdl`'s scope with the UNFUSED module names (checkpoint/export
    byte-compatible), gate eval/init onto `plain_fn` (running stats), and
    run `train_fn` (the custom-VJP fused path) with the flax running-stat
    update."""
    k = x.shape[-1]
    bn = mdl.param(
        bn_name,
        lambda rng: {
            "scale": jnp.ones((k,), jnp.float32),
            "bias": jnp.zeros((k,), jnp.float32),
        },
    )
    w4d = mdl.param(
        conv_name,
        lambda rng: {
            "kernel": nn.initializers.lecun_normal()(rng, kshape, jnp.float32)
        },
    )["kernel"]
    ra = mdl.variable(
        "batch_stats",
        bn_name,
        lambda: {
            "mean": jnp.zeros((k,), jnp.float32),
            "var": jnp.ones((k,), jnp.float32),
        },
    )
    if not train or mdl.is_initializing():
        return plain_fn(
            x, ra.value["mean"], ra.value["var"], bn["scale"], bn["bias"],
            w4d, eps, dtype,
        )
    y, mean, var = train_fn(x, bn["scale"], bn["bias"], w4d, eps, dtype)
    ra.value = {
        "mean": momentum * ra.value["mean"] + (1 - momentum) * mean,
        "var": momentum * ra.value["var"] + (1 - momentum) * var,
    }
    return y


def fused_bn_relu_conv2(
    mdl: nn.Module, x, features: int, train: bool, momentum: float,
    eps: float, dtype,
) -> jax.Array:
    """The bn1→relu→conv2 (3x3, stride-1) interior fusion — Bottleneck mids
    and BasicBlock tails."""
    return _fused_bn_relu_conv(
        mdl, x, "bn1", "conv2", (3, 3, x.shape[-1], features), train,
        momentum, eps, dtype, _plain_apply3x3, _bn_relu_conv3x3_train,
    )


def fused_bn_relu_conv2_s2(
    mdl: nn.Module, x, features: int, train: bool, momentum: float,
    eps: float, dtype,
) -> jax.Array:
    """The stride-2 bn1→relu→conv2 fusion — the stage-first Bottleneck
    blocks (VERDICT r3 #5); forward through the Pallas stride-2 kernel,
    backward recomputes z once for the plain-XLA conv VJPs."""
    return _fused_bn_relu_conv(
        mdl, x, "bn1", "conv2", (3, 3, x.shape[-1], features), train,
        momentum, eps, dtype, _plain_apply3x3s2, _bn_relu_conv3x3s2_train,
    )


def fused_bn_relu_conv3(
    mdl: nn.Module, x, features: int, train: bool, momentum: float,
    eps: float, dtype,
) -> jax.Array:
    """The Bottleneck's bn2→relu→conv3 (1x1) tail fusion."""
    return _fused_bn_relu_conv(
        mdl, x, "bn2", "conv3", (1, 1, x.shape[-1], features), train,
        momentum, eps, dtype, _plain_apply, _bn_relu_conv_train,
    )
