"""Train-mode BatchNorm with Pallas streaming reductions (custom VJP).

`nn.BatchNorm`'s train path lowers to XLA reduce fusions for the batch
statistics (forward) and the dgamma/dbeta reductions (backward); round-2
profiling measured those passes at ~half the MoCo-v2 step on the v5e,
running well under the HBM roof. `FastBatchNorm` is a drop-in replacement
(same param/`batch_stats` collections: `scale`, `bias` / `mean`, `var`;
flax running-stat semantics — biased variance, same `momentum`/`epsilon`)
whose train-mode statistics run through `ops/pallas_stats.py` streaming
kernels under a custom VJP:

    fwd:  (Σx, Σx²)  — one Pallas read of x; the normalize stays an XLA
          elementwise op (fuses with the following ReLU/residual-add).
    bwd:  (Σdy, Σdy·x̂) — one Pallas read of dy and x (x̂ recomputed
          in-register); dx is the standard closed form
          dx = γ·r·(dy − (x̂·Σ(dy·x̂) + Σdy)/N), an XLA elementwise pass.

This is the TPU-native equivalent of the reference's cuDNN fused-BN
reductions (`torch.nn.BatchNorm2d` internals; SURVEY §2.10 cuDNN →
MXU/Pallas).

By default (and always for SyncBN via `axis_name`, and eval mode) the math
runs as plain jnp in EXACTLY flax's op order — f32 stats, promote-to-dtype
normalize, autodiff backward — bit-identical to `nn.BatchNorm`, on every
backend: the graph the CPU tests pin is the graph the chip runs.

Both accelerations are opt-in, and ROADMAP D1 queues both for a measured
keep-or-delete: the Pallas REDUCTION kernels (MOCO_TPU_PALLAS_BN=1, TPU
only — Mosaic) were ~52 ms/step SLOWER than XLA's reduce fusions at
R50/B=128 across ~106 pallas_calls, and the custom-VJP closed-form dx
(MOCO_TPU_BN_VJP=1, any backend) read 71.4 vs 71.8 ms/step (both
builder-measured 2026-07-31 on a v5e, single runs, no ledger row).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.ops.pallas_stats import channel_grad_sums, channel_sums


def _use_pallas() -> bool:
    # Opt-in (MOCO_TPU_PALLAS_BN=1) and TPU-only (Mosaic kernels): default
    # OFF because XLA's reduce fusions measured faster (module docstring).
    # Numerics are identical either way (same math, f32 accumulation).
    # MOCO_TPU_DISABLE_PALLAS (the global kill-switch) wins over the opt-in.
    from moco_tpu.utils.envflags import env_flag

    return (jax.default_backend() == "tpu"
            and env_flag("MOCO_TPU_PALLAS_BN")
            and not env_flag("MOCO_TPU_DISABLE_PALLAS"))


def _use_custom_vjp() -> bool:
    """Route train-mode BN (axis_name=None) through `_bn_train`'s
    custom-VJP closed-form dx — opt-in via MOCO_TPU_BN_VJP=1, on whatever
    backend runs, so no test ever pins a backward the chip does not run.
    MOCO_TPU_PALLAS_BN=1 implies it regardless (the Pallas reduction
    kernels live inside `_bn_train`; "pallas reductions + plain autodiff"
    is not a constructible program). The closed form differs from flax
    autodiff by ~1 ulp."""
    from moco_tpu.utils.envflags import env_flag

    return env_flag("MOCO_TPU_BN_VJP")


def _batch_stats(x, use_pallas):
    """f32 (mean, var) over all but the channel axis — flax's
    `_compute_stats` math (biased variance, mean-of-squares form)."""
    n = x.size // x.shape[-1]
    if use_pallas:
        s, sq = channel_sums(x)
        return s / n, sq / n - (s / n) * (s / n)
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(xf, axis=axes)
    mean2 = jnp.mean(xf * xf, axis=axes)
    return mean, mean2 - mean * mean


def _normalize(x, mean, var, scale, bias, eps, dtype):
    """flax `_normalize` semantics (force_float32_reductions=True): the whole
    computation runs in f32 via promotion — `(x - mean) * (rsqrt(var + eps)
    * scale) + bias` with f32 mean/var/scale/bias — and only the RESULT is
    cast to `dtype`."""
    y = (x - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias
    return y.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, scale, bias, eps, dtype):
    mean, var = _batch_stats(x, _use_pallas())
    return _normalize(x, mean, var, scale, bias, eps, dtype), mean, var


def _bn_train_fwd(x, scale, bias, eps, dtype):
    mean, var = _batch_stats(x, _use_pallas())
    y = _normalize(x, mean, var, scale, bias, eps, dtype)
    return (y, mean, var), (x, mean, var, scale)


def _bn_train_bwd(eps, dtype, res, cts):
    x, mean, var, scale = res
    dy, _dmean, _dvar = cts  # the stats outputs feed the (non-differentiated)
    #                          running-stat update: their cotangents are zero
    n = x.size // x.shape[-1]
    rstd = jax.lax.rsqrt(var + eps)  # f32
    if _use_pallas():
        dsum, dxh = channel_grad_sums(dy, x, mean, rstd)
    else:
        dyf = dy.astype(jnp.float32)
        xh = (x.astype(jnp.float32) - mean) * rstd
        axes = tuple(range(x.ndim - 1))
        dsum = jnp.sum(dyf, axis=axes)
        dxh = jnp.sum(dyf * xh, axis=axes)
    # dx = γ·r·(dy − (x̂·Σ(dy·x̂) + Σdy)/N): one f32 elementwise pass over
    # (dy, x), cast to x's dtype at the end (mirrors the fwd's f32 math)
    dyf = dy.astype(jnp.float32)
    xh = (x.astype(jnp.float32) - mean) * rstd
    dx = (scale.astype(jnp.float32) * rstd) * (dyf - (xh * (dxh / n) + dsum / n))
    return dx.astype(x.dtype), dxh.astype(scale.dtype), dsum.astype(scale.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


class FastBatchNorm(nn.Module):
    """Drop-in `nn.BatchNorm` (same fields, params, and `batch_stats`
    collection) with Pallas train-mode statistics on TPU. `axis_name`
    (SyncBN) takes the inline jnp path with a `pmean` over the per-device
    mean/mean² (mathematically the cross-device batch stats; flax's exact op
    order, autodiff backward) — the Pallas custom-VJP path is per-device
    only, so sync mode never uses it."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    axis_name: str | None = None

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None):
        use_ra = (
            self.use_running_average
            if use_running_average is None
            else use_running_average
        )
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (c,), self.param_dtype)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )
        if use_ra:
            return _normalize(
                x, ra_mean.value, ra_var.value, scale, bias, self.epsilon, self.dtype
            )
        if self.axis_name is None and (_use_pallas() or _use_custom_vjp()):
            # opt-in closed-form custom VJP; reductions are pallas or jnp
            # per _use_pallas() inside _bn_train
            y, mean, var = _bn_train(x, scale, bias, self.epsilon, self.dtype)
        else:
            # default / SyncBN: plain jnp in flax's exact op order, autodiff
            # backward — bit-identical to nn.BatchNorm on every backend
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(xf, axis=axes)
            mean2 = jnp.mean(jax.lax.square(xf), axis=axes)  # lax.square: flax's exact graph
            if self.axis_name is not None and not self.is_initializing():
                mean = jax.lax.pmean(mean, self.axis_name)
                mean2 = jax.lax.pmean(mean2, self.axis_name)
            var = mean2 - mean * mean
            y = _normalize(x, mean, var, scale, bias, self.epsilon, self.dtype)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * mean
            ra_var.value = m * ra_var.value + (1 - m) * var
        return y
