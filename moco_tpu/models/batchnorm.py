"""The ResNets' BatchNorm: plain jnp in `flax.linen.BatchNorm`'s op order.

Same fields, parameters (`scale`, `bias`) and `batch_stats` collection
(`mean`, `var`) as `nn.BatchNorm`, flax's running-statistics semantics
(biased variance, same `momentum` / `epsilon`), float32 statistics whatever
the compute dtype, autodiff backward. The graph the CPU tests pin is the
graph the chip runs: nothing here asks the backend or the environment.

Why it is not `nn.BatchNorm` itself (ROADMAP D1): flax 0.12 clamps the
variance (`jnp.maximum(0., mean2 - mean²)`) and its backward differs from
this one by an ulp (`tests/test_batchnorm.py`), so the swap moves the R50
cell's program and `tests/test_golden.py`; it waits for a chip pair of its
own. `models/heads.py` uses flax's.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def _normalize(x, mean, var, scale, bias, eps, dtype):
    """flax `_normalize` semantics (force_float32_reductions=True): the whole
    computation runs in f32 via promotion — `(x - mean) * (rsqrt(var + eps)
    * scale) + bias` with f32 mean/var/scale/bias — and only the RESULT is
    cast to `dtype`."""
    y = (x - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias
    return y.astype(dtype)


class BatchNorm(nn.Module):
    """Per-device batch statistics by default (MoCo's ShuffleBN depends on
    them); `axis_name` (SyncBN) takes a `pmean` over the per-device mean and
    mean of squares, which is the cross-device batch statistic."""

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
