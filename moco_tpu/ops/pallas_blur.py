"""Pallas TPU kernel: per-sample separable Gaussian blur.

The v2 augmentation stack blurs each key/query crop with a per-sample random
sigma (SimCLR-style `GaussianBlur`, `moco/loader.py:≈L20-32`). The portable
implementation (data/augment.py) is 2x(2R+1) weighted shifted-adds over the
full image — ~46 full-image HBM round-trips per sample. This kernel does the
whole separable stencil in VMEM: ONE read of the padded image, one write of
the result, with both convolution passes and the intermediate transpose
on-chip. A measured ~10% of the MoCo-v2 step time on v5e rides on this op.

Layout notes (TPU tiling wants the last dim to be lanes=128-ish):
- Images are processed as `[3, H, W]` (channels first), so H/W land on the
  sublane/lane dims instead of the 3-wide channel axis.
- The H pass shifts along sublanes; the array is then transposed in VMEM so
  the W pass also shifts along sublanes (lane shifts are the slow path).
- Per-sample kernel WEIGHTS carry both the sigma and the apply/skip draw
  (skip == identity kernel: one-hot at the center tap) so there is no
  divergent control flow.

The public entry `gaussian_blur_batch` is vmapped over the batch (pallas
lifts the vmap axis into the grid); `interpret=True` is used automatically
off-TPU so the same code path is unit-testable on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _blur_kernel(img_ref, kern_ref, out_ref):
    """One sample. img_ref: [3, H+2R, W+2R] edge-padded; kern_ref: [1, 2R+1]
    (SMEM); out_ref: [3, H, W]. Accumulates in f32 whatever the I/O dtype."""
    taps = kern_ref.shape[-1]
    h, w = out_ref.shape[1], out_ref.shape[2]
    x = img_ref[...].astype(jnp.float32)  # [3, H+2R, W+2R] in VMEM
    # H pass: shift along sublanes
    acc = jnp.zeros((3, h, x.shape[2]), jnp.float32)
    for j in range(taps):
        acc = acc + kern_ref[0, j] * x[:, j : j + h, :]
    # transpose so the W pass also shifts along sublanes
    t = jnp.transpose(acc, (0, 2, 1))  # [3, W+2R, H]
    acc2 = jnp.zeros((3, w, h), jnp.float32)
    for j in range(taps):
        acc2 = acc2 + kern_ref[0, j] * t[:, j : j + w, :]
    out_ref[...] = jnp.transpose(acc2, (0, 2, 1)).astype(out_ref.dtype)  # [3, H, W]


@functools.partial(jax.jit, static_argnames=("radius", "interpret"))
def gaussian_blur_batch(
    images: jax.Array,   # [B, H, W, 3] float (NHWC, the pipeline dtype)
    kernels: jax.Array,  # [B, 2R+1] per-sample normalized tap weights
    radius: int,
    interpret: bool = False,
) -> jax.Array:
    """Apply each sample's separable kernel to its image; returns NHWC in
    the input dtype (f32 accumulation inside the kernel)."""
    b, h, w, _ = images.shape
    taps = 2 * radius + 1
    assert kernels.shape == (b, taps), (kernels.shape, (b, taps))
    chw = jnp.transpose(images, (0, 3, 1, 2))  # [B, 3, H, W]
    padded = jnp.pad(
        chw, ((0, 0), (0, 0), (radius, radius), (radius, radius)), mode="edge"
    )

    def one(img_padded, kern):
        # inside a shard_map region the replication checker needs to know the
        # output varies the same way the input does (vma must be explicit on
        # pallas outputs); outside, vma is just empty
        vma = getattr(getattr(img_padded, "aval", None), "vma", frozenset())
        return pl.pallas_call(
            _blur_kernel,
            out_shape=jax.ShapeDtypeStruct((3, h, w), images.dtype, vma=vma),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=interpret,
        )(img_padded, kern.reshape(1, taps))

    out = jax.vmap(one)(padded, kernels.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 3, 1))


def blur_radius(out_size: int) -> int:
    """Fixed tap radius for a given crop size (single source of truth for
    both the portable and Pallas blur paths)."""
    return max(1, int(0.05 * out_size))


def blur_weights(key: jax.Array, radius: int, sigma_range, prob: float) -> jax.Array:
    """Per-sample tap weights folding in BOTH the sigma draw and the
    apply-probability draw (skip == identity one-hot kernel). The single
    source of the sigma/apply sampling math — the portable shifted-add blur
    in data/augment.py consumes these same weights."""
    ksig, kp = jax.random.split(key)
    sigma = jax.random.uniform(ksig, (), minval=sigma_range[0], maxval=sigma_range[1])
    offs = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    kernel = jnp.exp(-0.5 * (offs / sigma) ** 2)
    kernel = kernel / jnp.sum(kernel)
    identity = jnp.zeros((2 * radius + 1,), jnp.float32).at[radius].set(1.0)
    apply = jax.random.uniform(kp, ()) < prob
    return jnp.where(apply, kernel, identity)
