"""Pallas TPU kernels: streaming per-channel reduction passes for BatchNorm.

Round-2 profiling of the MoCo-v2 R50 step (xplane, v5e) put ~35 ms of the
~70 ms step in XLA's per-channel reduce fusions — the train-mode BN batch
statistics (forward) and the dgamma/dbeta-style reductions (backward). Those
passes are pure streaming reads of the fattest activations in the network,
but XLA's reduce fusions run well below the HBM roof (~55-60% measured in
isolation). These kernels do the same reductions as explicit Pallas
streaming loops tiled for VMEM, with f32 accumulation:

- `channel_sums(x)`        → (Σx, Σx²) over N,H,W          (BN fwd stats)
- `channel_grad_sums(dy, xhat)` → (Σdy, Σdy·x̂) over N,H,W  (BN bwd terms)

Both read each element exactly once. Used by `models/fast_bn.py`'s
custom-VJP BatchNorm; `interpret=True` makes the same code path testable on
CPU (see tests/test_pallas_stats.py).

The reference's cuDNN BN kernels do these same fused reductions on GPU
(`torch.nn.BatchNorm2d` internals) — this is the TPU-native equivalent
(SURVEY §2.10: cuDNN → MXU/Pallas).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Read ONCE at import: these kernels are traced inside jitted programs
# (fast_bn inside the train step), so a mid-process env change could never
# reach an already-compiled program — the jit cache does not key on it.
# Import-time semantics make that staleness impossible instead of silent
# (tools/_perf_ab.py sweeps the knob one subprocess per setting).
_TILE_KIB = int(os.environ.get("MOCO_TPU_STATS_TILE_KIB", "0") or 0)


def _sums_kernel(x_ref, sum_ref, sq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...].astype(jnp.float32)  # [T, C]
    sum_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def _grad_sums_kernel(dy_ref, x_ref, mu_ref, r_ref, dsum_ref, dxh_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dsum_ref[...] = jnp.zeros_like(dsum_ref)
        dxh_ref[...] = jnp.zeros_like(dxh_ref)

    dy = dy_ref[...].astype(jnp.float32)  # [T, C]
    # recompute x̂ = (x-μ)·r in-register: saves materializing x̂ in HBM
    xh = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * r_ref[...]
    dsum_ref[...] += jnp.sum(dy, axis=0, keepdims=True)
    dxh_ref[...] += jnp.sum(dy * xh, axis=0, keepdims=True)


def _tile_rows(n: int, c: int, kib: int | None = None) -> int:
    """Rows per VMEM tile: target ~1 MB per streamed operand tile, keep the
    row count a divisor-friendly power of two, and never exceed n.

    Why 1 MB (first-chip finding, r5): the grad-sums kernel keeps ~4 f32
    tile-sized intermediates live on the Mosaic stack (dy, x̂, their
    product, plus the cast of x); at the old 2 MB bf16 tile (t=16384,
    c=64) that stack plus the double-buffered input windows totalled
    19.87 MB against the 16 MB scoped-VMEM limit and the R50 step failed
    to compile on the v5e (builder run, 2026-07-31). The
    forward microbench only ever passed because its row count happened to
    be indivisible by 16384. 1 MB tiles put the worst case ~10 MB. The
    floor is 8 (the f32 sublane count), NOT a round 512: a 512-row floor
    would recreate the same 1M-element tile at c=2048 (R50 layer4) that
    blew the limit at c=64.

    MOCO_TPU_STATS_TILE_KIB (read at import, see _TILE_KIB above)
    overrides the per-operand byte target (tools/_perf_ab.py sweeps it to
    bound the tile size's share of the r5-vs-r2 step-time gap)."""
    if kib is None:
        kib = _TILE_KIB
    budget = kib * 1024 if kib else (1 << 20)
    # the row cap scales with the budget (fractionally — an integer >>20
    # would floor a 1.5 MiB budget back to the default cap): a fixed 1<<13
    # cap would make a 2 MiB override compile the SAME program as the
    # default at c<=64 (R50 layer1 — exactly the pre-fix operating point
    # the sweep exists to reach), silently voiding the A/B (review, r5)
    row_cap = max(8, (1 << 13) * budget // (1 << 20))
    target = max(8, min(row_cap, budget // (2 * c)))
    # floor to a power of two BEFORE the divisibility loop: a factor-3
    # target (e.g. a 768 KiB budget) would otherwise never divide a
    # pow2-shaped n and halve all the way to degenerate 1-row tiles
    # (review, r5)
    target = 1 << (target.bit_length() - 1)
    while n % target:
        target //= 2
        if target == 0:
            return n  # pathological n: single tile
    return target


@functools.partial(jax.jit, static_argnames=("interpret",))
def channel_sums(x: jax.Array, interpret: bool = False):
    """(Σx, Σx²) over all but the last axis. x: [..., C] (any rank), returns
    two f32 [C] vectors. One streaming read of x."""
    c = x.shape[-1]
    xr = x.reshape(-1, c)
    n = xr.shape[0]
    t = _tile_rows(n, c)
    vma = getattr(getattr(x, "aval", None), "vma", frozenset())
    s, sq = pl.pallas_call(
        _sums_kernel,
        grid=(n // t,),
        in_specs=[pl.BlockSpec((t, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(xr)
    return s[0], sq[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def channel_grad_sums(
    dy: jax.Array,
    x: jax.Array,
    mean: jax.Array,
    rstd: jax.Array,
    interpret: bool = False,
):
    """(Σdy, Σdy·x̂) over all but the last axis, with x̂ = (x-mean)·rstd
    recomputed in-register — the two reductions of the BN backward. One
    streaming read of dy and x each; x̂ never touches HBM."""
    c = dy.shape[-1]
    dyr = dy.reshape(-1, c)
    xr = x.reshape(-1, c)
    n = dyr.shape[0]
    t = _tile_rows(n, c)
    vma = getattr(getattr(dy, "aval", None), "vma", frozenset())
    s, sx = pl.pallas_call(
        _grad_sums_kernel,
        grid=(n // t,),
        in_specs=[
            pl.BlockSpec((t, c), lambda i: (i, 0)),
            pl.BlockSpec((t, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(dyr, xr, mean.reshape(1, c).astype(jnp.float32),
      rstd.reshape(1, c).astype(jnp.float32))
    return s[0], sx[0]
