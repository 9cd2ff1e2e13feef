"""Pallas TPU kernel: BN-normalize → ReLU fused into a stride-1 3x3 conv.

Companion to `pallas_fused_conv.py` (the 1x1 tail): the Bottleneck's OTHER
interior normalize pass is bn1→relu feeding the 3x3 conv2. A 3x3 stride-1
convolution is nine channel-contractions over row/column-shifted views, so
the same in-register trick applies — normalize+ReLU each x tile in VMEM and
accumulate the nine `[rows·W, K] @ [K, N]` tap matmuls without the
normalized tensor ever reaching HBM.

Halo handling: the kernel receives the SAME array through three input refs
whose index maps point at the previous / current / next row-block (clamped
at the boundary); row masks zero the out-of-range contributions, and column
shifts are masked at the W edges, reproducing the conv's zero padding
exactly.

Stride-2 conv2 (the first block of each stage) is fused too:
`bn_relu_conv3x3_s2` below tiles the OUTPUT rows and reads the strided
input halo through one widened ref (even/odd row decomposition, two edge
masks), so all 16 R50 interior 3x3s go through the fused family.
`interpret=True` runs on CPU for the equivalence tests;
`tests/test_fused_conv3x3.py` also pins the TPU (Mosaic) lowering
hardware-free via cross-platform export.

The backward twin `conv3x3_dw` (VERDICT r3 #5) closes the remaining HBM
leak: the custom VJP used to materialize z = relu(x̂) in HBM solely to feed
the filter-gradient correlation (the input-gradient dz never reads z — it
is a transposed conv of dy, already optimal as plain XLA). Here the nine
tap gradients dW[di,dj] = Σ z[i+di, j+dj]ᵀ·dy[i,j] accumulate in one VMEM
scratch while z is recomputed tile-by-tile from x with the same halo refs
and edge masks as the forward — so the normalized activation now never
exists in HBM in EITHER direction for the 3x3, matching the 1x1 tail's
`bn_relu_matmul_dw` story.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv3x3_kernel(xm_ref, x0_ref, xp_ref, a_ref, b_ref, w_ref, o_ref, *,
                    bh, h, blocks_per_img):
    """One row-block [bh, W, K] → [bh, W, N].

    x0 is the current row-block; xm/xp are SINGLE halo rows (the row just
    above / below the block, index maps clamped WITHIN the image; masks
    below zero the clamped rows) — x streams at ~(bh+2)/bh reads, not 3x.
    The batch is folded into the row grid, so all row coordinates here are
    per-IMAGE (a block never straddles an image). w_ref holds the taps as
    [9, K, N].
    """
    i = pl.program_id(0)  # row-block index over B*H/bh
    w_all = w_ref[...]
    bw = x0_ref.shape[1]  # W (full width in this block)
    k = x0_ref.shape[2]
    n = w_all.shape[-1]

    def normalize(ref):
        x = ref[...].astype(jnp.float32)
        return jnp.maximum(x * a_ref[0, 0] + b_ref[0, 0], 0.0).astype(w_all.dtype)

    zm = normalize(xm_ref)  # [1, W, K] halo row above (clamped at image top)
    z0 = normalize(x0_ref)  # [bh, W, K] current row-block
    zp = normalize(xp_ref)  # [1, W, K] halo row below (clamped at bottom)

    acc = jnp.zeros((bh * bw, n), jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (bh, bw, 1), 1)
    row_in_block = jax.lax.broadcasted_iota(jnp.int32, (bh, bw, 1), 0)
    # row within THIS IMAGE (zero pad happens at image edges, not batch ones)
    img_row = (i % blocks_per_img) * bh + row_in_block

    for di in (-1, 0, 1):
        # source rows (img_row + di): build the di-shifted row view of the
        # current block from the halo rows + z0
        if di == 0:
            z_rows = z0
            row_ok = jnp.ones((bh, bw, 1), jnp.bool_)
        elif di == -1:
            # shift down: row r reads source row r-1 → top row is the halo
            # (bh == 1: the shifted block IS the halo row; avoids a
            # zero-size slice, which Mosaic rejects)
            z_rows = zm if bh == 1 else jnp.concatenate(
                [zm, z0[:-1]], axis=0
            )
            row_ok = img_row - 1 >= 0
        else:
            z_rows = zp if bh == 1 else jnp.concatenate(
                [z0[1:], zp], axis=0
            )
            row_ok = img_row + 1 <= h - 1
        for dj in (-1, 0, 1):
            if dj == 0:
                z_tap = z_rows
                col_ok = jnp.ones((bh, bw, 1), jnp.bool_)
            elif dj == -1:
                z_tap = jnp.concatenate(
                    [jnp.zeros_like(z_rows[:, :1]), z_rows[:, :-1]], axis=1
                )
                col_ok = col - 1 >= 0
            else:
                z_tap = jnp.concatenate(
                    [z_rows[:, 1:], jnp.zeros_like(z_rows[:, :1])], axis=1
                )
                col_ok = col + 1 <= bw - 1
            mask = (row_ok & col_ok).astype(w_all.dtype)
            z_masked = (z_tap * mask).reshape(bh * bw, k)
            tap = w_all[(di + 1) * 3 + (dj + 1)]
            acc += jnp.dot(z_masked, tap, preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(bh, bw, n).astype(o_ref.dtype)


def _dw3x3_kernel(xm_ref, x0_ref, xp_ref, a_ref, b_ref, dy_ref, o_ref,
                  acc_ref, *, bh, h, blocks_per_img):
    """Accumulate the nine tap gradients over row-blocks.

    Grid is (n_blocks, row_blocks) with the ROW dim last (the sequential
    accumulation axis, `_dw_kernel` convention): for each row-block the
    di/dj-shifted masked ẑ views — identical construction to the forward —
    contract against the local dy tile, `acc[tap] += ẑ_tapᵀ @ dy`.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...]
    bw = x0_ref.shape[1]
    k = x0_ref.shape[2]

    def normalize(ref):
        x = ref[...].astype(jnp.float32)
        return jnp.maximum(x * a_ref[0, 0] + b_ref[0, 0], 0.0).astype(dy.dtype)

    zm = normalize(xm_ref)
    z0 = normalize(x0_ref)
    zp = normalize(xp_ref)

    col = jax.lax.broadcasted_iota(jnp.int32, (bh, bw, 1), 1)
    row_in_block = jax.lax.broadcasted_iota(jnp.int32, (bh, bw, 1), 0)
    img_row = (i % blocks_per_img) * bh + row_in_block
    dyr = dy.reshape(bh * bw, dy.shape[-1])

    for di in (-1, 0, 1):
        if di == 0:
            z_rows = z0
            row_ok = jnp.ones((bh, bw, 1), jnp.bool_)
        elif di == -1:
            z_rows = zm if bh == 1 else jnp.concatenate([zm, z0[:-1]], axis=0)
            row_ok = img_row - 1 >= 0
        else:
            z_rows = zp if bh == 1 else jnp.concatenate([z0[1:], zp], axis=0)
            row_ok = img_row + 1 <= h - 1
        for dj in (-1, 0, 1):
            if dj == 0:
                z_tap = z_rows
                col_ok = jnp.ones((bh, bw, 1), jnp.bool_)
            elif dj == -1:
                z_tap = jnp.concatenate(
                    [jnp.zeros_like(z_rows[:, :1]), z_rows[:, :-1]], axis=1
                )
                col_ok = col - 1 >= 0
            else:
                z_tap = jnp.concatenate(
                    [z_rows[:, 1:], jnp.zeros_like(z_rows[:, :1])], axis=1
                )
                col_ok = col + 1 <= bw - 1
            mask = (row_ok & col_ok).astype(z_tap.dtype)
            z_masked = (z_tap * mask).reshape(bh * bw, k)
            tap = (di + 1) * 3 + (dj + 1)
            acc_ref[tap] += jax.lax.dot_general(
                z_masked, dyr, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...]


def _pick_rows(h: int, w: int, k: int) -> int:
    """Row-block: target a few hundred KB of z tile, divide H."""
    target = max(1, (256 << 10) // max(1, 2 * w * k))
    bh = 1
    for c in (32, 16, 8, 4, 2, 1):
        if c <= target and h % c == 0:
            bh = c
            break
    return bh


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def bn_relu_conv3x3(
    x: jax.Array,      # [B, H, W, K] pre-normalize activations
    a: jax.Array,      # [K] f32 (γ·rstd)
    b: jax.Array,      # [K] f32 (β − μ·γ·rstd)
    w: jax.Array,      # [3, 3, K, N] conv kernel
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """relu(x·a + b) ⊛ w (stride 1, zero pad 1), normalized tensor VMEM-only.

    The batch folds into the row grid: blocks never straddle a batch
    boundary (bh divides H), and the row masks use per-image coordinates.
    """
    bsz, h, wd, k = x.shape
    n = w.shape[-1]
    bh = _pick_rows(h, wd, k)
    xr = x.reshape(bsz * h, wd, k)
    w9 = w.reshape(9, k, n).astype(x.dtype)
    nblocks = (bsz * h) // bh
    blocks_per_img = h // bh

    # current row-block, plus SINGLE-ROW halo blocks above/below (block
    # shape (1, W, K) → the row index IS the block index), clamped to the
    # same image; the kernel's row masks zero the clamped contributions
    def idx_cur(i):
        return (i, 0, 0)

    def idx_prev_row(i):
        img = i // blocks_per_img
        return (jnp.maximum(i * bh - 1, img * h), 0, 0)

    def idx_next_row(i):
        img = i // blocks_per_img
        return (jnp.minimum((i + 1) * bh, (img + 1) * h - 1), 0, 0)

    vma = getattr(getattr(x, "aval", None), "vma", frozenset())
    kernel = functools.partial(_conv3x3_kernel, bh=bh, h=h,
                               blocks_per_img=blocks_per_img)
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, wd, k), idx_prev_row),
            pl.BlockSpec((bh, wd, k), idx_cur),
            pl.BlockSpec((1, wd, k), idx_next_row),
            pl.BlockSpec((1, 1, k), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i: (0, 0, 0)),
            pl.BlockSpec((9, k, n), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bh, wd, n), idx_cur),
        out_shape=jax.ShapeDtypeStruct((bsz * h, wd, n), out_dtype, vma=vma),
        interpret=interpret,
    )(xr, xr, xr, a.reshape(1, 1, k).astype(jnp.float32),
      b.reshape(1, 1, k).astype(jnp.float32), w9)
    return out.reshape(bsz, h, wd, n)


def _conv3x3s2_kernel(xm_ref, x0_ref, a_ref, b_ref, w_ref, o_ref, *,
                      bho, blocks_per_img):
    """One OUTPUT row-block [bho, W/2, N] of the stride-2 fused conv.

    x0 holds the 2·bho input rows [2r₀, 2r₀+2bho) — output row r reads
    input rows 2r−1/2r/2r+1 (symmetric pad 1, torch semantics), so the even
    rows of x0 are the di=0 taps, the odd rows the di=+1 taps, and di=−1 is
    the odd rows shifted down with xm (the single row above, clamped within
    the image) sliding in at the top. With H and W even, only the image-top
    row (di=−1) and the first output column (dj=−1) ever touch padding —
    the only two masks in the kernel.
    """
    i = pl.program_id(0)
    w_all = w_ref[...]
    w_in = x0_ref.shape[1]
    k = x0_ref.shape[2]
    n = w_all.shape[-1]
    wo = w_in // 2

    def normalize(ref):
        x = ref[...].astype(jnp.float32)
        return jnp.maximum(x * a_ref[0, 0] + b_ref[0, 0], 0.0).astype(w_all.dtype)

    zm = normalize(xm_ref)                       # [1, W, K] row 2r₀−1
    zpair = normalize(x0_ref).reshape(bho, 2, w_in, k)
    even = zpair[:, 0]                           # input rows 2r   [bho, W, K]
    odd = zpair[:, 1]                            # input rows 2r+1
    above = zm if bho == 1 else jnp.concatenate([zm, odd[:-1]], axis=0)

    acc = jnp.zeros((bho * wo, n), jnp.float32)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (bho, wo, 1), 0)
    img_out_row = (i % blocks_per_img) * bho + out_row
    out_col = jax.lax.broadcasted_iota(jnp.int32, (bho, wo, 1), 1)

    for di, z_rows in ((-1, above), (0, even), (1, odd)):
        row_ok = (2 * img_out_row - 1 >= 0) if di == -1 else None
        pairs = z_rows.reshape(bho, wo, 2, k)
        for dj in (-1, 0, 1):
            if dj == 0:
                z_tap = pairs[:, :, 0]           # input col 2c
                col_ok = None
            elif dj == 1:
                z_tap = pairs[:, :, 1]           # input col 2c+1
                col_ok = None
            else:                                # input col 2c−1
                odd_cols = pairs[:, :, 1]
                z_tap = jnp.concatenate(
                    [jnp.zeros_like(odd_cols[:, :1]), odd_cols[:, :-1]],
                    axis=1,
                )
                col_ok = out_col - 1 >= 0
            ok = row_ok if col_ok is None else (
                col_ok if row_ok is None else row_ok & col_ok)
            if ok is not None:
                z_tap = z_tap * ok.astype(z_tap.dtype)
            tap = w_all[(di + 1) * 3 + (dj + 1)]
            acc += jnp.dot(z_tap.reshape(bho * wo, k), tap,
                           preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(bho, wo, n).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def bn_relu_conv3x3_s2(
    x: jax.Array,      # [B, H, W, K] pre-normalize activations (H, W even)
    a: jax.Array,      # [K] f32 (γ·rstd)
    b: jax.Array,      # [K] f32 (β − μ·γ·rstd)
    w: jax.Array,      # [3, 3, K, N] conv kernel
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """relu(x·a + b) ⊛ w at stride 2, symmetric pad 1 — the stage-first
    Bottleneck conv2 sites (VERDICT r3 #5), normalized tensor VMEM-only."""
    bsz, h, wd, k = x.shape
    assert h % 2 == 0 and wd % 2 == 0, (h, wd)
    n = w.shape[-1]
    ho = h // 2
    # one output row costs two input rows of VMEM: halve the row target
    bho = _pick_rows(ho, wd, 2 * k)
    xr = x.reshape(bsz * h, wd, k)
    w9 = w.reshape(9, k, n).astype(x.dtype)
    nblocks = (bsz * ho) // bho
    blocks_per_img = ho // bho

    def idx_cur(i):
        # output block i consumes the contiguous input rows
        # [2·bho·i, 2·bho·(i+1)) — block-aligned by construction
        return (i, 0, 0)

    def idx_above(i):
        img = i // blocks_per_img
        return (jnp.maximum(2 * bho * i - 1, img * h), 0, 0)

    vma = getattr(getattr(x, "aval", None), "vma", frozenset())
    kernel = functools.partial(_conv3x3s2_kernel, bho=bho,
                               blocks_per_img=blocks_per_img)
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, wd, k), idx_above),
            pl.BlockSpec((2 * bho, wd, k), idx_cur),
            pl.BlockSpec((1, 1, k), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i: (0, 0, 0)),
            pl.BlockSpec((9, k, n), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bho, wd // 2, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * ho, wd // 2, n), out_dtype,
                                       vma=vma),
        interpret=interpret,
    )(xr, xr, a.reshape(1, 1, k).astype(jnp.float32),
      b.reshape(1, 1, k).astype(jnp.float32), w9)
    return out.reshape(bsz, ho, wd // 2, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv3x3_dw(
    x: jax.Array,      # [B, H, W, K] pre-normalize activations
    a: jax.Array,      # [K] f32 (γ·rstd)
    b: jax.Array,      # [K] f32 (β − μ·γ·rstd)
    dy: jax.Array,     # [B, H, W, N] upstream cotangent
    interpret: bool = False,
) -> jax.Array:
    """dW[3, 3, K, N] of relu(x·a+b) ⊛ w with ẑ recomputed in VMEM.

    The [9,K,bn] f32 accumulator lives in VMEM across the row grid,
    N-blocked so the 512-channel stages stay within the ~16 MB/core
    budget. x and dy stream once PER N-BLOCK (n//bn passes — 2 at the
    K=N=512 stage, 1 elsewhere); the normalized activation still never
    exists in HBM, which is the HBM saving the fusion is after.
    """
    bsz, h, wd, k = x.shape
    n = dy.shape[-1]
    bh = _pick_rows(h, wd, k)
    xr = x.reshape(bsz * h, wd, k)
    dyr = dy.reshape(bsz * h, wd, n)
    nblocks = (bsz * h) // bh
    blocks_per_img = h // bh
    # N-block the accumulator: 9·K·bn·4 B ≤ ~4.7 MB at K=512, bn=256
    bn = n
    while 9 * k * bn * 4 > (5 << 20) and bn % 2 == 0:
        bn //= 2

    def idx_cur(j, i):
        return (i, 0, 0)

    def idx_prev_row(j, i):
        img = i // blocks_per_img
        return (jnp.maximum(i * bh - 1, img * h), 0, 0)

    def idx_next_row(j, i):
        img = i // blocks_per_img
        return (jnp.minimum((i + 1) * bh, (img + 1) * h - 1), 0, 0)

    vma = getattr(getattr(x, "aval", None), "vma", frozenset())
    kernel = functools.partial(_dw3x3_kernel, bh=bh, h=h,
                               blocks_per_img=blocks_per_img)
    out = pl.pallas_call(
        kernel,
        grid=(n // bn, nblocks),
        in_specs=[
            pl.BlockSpec((1, wd, k), idx_prev_row),
            pl.BlockSpec((bh, wd, k), idx_cur),
            pl.BlockSpec((1, wd, k), idx_next_row),
            pl.BlockSpec((1, 1, k), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((bh, wd, bn), lambda j, i: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((9, k, bn), lambda j, i: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((9, k, n), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((9, k, bn), jnp.float32)],
        interpret=interpret,
    )(xr, xr, xr, a.reshape(1, 1, k).astype(jnp.float32),
      b.reshape(1, 1, k).astype(jnp.float32), dyr)
    return out.reshape(3, 3, k, n)
