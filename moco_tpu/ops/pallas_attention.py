"""Pallas TPU kernels: block-causal grouped-query attention with the scores in
VMEM, and q and k's way to it from their projections.

`models/sdar.py::Attention` as plain einsums writes a float32 `[B, heads, L, L]`
score tensor to memory, masks it, and reads it back for every pass of the
softmax and of its transpose; the half above the block diagonal is computed
and thrown away. Here the scores, the mask, the softmax and the mix are one
kernel (and one more for the backward pass): a program serves one batch row, one
key/value head and up to `HEADS_PER_PROGRAM` of the query heads that share it,
works by q tiles of 128 rows, and for q tile `t` takes the keys
`[0, 128 (t + 1))` only. `block_length` divides 128, so the tiles above the
diagonal are wholly masked and never computed, those below it wholly visible,
and the tile on it takes the element mask. At L = 512 that is 10 of 16 tiles,
and a whole row of scores is in VMEM at once: no online softmax.

The arithmetic is the einsum path's: q·k with `dtype` operands and float32
accumulation, the scale by `1/sqrt(head_dim)` in float32, max / exp / sum in
float32, the weights cast to `dtype` before they meet v, float32 accumulation
there. One order differs, not in precision: the scale and the division by the
weights' sum are multiplications by the reciprocal.

The forward pass of a differentiated call also writes the rows' log-sum-exp
(`[B, heads, 1, L]` float32). The backward pass recomputes the weights from q,
k and it, with keys on sublanes and queries on lanes (the log-sum-exp and
`delta = sum(o * do)`, which it forms from o and do in float32, are then lane
rows, and two of the three transposed products need no transpose), and forms
dv, dp, ds = p (dp - delta), dq and dk in VMEM; dk and dv accumulate in float32
over the `group` query heads that share a key/value head. ds meets k and q as
`dtype`, which is what the TPU's default precision makes of the einsum path's
float32 ds.

A head is `head_dim` lanes of the projections' own last axis
(`[B, L, heads * head_dim]`), and q, k, v and o are never seen in another shape:
`norm_rotary` (one kernel and its transpose) does the per-head RMSNorm, the
rotate-half rotary and the cast of q and of k on that layout, a head's lanes at
a time in VMEM and in float32, so that from a projection's product to the `o`
projection's operand nothing is reshaped and XLA has no layout to change. The
two `optimization_barrier`s that held XLA's change of layout at the finished
`dtype` tensors (PR 28) are gone with it; the compiled step holds no copy of a
q- or k-sized tensor (`PERF.md` section 6, PR 30).

Outputs carry the inputs' `vma`: the calls type-check inside a `shard_map`
region with `check_vma` on. `interpret=True` runs the same code on the CPU
(outside any `shard_map`: see `data/augment.py::build_two_crops_sharded`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128
# query heads of one group a program serves, side by side on the lanes: 8 where
# one head a program read 10 % slower (1 024 grid steps a call for 128; my chip
# run, PR 28)
HEADS_PER_PROGRAM = 8
# rows of a batch row that a program of `norm_rotary` takes, at most
PREP_ROWS = 4 * TILE
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
# the longest view the whole-row kernel takes: a program's q and o blocks, twice
# each for the pipeline, are 8 MB of VMEM there
WHOLE_ROW_MAX = 1024
# the tiled pair's blocks: q rows a program, keys a grid step
Q_TILE, KEY_CHUNK = 256, 512
# a masked score: finite, so that a row whose keys so far are all masked has a
# running maximum to subtract; what it gathers meanwhile is multiplied by
# exp(MASKED - m) = 0 when the row's first live key arrives
MASKED = -1e30
# dk and dv of a key/value head at 8 192 tokens: two float32 scratches of 4 MB
# and two output blocks of 2 MB, twice for the pipeline, beside the tiles
_TILED_BWD_VMEM = 48 * 2 ** 20
_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel"))


def attention_plan(length: int, head_dim: int, block_length: int,
                   backend: str | None = None, qk_norm: bool = True,
                   masked: bool = False) -> dict:
    """Which path attention takes for these shapes, how many `TILE` x `TILE`
    score tiles it computes and skips, and who prepares q and k for it
    (`qk_prep`: `norm_rotary` where attention runs in a kernel, `fused` with the
    per-head norm and `rotary` for an encoder that has none; XLA elsewhere): the
    `attn` block of the `setup` event. The kernels need a TPU, whole lane tiles
    for a head, whole q tiles, and blocks that do not straddle a tile. `fused`
    is the whole-row kernel, for views of up to `WHOLE_ROW_MAX` tokens whose
    mask is a function of positions; a longer view, or a mask that is data
    (`masked`: the caller hands the pairs that are live), takes the `tiled` pair
    where the view is whole key chunks."""
    side = -(-length // TILE)
    aligned = ((backend or jax.default_backend()) == "tpu" and head_dim % TILE == 0
               and length % TILE == 0 and TILE % block_length == 0)
    if aligned and not masked and length <= WHOLE_ROW_MAX:
        path, skipped = "fused", side * (side - 1) // 2
    elif aligned and all(length % n == 0 for n in _tiling(length)):
        # every key chunk that reaches under the diagonal of a q tile, whole
        tq, tk = _tiling(length)
        computed = sum((tq // TILE) * (tk // TILE) * (_last_chunk(t, tq, tk) + 1)
                       for t in range(length // tq))
        path, skipped = "tiled", side * side - computed
    else:
        path, skipped = "einsum", 0
    return {"path": path, "tiles": side * side, "tiles_skipped": skipped,
            "qk_prep": "xla" if path == "einsum" else "fused" if qk_norm else "rotary"}


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _diagonal_visible(block_length: int, keys_on_rows: bool):
    """The element mask of a tile on the diagonal: a query sees a key iff the
    key's block is not after its own."""
    rows = lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0) // block_length
    cols = lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1) // block_length
    return rows <= cols if keys_on_rows else cols <= rows


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, dim, block_length):
    """One batch row, one key/value head and the query heads of its group that
    this program serves, side by side on the lanes: q/o refs `[L, n * D]`,
    k/v refs `[L, D]`, lse `[n, 1, L]`."""
    scale = 1.0 / math.sqrt(dim)
    visible = _diagonal_visible(block_length, keys_on_rows=False)
    for j in range(q_ref.shape[1] // dim):
        head = slice(j * dim, (j + 1) * dim)
        for t in range(q_ref.shape[0] // TILE):
            rows, before = slice(t * TILE, (t + 1) * TILE), slice(0, t * TILE)
            q = q_ref[rows, head]
            s = jnp.where(visible, _dot(q, k_ref[rows, :], _NT) * scale, -jnp.inf)
            m = jnp.max(s, -1, keepdims=True)
            if t:
                s0 = _dot(q, k_ref[before, :], _NT) * scale
                m = jnp.maximum(m, jnp.max(s0, -1, keepdims=True))
            e = jnp.exp(s - m)
            total = jnp.sum(e, -1, keepdims=True)
            if t:
                e0 = jnp.exp(s0 - m)
                total = total + jnp.sum(e0, -1, keepdims=True)
            # the weights are whole before they are rounded, as in the einsum path:
            # rounded first and divided after, the output's error reads 1.6 times
            # that path's (my chip run, PR 28)
            inv = 1.0 / total
            acc = _dot((e * inv).astype(v_ref.dtype), v_ref[rows, :])
            if t:
                acc = acc + _dot((e0 * inv).astype(v_ref.dtype), v_ref[before, :])
            o_ref[rows, head] = acc.astype(o_ref.dtype)
            if lse_ref:
                # a column of 128 row statistics -> the lane row the backward reads
                lse = jnp.broadcast_to(m + jnp.log(total), (TILE, TILE))
                lse_ref[0][j, :, rows] = lse.T[:1, :]


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, dim, block_length):
    """As `_fwd_kernel`, with the rest of the group on grid axis 2: dk and dv
    accumulate over both. Scores are `[keys, queries]` here."""
    scale = 1.0 / math.sqrt(dim)
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    visible = _diagonal_visible(block_length, keys_on_rows=True)
    for j in range(q_ref.shape[1] // dim):
        head = slice(j * dim, (j + 1) * dim)
        for t in range(q_ref.shape[0] // TILE):
            rows = slice(t * TILE, (t + 1) * TILE)
            q, do = q_ref[rows, head], do_ref[rows, head]
            lse = lse_ref[j, :, rows]
            delta = jnp.sum(o_ref[rows, head].astype(jnp.float32) * do.astype(jnp.float32),
                            -1, keepdims=True)
            delta = jnp.broadcast_to(delta, (TILE, TILE)).T[:1, :]    # as `lse`: a lane row
            dq = jnp.zeros(q.shape, jnp.float32)
            for keys in ([slice(0, t * TILE)] if t else []) + [rows]:
                k, v = k_ref[keys, :], v_ref[keys, :]
                s = _dot(k, q, _NT) * scale
                if keys is rows:
                    s = jnp.where(visible, s, -jnp.inf)
                p = jnp.exp(s - lse)
                ds = (p * (_dot(v, do, _NT) - delta)).astype(q.dtype)
                dv_acc[keys, :] += _dot(p.astype(do.dtype), do)
                dk_acc[keys, :] += _dot(ds, q)
                dq = dq + _dot(ds.T, k)
            dq_ref[rows, head] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _grid_and_specs(q, k, heads, kv_heads):
    """The grid `(batch, kv_head, programs a group)` and its block specs: `n`
    query heads' and a key/value head's lanes of `[B, L, heads * D]`, and the
    query heads' rows of `[B, heads, 1, L]`."""
    b, length, _ = q.shape
    dim = q.shape[2] // heads
    n = math.gcd(heads // kv_heads, HEADS_PER_PROGRAM)
    per = heads // kv_heads // n
    q_spec = pl.BlockSpec((None, length, n * dim), lambda b, h, g: (b, 0, h * per + g))
    kv_spec = pl.BlockSpec((None, length, dim), lambda b, h, g: (b, 0, h))
    row_spec = pl.BlockSpec((None, n, 1, length), lambda b, h, g: (b, h * per + g, 0, 0))
    return (b, kv_heads, per), q_spec, kv_spec, row_spec


def _out_shapes(inputs, *shapes_and_dtypes):
    """Inside a `shard_map` region the replication checker needs the outputs
    to vary as the inputs do (`ops/pallas_blur.py`); outside, `vma` is empty."""
    vma = frozenset().union(*(getattr(jax.typeof(a), "vma", frozenset()) for a in inputs))
    return [jax.ShapeDtypeStruct(shape, dtype, vma=vma) for shape, dtype in shapes_and_dtypes]


# jitted, as the kernels beside this one: the step calls each program four
# times or more, and an inner jit is traced and lowered to Mosaic once a
# signature where a bare `pallas_call` is lowered at every call (11 s of a warm
# start's 71: my chip run, PR 28)
@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "block_length", "interpret",
                                             "with_lse"))
def _forward(q, k, v, heads, kv_heads, block_length, interpret, with_lse):
    grid, q_spec, kv_spec, row_spec = _grid_and_specs(q, k, heads, kv_heads)
    b, length, _ = q.shape
    n_out = 2 if with_lse else 1
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dim=q.shape[2] // heads, block_length=block_length),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec][:n_out],
        out_shape=_out_shapes((q, k, v), (q.shape, q.dtype),
                              ((b, heads, 1, length), jnp.float32))[:n_out],
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, heads, kv_heads, block_length, interpret):
    return _forward(q, k, v, heads, kv_heads, block_length, interpret, with_lse=False)[0]


def _attention_fwd(q, k, v, heads, kv_heads, block_length, interpret):
    o, lse = _forward(q, k, v, heads, kv_heads, block_length, interpret, with_lse=True)
    return o, (q, k, v, o, lse)


def _attention_bwd(heads, kv_heads, block_length, interpret, residuals, do):
    return _backward(*residuals, do, heads=heads, kv_heads=kv_heads, block_length=block_length,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "block_length", "interpret"))
def _backward(q, k, v, o, lse, do, heads, kv_heads, block_length, interpret):
    grid, q_spec, kv_spec, row_spec = _grid_and_specs(q, k, heads, kv_heads)
    dim = q.shape[2] // heads
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dim=dim, block_length=block_length),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=_out_shapes((q, k, v, do), *((x.shape, x.dtype) for x in (q, k, v))),
        scratch_shapes=[pltpu.VMEM((q.shape[1], dim), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, o, do, lse)


_attention.defvjp(_attention_fwd, _attention_bwd)


def block_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, heads: int,
                           kv_heads: int, block_length: int,
                           interpret: bool = False) -> jax.Array:
    """softmax(q k^T / sqrt(D) under the block-causal mask) v.

    q `[B, L, heads * D]`, k and v `[B, L, kv_heads * D]` in one dtype, as the
    projections and `norm_rotary` leave them; query head `h` reads key/value
    head `h // (heads // kv_heads)`. Returns `[B, L, heads * D]` in that dtype.
    The shapes are those `attention_plan` sends here: `D` and `L` multiples of
    128, `block_length` a divisor of 128."""
    return _attention(q, k, v, heads, kv_heads, block_length, interpret)


# ---------------------------------------------------------------------------
# q and k on their way from the projection to the kernel above
# ---------------------------------------------------------------------------


def _rotary_tables(length: int, dim: int, theta: float):
    """`cos` and `sin` of `models/sdar.py::rotary`'s angles as `[L, D]` float32,
    the rotate-half sign folded into the sine: `concat(-sin, sin)`."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1)


def _prep_kernel(y_ref, *refs, eps, norm):
    """Rows of one batch row and whole heads: y/o refs `[rows, n * D]`, the
    tables' refs `[rows, D]`, and with `norm` the scale's `[1, D]` before them.
    float32 until the one cast, to o's dtype. `norm` off leaves the per-head
    RMSNorm out: rotary and the cast alone."""
    *scale_ref, cos_ref, sin_ref, o_ref = refs
    dim = cos_ref.shape[1]
    for t in range(y_ref.shape[0] // TILE):
        rows = slice(t * TILE, (t + 1) * TILE)
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        for j in range(y_ref.shape[1] // dim):
            head = slice(j * dim, (j + 1) * dim)
            x = y_ref[rows, head].astype(jnp.float32)
            if norm:
                x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale_ref[0][...]
            # the other half of the head is `dim // 2` lanes away, either way round
            out = x * cos + pltpu.roll(x, dim // 2, 1) * sin
            o_ref[rows, head] = out.astype(o_ref.dtype)


def _prep_bwd_kernel(*refs, eps, norm):
    """The transpose of `_prep_kernel` at the same blocks. With `norm`: refs y,
    g, scale, the tables, dx and dscale `[8, D]` (this program's rows summed
    eight apart, the rest of the sum is XLA's); `r` is computed again from y.
    Without: g, the tables and dx, since rotary is linear and keeps nothing."""
    if norm:
        y_ref, g_ref, scale_ref, cos_ref, sin_ref, dx_ref, dscale_ref = refs
        scale = scale_ref[...]
        dscale = jnp.zeros(dscale_ref.shape, jnp.float32)
    else:
        g_ref, cos_ref, sin_ref, dx_ref = refs
    dim = cos_ref.shape[1]
    for t in range(g_ref.shape[0] // TILE):
        rows = slice(t * TILE, (t + 1) * TILE)
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        for j in range(g_ref.shape[1] // dim):
            head = slice(j * dim, (j + 1) * dim)
            g = g_ref[rows, head].astype(jnp.float32)
            # a roll by half the lanes is its own transpose
            dx = g * cos + pltpu.roll(g * sin, dim // 2, 1)
            if norm:
                x = y_ref[rows, head].astype(jnp.float32)
                r = lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                u = x * r
                dscale = dscale + jnp.sum((dx * u).reshape(-1, *dscale.shape), 0)
                gx = dx * scale
                dx = r * (gx - u * (r * jnp.mean(gx * x, -1, keepdims=True)))
            dx_ref[rows, head] = dx.astype(dx_ref.dtype)
    if norm:
        dscale_ref[...] = dscale


def _prep_grid_and_specs(y, dim):
    """The grid `(batch, row blocks, head groups)`, the block specs of y and of
    a table, and the shape and spec of the scale's partial sums."""
    b, length, width = y.shape
    rows = math.gcd(length, PREP_ROWS)
    n = math.gcd(width // dim, HEADS_PER_PROGRAM)
    grid = (b, length // rows, width // (n * dim))
    y_spec = pl.BlockSpec((None, rows, n * dim), lambda b, r, h: (b, r, h))
    table_spec = pl.BlockSpec((rows, dim), lambda b, r, h: (r, 0))
    scale_spec = pl.BlockSpec((1, dim), lambda b, r, h: (0, 0))
    sums_spec = pl.BlockSpec((None, None, None, 8, dim), lambda b, r, h: (b, r, h, 0, 0))
    return grid, y_spec, table_spec, scale_spec, (*grid, 8, dim), sums_spec


@functools.partial(jax.jit, static_argnames=("dim", "dtype", "theta", "eps", "interpret"))
def _prep_forward(y, scale, dim, dtype, theta, eps, interpret):
    norm = scale is not None
    grid, y_spec, table_spec, scale_spec, _, _ = _prep_grid_and_specs(y, dim)
    scales = [scale[None, :]] if norm else []
    return pl.pallas_call(
        functools.partial(_prep_kernel, eps=eps, norm=norm),
        grid=grid,
        in_specs=[y_spec] + [scale_spec] * norm + [table_spec, table_spec],
        out_specs=y_spec,
        out_shape=_out_shapes((y, *scales), (y.shape, dtype))[0],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="qk_norm_rotary" if norm else "qk_rotary",
    )(y, *scales, *_rotary_tables(y.shape[1], dim, theta))


@functools.partial(jax.jit, static_argnames=("dim", "theta", "eps", "interpret"))
def _prep_backward(y, g, scale, dim, theta, eps, interpret):
    """`y` and `scale` are `None` where there is no norm: the cotangent alone."""
    norm = scale is not None
    grid, y_spec, table_spec, scale_spec, sums_shape, sums_spec = _prep_grid_and_specs(g, dim)
    operands = [y, g, scale[None, :]] if norm else [g]
    out = pl.pallas_call(
        functools.partial(_prep_bwd_kernel, eps=eps, norm=norm),
        grid=grid,
        in_specs=([y_spec, y_spec, scale_spec] if norm else [y_spec]) + [table_spec, table_spec],
        out_specs=[y_spec, sums_spec][: 1 + norm],
        out_shape=_out_shapes(operands, (g.shape, g.dtype), (sums_shape, jnp.float32))[: 1 + norm],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="qk_norm_rotary_bwd" if norm else "qk_rotary_bwd",
    )(*operands, *_rotary_tables(g.shape[1], dim, theta))
    # dx leaves the kernel in the cotangent's dtype, as the transpose of the
    # XLA path's `astype` would round it on its way into the projection
    return out[0], (jnp.sum(out[1], (0, 1, 2, 3)) if norm else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _norm_rotary(y, scale, dim, dtype, theta, eps, interpret):
    return _prep_forward(y, scale, dim, dtype, theta, eps, interpret)


def _norm_rotary_fwd(y, scale, dim, dtype, theta, eps, interpret):
    out = _prep_forward(y, scale, dim, dtype, theta, eps, interpret)
    # rotary alone is linear: its transpose needs y's dtype and nothing of y
    return out, ((y, scale) if scale is not None else (jnp.zeros((0,), y.dtype), None))


def _norm_rotary_bwd(dim, dtype, theta, eps, interpret, residuals, g):
    y, scale = residuals
    dx, dscale = _prep_backward(y if scale is not None else None, g, scale, dim, theta, eps,
                                interpret)
    return dx.astype(y.dtype), dscale


_norm_rotary.defvjp(_norm_rotary_fwd, _norm_rotary_bwd)


def norm_rotary(y: jax.Array, scale: jax.Array | None, *, dtype, theta: float, eps: float = 0.0,
                head_dim: int = 0, interpret: bool = False) -> jax.Array:
    """`rotary(RMSNorm(eps)(y per head), theta).astype(dtype)` of
    `models/sdar.py` on the projection's own output `[B, L, heads * D]`, heads
    of `D = scale.shape[0]` lanes, positions 0..L-1: what q and k pass through
    between their `Dense` and `block_causal_attention`. One read of y and one
    write; the backward pass keeps y alone and reads it once more with the
    cotangent. `scale` is the norm's `[D]` float32 parameter and gets its
    gradient. `scale=None` is an encoder without the per-head norm
    (`models/ouro.py`): `rotary(y, theta).astype(dtype)` over heads of
    `head_dim` lanes, the same kernel body with the norm switched off; its
    backward pass keeps nothing.

    y comes as `RMSNorm` takes it, cast to float32: XLA's fusion of the
    projection with that cast hands over the product's float32 accumulator
    and not its rounding to `dtype` (my chip run, PR 30: the layer's output
    and gradients read 8 - 14 % further from a float32 oracle with y in
    bfloat16), and the cotangent of the cast is the rounding that the XLA
    path's backward pass has there too."""
    dim = int(scale.shape[0]) if scale is not None else int(head_dim)
    return _norm_rotary(y, scale, dim, jnp.dtype(dtype), float(theta), float(eps), interpret)


# ---------------------------------------------------------------------------
# views longer than a VMEM row of scores, and masks that are data: the tiled pair
# ---------------------------------------------------------------------------
#
# At 8 192 tokens a row of float32 scores of one head is 32 KB and a q tile's is
# 8 MB: the keys come by chunks of `KEY_CHUNK` on the innermost grid axis, and a
# forward program keeps the running maximum, the running sum and the unnormalised
# mix of its `FWD_Q_TILE` rows in VMEM between them (online softmax). The two row
# statistics are held as `[rows, 128]` with a row's value on every lane, the
# layout of the scores and of a head's mix: as `[rows, 1]` columns every use
# relays them across the lanes, and the kernel takes 15.9 ms a call for 9.0 at
# the long-document cell's shapes on a TPU v5e (37 % of the MXU's peak on the
# pairs it computes for 66 %; the same bits out). WHICH pairs are live
# is an operand: `live`, int8 `[B or 1, L, L]`, nonzero where query `t` sees key
# `s`, zero above the diagonal (the caller's: a selection that is per batch row,
# or a mask of positions that every row shares). The kernels know of the
# diagonal only that a key chunk wholly above it holds no live pair: those grid
# steps do nothing, and their blocks' index maps repeat the step before, so
# nothing is fetched for them. Every chunk that reaches under the diagonal is
# computed whole, whatever `live` holds there.
#
# The arithmetic is the whole-row kernel's but for the order that online softmax
# forces: the weights meet v as `exp(s - m)` rounded to `dtype`, with `m` the
# maximum SO FAR, and the mix is divided by the sum at the end (there, the
# weights are whole before they are rounded). The backward pass is one kernel on
# the same grid: scores `[keys, queries]` as `_bwd_kernel`, dq summed over a q
# tile's chunks in VMEM, dk and dv over a key/value head's q tiles and query
# heads in a float32 `[L, D]` scratch each.
#
# The differentiated call's forward rule hands `o` and the log-sum-exp on BY NAME
# (`_masked_fwd`): a rematerialised caller whose policy keeps those names runs
# one backward kernel, where a plain `jax.checkpoint` runs the forward kernel a
# second time to have the two again. The whole-row pair above names nothing.

# The forward's q tile is twice the backward's (`Q_TILE`): a program of 512 rows
# takes 8.14 ms a call where one of 256 takes 9.00 (the long-document cell's
# shapes on a TPU v5e), and computes the same pairs, its tile on the diagonal
# being one key chunk. Its three float32 scratches of 2 MB and its q and o
# blocks of 1 MB, twice for the pipeline, beside a head's `[512, 512]` float32
# scores, are 19.3 MB at 8 192 tokens by the TPU compiler, over the 16 MB it
# allows by default.
FWD_Q_TILE = 512
_TILED_FWD_VMEM = 32 * 2 ** 20


def _tiling(length: int, q_tile: int = Q_TILE) -> tuple[int, int]:
    """Rows of a q tile and keys of a chunk for a view of `length` tokens."""
    return min(q_tile, length), min(KEY_CHUNK, length)


def _last_chunk(t, tq: int, tk: int):
    """The last key chunk that reaches under the diagonal of q tile `t`."""
    return ((t + 1) * tq - 1) // tk


def _bias(live_ref):
    """0 where the pair is live, `MASKED` elsewhere: float32, a q tile's rows by a chunk's keys."""
    return jnp.where(live_ref[...].astype(jnp.int32) != 0, 0.0, MASKED)


def _lane_row(column):
    """A column of row statistics `[n, 1]` as the lane row `[1, n]`."""
    n = column.shape[0]
    return jnp.broadcast_to(column, (n, n)).T[:1, :]


def _lanes(rows, width: int):
    """Row statistics held on 128 lanes `[r, 128]` as `[r, width]`, `width` a multiple of 128."""
    return rows if rows.shape[1] == width else jnp.tile(rows, (1, width // rows.shape[1]))


def _tiled_fwd_kernel(q_ref, k_ref, v_ref, live_ref, o_ref, *rest, dim):
    """One batch row, one key/value head, `n` query heads of its group side by
    side on the lanes, one q tile, one key chunk: q/o refs `[FWD_Q_TILE, n * D]`,
    k/v refs `[KEY_CHUNK, D]`, live `[FWD_Q_TILE, KEY_CHUNK]`, lse
    `[n, 1, FWD_Q_TILE]` (a differentiated call's), then the scratch: the running
    maximum and sum `[n, FWD_Q_TILE, 128]`, a row's value on every lane, and the
    mix `[FWD_Q_TILE, n * D]`, float32."""
    *lse_ref, m_scr, l_scr, acc_scr = rest
    t, c = pl.program_id(3), pl.program_id(4)
    tk = live_ref.shape[1]
    last = _last_chunk(t, *live_ref.shape)
    scale = 1.0 / math.sqrt(dim)
    heads = [slice(j * dim, (j + 1) * dim) for j in range(q_ref.shape[1] // dim)]

    @pl.when(c == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, MASKED, jnp.float32)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(c <= last)
    def _():
        bias = _bias(live_ref)
        k, v = k_ref[...], v_ref[...]
        for j, head in enumerate(heads):
            s = _dot(q_ref[:, head], k, _NT) * scale + bias
            m_old = m_scr[j]
            m = jnp.maximum(m_old, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - _lanes(m, tk))
            alpha = jnp.exp(m_old - m)
            l_scr[j] = alpha * l_scr[j] + jnp.sum(p, -1, keepdims=True)
            acc_scr[:, head] = _lanes(alpha, dim) * acc_scr[:, head] + _dot(p.astype(v.dtype), v)
            m_scr[j] = m

    @pl.when(c == last)
    def _():
        for j, head in enumerate(heads):
            total = l_scr[j]
            o_ref[:, head] = (acc_scr[:, head] * _lanes(1.0 / total, dim)).astype(o_ref.dtype)
            if lse_ref:
                # every lane holds the row's value: a transpose makes the lane row
                lse_ref[0][j] = (m_scr[j] + jnp.log(total)).T[:1, :]


def _tiled_bwd_kernel(q_ref, k_ref, v_ref, live_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, delta_scr, *, dim):
    """As `_tiled_fwd_kernel`; dk/dv refs `[L, D]` a key/value head, written once
    its last q tile of its last program is done. Scores are `[keys, queries]`."""
    g, t, c = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    tk = live_ref.shape[1]
    last = _last_chunk(t, *live_ref.shape)
    scale = 1.0 / math.sqrt(dim)
    heads = [slice(j * dim, (j + 1) * dim) for j in range(q_ref.shape[1] // dim)]

    @pl.when((g == 0) & (t == 0) & (c == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(c == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for j, head in enumerate(heads):
            delta = jnp.sum(o_ref[:, head].astype(jnp.float32)
                            * do_ref[:, head].astype(jnp.float32), -1, keepdims=True)
            delta_scr[j] = _lane_row(delta)

    @pl.when(c <= last)
    def _():
        bias = _bias(live_ref).T
        k, v = k_ref[...], v_ref[...]
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        for j, head in enumerate(heads):
            q, do = q_ref[:, head], do_ref[:, head]
            p = jnp.exp(_dot(k, q, _NT) * scale + bias - lse_ref[j])
            ds = (p * (_dot(v, do, _NT) - delta_scr[j])).astype(q.dtype)
            dv = dv + _dot(p.astype(do.dtype), do)
            dk = dk + _dot(ds, q)
            dq_acc[:, head] += _dot(ds.T, k)
        keys = pl.ds(pl.multiple_of(c * tk, tk), tk)
        dk_acc[keys, :] += dk
        dv_acc[keys, :] += dv

    @pl.when(c == last)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when((g == pl.num_programs(2) - 1) & (t == pl.num_programs(3) - 1) & (c == last))
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _tiled_grid_and_specs(q, live, heads, kv_heads, q_tile):
    """The grid `(batch, kv_head, programs a group, q tiles, key chunks)` and its
    block specs. A chunk above the diagonal repeats the block before it."""
    b, length, _ = q.shape
    dim = q.shape[2] // heads
    n = math.gcd(heads // kv_heads, HEADS_PER_PROGRAM)
    per = heads // kv_heads // n
    tq, tk = _tiling(length, q_tile)
    shared = live.shape[0] == 1       # one mask for every batch row

    def chunk(t, c):
        return jnp.minimum(c, _last_chunk(t, tq, tk))

    q_spec = pl.BlockSpec((None, tq, n * dim), lambda b, h, g, t, c: (b, t, h * per + g))
    kv_spec = pl.BlockSpec((None, tk, dim), lambda b, h, g, t, c: (b, chunk(t, c), h))
    live_spec = pl.BlockSpec((None, tq, tk),
                             lambda b, h, g, t, c: (0 if shared else b, t, chunk(t, c)))
    row_spec = pl.BlockSpec((None, n, 1, tq), lambda b, h, g, t, c: (b, h * per + g, 0, t))
    whole_spec = pl.BlockSpec((None, length, dim), lambda b, h, g, t, c: (b, 0, h))
    grid = (b, kv_heads, per, length // tq, length // tk)
    return grid, (n, tq), q_spec, kv_spec, live_spec, row_spec, whole_spec


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "interpret", "with_lse"))
def _tiled_forward(q, k, v, live, heads, kv_heads, interpret, with_lse):
    grid, (n, tq), q_spec, kv_spec, live_spec, row_spec, _ = _tiled_grid_and_specs(
        q, live, heads, kv_heads, FWD_Q_TILE)
    b, length, _ = q.shape
    dim = q.shape[2] // heads
    n_out = 2 if with_lse else 1
    return pl.pallas_call(
        functools.partial(_tiled_fwd_kernel, dim=dim),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, live_spec],
        out_specs=[q_spec, row_spec][:n_out],
        out_shape=_out_shapes((q, k, v, live), (q.shape, q.dtype),
                              ((b, heads, 1, length), jnp.float32))[:n_out],
        scratch_shapes=[pltpu.VMEM((n, tq, TILE), jnp.float32)] * 2
        + [pltpu.VMEM((tq, n * dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_TILED_FWD_VMEM),
        interpret=interpret,
        name="masked_attention_fwd",
    )(q, k, v, live)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "interpret"))
def _tiled_backward(q, k, v, live, o, lse, do, heads, kv_heads, interpret):
    grid, (n, tq), q_spec, kv_spec, live_spec, row_spec, whole_spec = _tiled_grid_and_specs(
        q, live, heads, kv_heads, Q_TILE)
    length, dim = q.shape[1], q.shape[2] // heads
    return pl.pallas_call(
        functools.partial(_tiled_bwd_kernel, dim=dim),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, live_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, whole_spec, whole_spec],
        out_shape=_out_shapes((q, k, v, live, do), *((x.shape, x.dtype) for x in (q, k, v))),
        scratch_shapes=[pltpu.VMEM((tq, n * dim), jnp.float32),
                        pltpu.VMEM((length, dim), jnp.float32),
                        pltpu.VMEM((length, dim), jnp.float32),
                        pltpu.VMEM((n, 1, tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_TILED_BWD_VMEM),
        interpret=interpret,
        name="masked_attention_bwd",
    )(q, k, v, live, o, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked(q, k, v, live, heads, kv_heads, interpret):
    return _tiled_forward(q, k, v, live, heads, kv_heads, interpret, with_lse=False)[0]


# What the backward kernel reads beyond q, k and v, by the names under which a
# rematerialised caller's policy (`save_only_these_names`; `models/sdar.py::KEPT`
# has the rule) can keep it: the forward kernel's two results, named below, and
# the operand `live`, named by the caller that made it (`models/keye.py::Indexer`).
# `checkpoint_name` is the identity to every caller without such a policy.
KEPT_OUT, KEPT_LSE, KEPT_LIVE = ("masked_attention_o", "masked_attention_lse",
                                 "masked_attention_live")


def _masked_fwd(q, k, v, live, heads, kv_heads, interpret):
    from jax.ad_checkpoint import checkpoint_name   # no attribute of `jax`

    o, lse = _tiled_forward(q, k, v, live, heads, kv_heads, interpret, with_lse=True)
    o, lse = checkpoint_name(o, KEPT_OUT), checkpoint_name(lse, KEPT_LSE)
    return o, (q, k, v, live, o, lse)


def _masked_bwd(heads, kv_heads, interpret, residuals, do):
    dq, dk, dv = _tiled_backward(*residuals, do, heads=heads, kv_heads=kv_heads,
                                 interpret=interpret)
    return dq, dk, dv, None     # which pairs are live is no function of a float


_masked.defvjp(_masked_fwd, _masked_bwd)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array, live: jax.Array, *, heads: int,
                     kv_heads: int, interpret: bool = False) -> jax.Array:
    """softmax(q k^T / sqrt(D) over the live pairs) v, for views of any number of
    `KEY_CHUNK`s: the tiled pair above.

    q, k, v and the result as `block_causal_attention`'s. `live` is int8
    `[B, L, L]`, or `[1, L, L]` for a mask that every batch row shares: nonzero
    where query `t` sees key `s`. It must be zero above the diagonal (a chunk
    wholly above it is never read) and every query must see a key. No gradient
    passes into it."""
    return _masked(q, k, v, live, heads, kv_heads, interpret)
