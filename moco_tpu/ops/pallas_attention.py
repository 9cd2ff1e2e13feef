"""Pallas TPU kernel: block-causal grouped-query attention with the scores in VMEM.

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
`delta = sum(o * do)` are then lane rows, and two of the three transposed
products need no transpose), and forms dv, dp, ds = p (dp - delta), dq and dk
in VMEM; dk and dv accumulate in float32 over the `group` query heads that
share a key/value head. ds meets k and q as `dtype`, which is what the TPU's
default precision makes of the einsum path's float32 ds.

A head is `head_dim` lanes of the projections' own last axis
(`[B, L, heads * head_dim]`), so nothing is transposed on the way in or out.
Outputs carry the inputs' `vma`: the call type-checks inside a `shard_map`
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
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def attention_plan(length: int, head_dim: int, block_length: int,
                   backend: str | None = None) -> dict:
    """Which path attention takes for these shapes, and how many `TILE` x
    `TILE` score tiles it computes and skips: the `attn` block of the `setup`
    event. The kernel needs a TPU, whole lane tiles for a head, whole q tiles,
    and blocks that do not straddle a tile."""
    side = -(-length // TILE)
    fused = ((backend or jax.default_backend()) == "tpu" and head_dim % TILE == 0
             and length % TILE == 0 and TILE % block_length == 0)
    return {"path": "fused" if fused else "einsum", "tiles": side * side,
            "tiles_skipped": side * (side - 1) // 2 if fused else 0}


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


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
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
            lse, delta = lse_ref[j, :, rows], delta_ref[j, :, rows]
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


def _flat(x):
    """`[B, L, heads, D]` -> `[B, L, heads * D]`: a head is `D` lanes of the
    projections' own last axis."""
    return x.reshape(*x.shape[:2], -1)


def _grid_and_specs(q, k):
    """The grid `(batch, kv_head, programs a group)` and its block specs: `n`
    query heads' and a key/value head's lanes of `[B, L, heads * D]`, and the
    query heads' rows of `[B, heads, 1, L]`."""
    b, length, heads, dim = q.shape
    kv_heads = k.shape[2]
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


# jitted, as the kernels beside this one: the step calls each of the three
# programs four times or eight, and an inner jit is traced and lowered to Mosaic
# once a signature where a bare `pallas_call` is lowered at every call (11 s of
# a warm start's 71: my chip run, PR 28)
@functools.partial(jax.jit, static_argnames=("block_length", "interpret", "with_lse"))
def _forward(q, k, v, block_length, interpret, with_lse):
    grid, q_spec, kv_spec, row_spec = _grid_and_specs(q, k)
    qf, kf, vf = _flat(q), _flat(k), _flat(v)
    b, length, heads, dim = q.shape
    n_out = 2 if with_lse else 1
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, dim=dim, block_length=block_length),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec][:n_out],
        out_shape=_out_shapes((q, k, v), (qf.shape, q.dtype),
                              ((b, heads, 1, length), jnp.float32))[:n_out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(qf, kf, vf)
    return (out[0].reshape(q.shape), *out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, block_length, interpret):
    return _forward(q, k, v, block_length, interpret, with_lse=False)[0]


def _attention_fwd(q, k, v, block_length, interpret):
    o, lse = _forward(q, k, v, block_length, interpret, with_lse=True)
    return o, (q, k, v, o, lse)


def _attention_bwd(block_length, interpret, residuals, do):
    return _backward(*residuals, do, block_length=block_length, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_length", "interpret"))
def _backward(q, k, v, o, lse, do, block_length, interpret):
    grid, q_spec, kv_spec, row_spec = _grid_and_specs(q, k)
    qf, kf, vf, dof = _flat(q), _flat(k), _flat(v), _flat(do)
    delta = jnp.einsum("blhd,blhd->bhl", o.astype(jnp.float32), do.astype(jnp.float32))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, dim=q.shape[3], block_length=block_length),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=_out_shapes((q, k, v, do), *((x.shape, x.dtype) for x in (qf, kf, vf))),
        scratch_shapes=[pltpu.VMEM((k.shape[1], k.shape[3]), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta[:, :, None, :])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_attention.defvjp(_attention_fwd, _attention_bwd)


def block_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, block_length: int,
                           interpret: bool = False) -> jax.Array:
    """softmax(q k^T / sqrt(D) under the block-causal mask) v.

    q `[B, L, heads, D]`, k and v `[B, L, kv_heads, D]` in one dtype, after
    norm and rotary; query head `h` reads key/value head `h // (heads //
    kv_heads)`. Returns `[B, L, heads, D]` in that dtype. The shapes are those
    `attention_plan` sends here: `D` and `L` multiples of 128, `block_length`
    a divisor of 128.

    The barriers hold the change of layout (`[B, L, H, D]` tiled over `(H, D)`
    to `[B, L, H * D]` tiled over `(L, lanes)`) at the finished `dtype` tensors,
    one copy each way, and the cotangents' likewise. Without them XLA moves it up
    into norm and rotary's float32 operands, three copies of twice the size a
    tensor: the layer's forward and backward read 35.7 ms without and 27.5 with
    (my chip run, PR 28; the einsums 40.5)."""
    q, k, v = lax.optimization_barrier((q, k, v))
    return lax.optimization_barrier(_attention(q, k, v, block_length, interpret))
