"""Pallas TPU kernels: a sparse-attention indexer's scores and its exact top-k
selection, for views whose `[L, L]` scores a batch row are too many for XLA to
sort (`models/keye.py`; at 8 192 tokens, 67 M scores a batch row and layer).

`index_scores`: `I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])` over the
indexer's heads `j`, one key head shared by all: `dtype` operands, float32
accumulation, float32 `relu`, weights and sum (heads summed in order), written
once as float32 `[B, L, L]`. The per-head products `[L, heads, L]` (4.3 GB a
batch row at the published sizes) stay in VMEM, a `Q_ROWS x KEY_COLS` tile at a
time; a tile wholly above the diagonal is written as zeros and costs no product.
A head's `dim` numbers come zero-padded to a whole lane tile (the caller's pad:
a product with zeros adds nothing), so a head is `TILE` lanes of q's last axis,
as in `ops/pallas_attention.py`.

`select_top_k`: for every query `t`, WHICH `min(topk, t + 1)` of its causal keys
`s <= t` score highest, equal scores to the lower `s` (what `lax.top_k` returns),
as int8 `[B, L, L]`: 1 on a selected pair, 0 elsewhere and above the diagonal.
No sort. A program holds `ROWS` queries' scores as integers that order as the
floats do (`_ordered`) and finds each row's `topk`-th largest by bisection on
the integer's 32 bits: a pass over the row's keys per bit counts the keys not
under the candidate. A row's threshold, candidate, room and position are held
`[ROWS, TILE]`, its value on every lane: the layout of a key chunk's lane
tiles, which are compared with them as they lie, and a pass adds its hits into
lane partial sums that it reduces across the lanes once, after its last chunk.
As `[ROWS, 1]` columns every chunk of every pass paid a reduction across its
lanes and a broadcast of the threshold back over them: 4.67 ms a call for 2.35
at the long-document cell's shapes on a TPU v5e, the same selection. Where
rows of the block hold more keys equal to their threshold than the selection
has room for, 13 more passes find the position up to which the equal ones are
taken. Both counts run over the key chunks that reach under the block's
diagonal only, and a block whose queries all have `t < topk` skips the search:
every causal key is selected there. The result is exact: the same pairs as a
stable descending sort of each row.

Nothing here has a gradient: a selection is no function of a float. Outputs
carry the inputs' `vma`; `interpret=True` runs the same code on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from moco_tpu.ops.pallas_attention import _NT, TILE, _dot, _out_shapes

# the scores' tiles: query rows and key columns a grid step
Q_ROWS, KEY_COLS = 256, 512
# queries a program of the selection holds, whole rows of scores: 4 MB at 8 192
ROWS = 128
# keys a pass of the bisection counts at once
CHUNK = 512
INT_MIN = -2 ** 31
_SELECT_VMEM = 40 * 2 ** 20    # the scores' block twice, their integers once, the result twice


def select_plan(length: int, topk: int, index_dim: int, backend: str | None = None) -> str:
    """`kernels` where the scores and the selection take the kernels above: a
    TPU, views of whole key columns, heads that pad to a lane tile. `xla`
    elsewhere: the einsum and `lax.top_k` of `models/keye.py`, their oracle."""
    fits = ((backend or jax.default_backend()) == "tpu" and length % KEY_COLS == 0
            and length % CHUNK == 0 and 0 < index_dim <= TILE and topk > 0)
    return "kernels" if fits else "xla"


# -- scores ---------------------------------------------------------------------


def _index_kernel(q_ref, k_ref, w_ref, o_ref, *, heads):
    """q ref `[Q_ROWS, heads * TILE]`, k ref `[KEY_COLS, TILE]`, w ref
    `[Q_ROWS, heads]` float32, o ref `[Q_ROWS, KEY_COLS]` float32."""
    t, c = pl.program_id(1), pl.program_id(2)
    under = c * KEY_COLS < (t + 1) * Q_ROWS

    @pl.when(under)
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            s = _dot(q_ref[:, j * TILE:(j + 1) * TILE], k, _NT)
            acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
        o_ref[...] = acc

    @pl.when(jnp.logical_not(under))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores(q, k, w, interpret):
    b, length, heads = w.shape

    def chunk(t, c):     # a tile above the diagonal repeats the key block before it
        return jnp.minimum(c, ((t + 1) * Q_ROWS - 1) // KEY_COLS)

    return pl.pallas_call(
        functools.partial(_index_kernel, heads=heads),
        grid=(b, length // Q_ROWS, length // KEY_COLS),
        in_specs=[pl.BlockSpec((None, Q_ROWS, heads * TILE), lambda b, t, c: (b, t, 0)),
                  pl.BlockSpec((None, KEY_COLS, TILE), lambda b, t, c: (b, chunk(t, c), 0)),
                  pl.BlockSpec((None, Q_ROWS, heads), lambda b, t, c: (b, t, 0))],
        out_specs=pl.BlockSpec((None, Q_ROWS, KEY_COLS), lambda b, t, c: (b, t, c)),
        out_shape=_out_shapes((q, k, w), ((b, length, length), jnp.float32))[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="index_scores",
    )(q, k, w)


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array, *, interpret: bool = False) -> jax.Array:
    """q `[B, L, heads, dim]` and k `[B, L, dim]` in one dtype, w `[B, L, heads]`
    float32 -> float32 `[B, L, L]`; a tile wholly above the diagonal is zeros, and
    what else lies above it is never meant to be read."""
    b, length, heads, dim = q.shape
    pad = TILE - dim
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad))).reshape(b, length, heads * TILE)
    return _index_scores(q, jnp.pad(k, ((0, 0), (0, 0), (0, pad))), w.astype(jnp.float32),
                         interpret)


# -- selection ------------------------------------------------------------------


def _ordered(scores):
    """float32 -> int32 that orders as the floats do; -0.0 as 0.0."""
    bits = pltpu.bitcast(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jnp.where(bits == INT_MIN, 0, key)


def _count(key_ref, pred, lo, hi):
    """Keys a row in chunks `lo` to `hi` (excluded) of `key_ref` `[chunks, rows,
    CHUNK]` int32 for which `pred(keys, positions)` holds, as float32 `[rows,
    TILE]` (whole numbers, exact), a row's count on every lane. A chunk is
    compared as its lane tiles, each against thresholds held `[rows, TILE]`, and
    the hits are added into lane partial sums: one reduction across the lanes,
    after the last chunk. Two chunks a loop iteration: 2.35 ms a select call for
    2.42 with one, on the chip as above."""
    rows = key_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (rows, TILE), 1)

    def body(c, part):
        for j in range(0, CHUNK, TILE):
            hit = pred(key_ref[c, :, j:j + TILE], c * CHUNK + j + lane)
            part = part + jnp.where(hit, 1.0, 0.0)
        return part

    part = lax.fori_loop(0, (hi - lo) // 2, lambda i, p: body(lo + 2 * i + 1, body(lo + 2 * i, p)),
                         jnp.zeros((rows, TILE), jnp.float32))
    part = lax.cond((hi - lo) % 2 == 1, lambda p: body(hi - 1, p), lambda p: p, part)
    return jnp.broadcast_to(jnp.sum(part, -1, keepdims=True), part.shape)


def _select_kernel(s_ref, o_ref, key_scr, *, topk):
    """s ref `[ROWS, L]` float32, o ref `[ROWS, L]` int8, the scratch
    `[L / CHUNK, ROWS, CHUNK]` int32: the rows' keys, chunk by chunk."""
    rows, length = s_ref.shape
    chunks = length // CHUNK
    first = pl.program_id(1) * rows
    row = first + lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 1)
    under = (first + rows - 1) // CHUNK + 1     # chunks that reach under the diagonal
    bits = (length - 1).bit_length()            # of a key's position

    for c in range(chunks):
        key = _ordered(s_ref[:, c * CHUNK:(c + 1) * CHUNK])
        key_scr[c] = jnp.where(c * CHUNK + lane <= row, key, INT_MIN)

    def count(pred):
        return _count(key_scr, pred, 0, under)

    # a row's threshold, room and position are `[ROWS, TILE]`, its value on every lane
    def search():
        # the largest integer that `topk` keys or more are not under: the
        # `topk`-th largest key, bit by bit from the sign down
        thr = jnp.where(count(lambda key, _: key >= 0) >= topk, 0, INT_MIN)

        def bit(i, thr):
            cand = thr + jnp.left_shift(jnp.int32(1), 30 - i)
            return jnp.where(count(lambda key, _: key >= cand) >= topk, cand, thr)

        thr = lax.fori_loop(0, 31, bit, thr)
        # the keys equal to it share what the larger ones leave, lowest first: the
        # last position taken is the largest with fewer than `room` equals before it
        room = topk - count(lambda key, _: key > thr)

        def pos_bit(i, pos):
            cand = pos + jnp.left_shift(jnp.int32(1), bits - 1 - i)
            before = count(lambda key, at: (key == thr) & (at < cand))
            return jnp.where(before < room, cand, pos)

        def tie_break():
            return lax.fori_loop(0, bits, pos_bit, jnp.zeros((rows, TILE), jnp.int32))

        # no row of the block with more equals than room (nearly every block):
        # every key not under the threshold is taken, wherever it stands
        crowded = jnp.max(count(lambda key, _: key >= thr)) > topk
        pos = lax.cond(crowded, tie_break, lambda: jnp.full((rows, TILE), length, jnp.int32))
        return thr, pos

    def everything():
        return jnp.full((rows, TILE), INT_MIN, jnp.int32), jnp.full((rows, TILE), length, jnp.int32)

    # a block of queries that all have `t < topk` selects every causal key
    thr, pos = lax.cond(first + rows > topk, search, everything)
    thr, pos = thr[:, :1], pos[:, :1]
    for c in range(chunks):
        key, at = key_scr[c], c * CHUNK + lane
        taken = ((key > thr) | ((key == thr) & (at <= pos))) & (at <= row)
        o_ref[:, c * CHUNK:(c + 1) * CHUNK] = jnp.where(taken, 1, 0).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select(scores, topk, interpret):
    b, length, _ = scores.shape
    spec = pl.BlockSpec((None, ROWS, length), lambda b, r: (b, r, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(b, length // ROWS),
        in_specs=[spec],
        out_specs=spec,
        out_shape=_out_shapes((scores,), (scores.shape, jnp.int8))[0],
        scratch_shapes=[pltpu.VMEM((length // CHUNK, ROWS, CHUNK), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=_SELECT_VMEM),
        interpret=interpret,
        name="select_top_k",
    )(scores)


def select_top_k(scores: jax.Array, topk: int, *, interpret: bool = False) -> jax.Array:
    """float32 `[B, L, L]` scores -> int8 `[B, L, L]`, 1 where query `t` selects
    key `s`: its `min(topk, t + 1)` highest among `s <= t`, ties to the lower
    `s`. What `scores` holds above the diagonal is never looked at."""
    return _select(scores, int(topk), interpret)
