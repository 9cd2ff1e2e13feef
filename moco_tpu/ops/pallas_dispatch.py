"""Pallas TPU kernels: the routed layer's rows on their way between the token
order `[tokens, hidden]` and the expert-sorted buffer `[n, hidden]`.

`models/sdar.py::Experts.one_pass` as plain XLA gathers `ub[token]`, masks the
rows past the last assignment, multiplies the grouped products' result by the
float32 weights into a float32 `[n, hidden]` tensor and scatter-adds that into
a zero `[tokens, hidden]`: five passes over buffer-sized tensors, the gather and
the scatter-add row by row at a tenth of the memory's speed. Here the rows move
by asynchronous copies, and the mask, the cast, the weights and the sum happen
on a tile in VMEM. Two kernels, each the other's transpose (`jax.custom_vjp`):

`dispatch`: `x[r] = cast(src[token[r]])` for the rows before `count`, zeros
after it. The row numbers sit in SMEM; a program fills a tile of `ROW_TILE`
buffer rows with one copy a row, the next tile's copies started while this
tile is masked, cast and written. Given the weights and `y`'s tile the same
kernel is `combine`'s transpose: `dy[r] = cast(w[r] * dout[token[r]])` and
`dw[r] = sum_h dout[token[r], h] * f32(y[r, h])` in one visit of the row.

`combine`: `out[t] = sum over the rows r with token[r] = t of w[r] * f32(y[r])`
in float32, written once: no zero tensor first, no float32 `[n, hidden]`
product in memory. The chip's copies cannot add, so it is a gather in token
order: `listing` sorts the pass's rows by token (one sort of `n` keys), a
program sums one `CHUNK` of that list into a block of `TOKEN_TILE` tokens, and
the sum is a product on the otherwise idle MXU: `where(token == t, w, 0)`
`[TOKEN_TILE, CHUNK]` against the chunk's rows `[CHUNK, hidden]`, float32
accumulation. A bfloat16 `y` meets the float32 weights split into three
bfloat16 terms (w = w1 + w2 + w3 to the last bit, every product exact in
float32); a float32 `y` meets them at `highest`. A chunk that straddles two
blocks of tokens is visited once for each. The grid is over VISITS, a static
`n / CHUNK + tokens / TOKEN_TILE` of them whatever the router sent (the ones
a routing leaves over add zeros to the last block): a call's work does not
follow the router's luck. With weights of one it is `dispatch`'s transpose.

A copy moves whole tiles, so a row is seen as `[hidden / 128, 128]`: the
token-ordered side of `dispatch` and the buffer side of `combine` are
`[rows, hidden / 128, 128]` views made by XLA outside the kernels; the tile is
brought to `[rows, hidden]` in VMEM.

Outputs carry the inputs' `vma` (`ops/pallas_attention.py`). `interpret=True`
runs the same code on the CPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from moco_tpu.ops.pallas_attention import _out_shapes

LANES = 128
# what the chip read at the cell's shapes (my chip runs, PR 32; `PERF.md` section 6)
ROW_TILE = 128      # buffer rows a program of `dispatch` fills: 256 read 12 % slower weighted
CHUNK = 128         # entries of the by-token list a visit of `combine` sums: 64 and 256 read slower
TOKEN_TILE = 128    # tokens an output block of `combine` holds: 64 and 256 read slower
GROUP = 16          # copies started in one turn of the loop that starts a tile's
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def dispatch_plan(tokens: int, hidden: int, n: int, backend: str | None = None) -> str:
    """`kernels` where a pass of `n` buffer rows over `tokens` rows of `hidden`
    can take the two kernels, `xla` elsewhere. They need a TPU, whole lane
    tiles for a row, whole tiles of buffer rows and whole blocks of tokens."""
    ok = ((backend or jax.default_backend()) == "tpu" and hidden % LANES == 0
          and n % ROW_TILE == 0 and tokens % TOKEN_TILE == 0)
    return "kernels" if ok else "xla"


class Listing(NamedTuple):
    """A pass's rows by token, for `combine`: see `listing`."""

    rows: jax.Array     # [n] int32: buffer rows sorted by token, the unassigned last
    tok: jax.Array      # [n / CHUNK, CHUNK] int32: their tokens (`tokens` for the unassigned)
    w: jax.Array        # [n / CHUNK, CHUNK] float32: their weights, no gradient
    tile: jax.Array     # [visits] int32: the block of tokens a visit adds to
    chunk: jax.Array    # [visits] int32: the chunk of the list it reads
    base: jax.Array     # [visits] int32: the block's first token; negative: adds nothing
    first: jax.Array    # [visits] int32: 1 where the visit is its block's first


# jitted for the same reason as the kernels below: its three dozen small
# operations are traced at 36 call sites of the step, 3.4 s of each lowering
# (the step lowers twice a run: my CPU runs, PR 32) where an inner jit traces
# them once a signature
@functools.partial(jax.jit, static_argnames=("tokens",))
def listing(token: jax.Array, count: jax.Array, w: jax.Array, tokens: int) -> Listing:
    """The rows `0 .. n` of a pass sorted by `token` (those from `count` on
    last), and the static sequence of visits that walks that list block of
    tokens by block of tokens. Index work on `n` and `tokens / TOKEN_TILE`
    integers, all XLA's."""
    n, tiles, chunks = token.shape[0], tokens // TOKEN_TILE, token.shape[0] // CHUNK
    row = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(row < count, token.astype(jnp.int32), tokens)
    weights = lax.stop_gradient(w).astype(jnp.float32).reshape(n)
    bits = (n - 1).bit_length()
    if (tokens + 1) << bits <= 1 << 32:
        # token and row in one word: a sort of one key and one payload where
        # three operands cost a third more (0.38 ms for 0.49 at the cell's n)
        packed, w_by = lax.sort((key.astype(jnp.uint32) << bits | row.astype(jnp.uint32), weights),
                                num_keys=1)
        tok, rows = (packed >> bits).astype(jnp.int32), (packed & (1 << bits) - 1).astype(jnp.int32)
    else:
        tok, rows, w_by = lax.sort((key, row, weights), num_keys=1, is_stable=True)
    # where each block's entries start in the list; the chunks they lie in
    edge = jnp.sum(tok[None, :] < jnp.arange(tiles + 1, dtype=jnp.int32)[:, None] * TOKEN_TILE,
                   -1, dtype=jnp.int32)
    c_lo = jnp.minimum(edge[:-1] // CHUNK, chunks - 1)
    c_hi = jnp.maximum(c_lo, (edge[1:] - 1) // CHUNK)
    k = c_hi - c_lo + 1                                  # a block with no entry: one visit
    start = jnp.cumsum(k) - k
    visit = jnp.arange(chunks + tiles, dtype=jnp.int32)
    live = visit < start[-1] + k[-1]
    tile = jnp.clip(jnp.sum(start[None, :] <= visit[:, None], -1, dtype=jnp.int32) - 1, 0, tiles - 1)
    chunk = jnp.where(live, c_lo[tile] + visit - start[tile], chunks - 1)
    first = (live & (visit == start[tile])).astype(jnp.int32)
    tile = jnp.where(live, tile, tiles - 1)
    base = jnp.where(live, tile * TOKEN_TILE, -TOKEN_TILE)
    return Listing(rows, tok.reshape(chunks, CHUNK), w_by.reshape(chunks, CHUNK), tile, chunk,
                   base, first)


def _tokens_of(lst: Listing) -> int:
    """The token count a listing was made for: its visits are `n / CHUNK +
    tokens / TOKEN_TILE`."""
    return (lst.tile.shape[0] - lst.tok.shape[0]) * TOKEN_TILE


def _start_rows(index_ref, first, src_ref, dst_ref, sem):
    """Start the copies `src[index[first + j]] -> dst[j]` for all of `dst`'s
    rows, one a row, `GROUP` of them written out in a loop's body."""

    def group(g, carry):
        for k in range(GROUP):
            j = g * GROUP + k
            pltpu.make_async_copy(src_ref.at[index_ref[first + j]], dst_ref.at[j], sem).start()
        return carry

    lax.fori_loop(0, dst_ref.shape[0] // GROUP, group, 0)


def _wait_rows(src_ref, dst_ref, sem):
    """One wait for all of `dst`'s rows: a copy's semaphore counts bytes."""
    pltpu.make_async_copy(src_ref.at[pl.ds(0, dst_ref.shape[0])], dst_ref, sem).wait()


def _with_next(index_ref, first_of, src_ref, buf, sem, work):
    """This program's rows in VMEM and the next program's on their way: the
    next program's copies are started before `work(slot)` is done on
    `buf[slot]`. `first_of(i)` is where program `i`'s row numbers start in
    `index_ref`. The last program has no next: it fetches its own rows again
    and waits for them, so that no copy outlives the kernel."""
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    slot, nxt = i % 2, (i + 1) % 2

    @pl.when(i == 0)
    def _():
        _start_rows(index_ref, first_of(0), src_ref, buf.at[0], sem.at[0])

    _wait_rows(src_ref, buf.at[slot], sem.at[slot])
    _start_rows(index_ref, first_of(jnp.minimum(i + 1, last)), src_ref, buf.at[nxt], sem.at[nxt])
    work(slot)

    @pl.when(i == last)
    def _():
        _wait_rows(src_ref, buf.at[nxt], sem.at[nxt])


def _gather_kernel(token_ref, count_ref, src_ref, *refs, weighted):
    """A tile of buffer rows: `src_ref` `[tokens, C, 128]` in HBM, the outputs'
    blocks `[ROW_TILE, hidden]`; weighted, w's `[ROW_TILE, 1]` and y's block
    come before them and dw's `[ROW_TILE, 1]` after."""
    if weighted:
        w_ref, y_ref, o_ref, dw_ref, buf, sem = refs
    else:
        o_ref, buf, sem = refs
    rows = o_ref.shape[0]

    def work(slot):
        g = buf[slot].reshape(o_ref.shape).astype(jnp.float32)
        if weighted:
            dw_ref[...] = jnp.sum(g * y_ref[...].astype(jnp.float32), -1, keepdims=True)
            g = g * w_ref[...]
        row = pl.program_id(0) * rows + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        o_ref[...] = jnp.where(row < count_ref[0], g, 0.0).astype(o_ref.dtype)

    _with_next(token_ref, lambda i: i * rows, src_ref, buf, sem, work)


def _combine_kernel(rows_ref, tile_ref, chunk_ref, base_ref, first_ref, tok_ref, *refs, weighted):
    """One visit: the `CHUNK` rows of y that `chunk_ref[v]` lists, summed by
    token into the block `tile_ref[v]` of the output, which stays in VMEM while
    the visits that follow name the same block."""
    if weighted:
        w_ref, y_ref, o_ref, buf, sem = refs
    else:
        y_ref, o_ref, buf, sem = refs
    del tile_ref    # the output's index map reads it
    v = pl.program_id(0)
    exact = buf.dtype == jnp.bfloat16

    def work(slot):
        at = pl.ds(chunk_ref[v], 1)
        token = base_ref[v] + lax.broadcasted_iota(jnp.int32, (o_ref.shape[0], CHUNK), 0)
        hit = token == tok_ref[at, :]
        if not weighted:
            terms = [hit.astype(buf.dtype)]
        elif exact:
            # w = w1 + w2 + w3 exactly (3 x 8 bits of mantissa), and a bfloat16
            # times a bfloat16 is exact in float32: the float32 product in three
            # passes
            terms, rest = [], jnp.where(hit, w_ref[at, :], 0.0)
            for _ in range(3):
                terms.append(rest.astype(jnp.bfloat16))
                rest = rest - terms[-1].astype(jnp.float32)
        else:
            terms = [jnp.where(hit, w_ref[at, :], 0.0)]
        y = buf[slot].reshape(CHUNK, o_ref.shape[1])
        acc = sum(lax.dot_general(
            s, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=None if exact else lax.Precision.HIGHEST) for s in terms)
        # the block's first visit writes it; what VMEM held there is never added
        o_ref[...] = acc + jnp.where(first_ref[v] == 1, 0.0, o_ref[...])

    _with_next(rows_ref, lambda i: chunk_ref[i] * CHUNK, y_ref, buf, sem, work)


def _as_rows(a):
    """`[rows, hidden] -> [rows, hidden / 128, 128]`: a row as whole tiles. On
    the chip this is a copy of the tensor (XLA tiles the last two axes)."""
    return a.reshape(a.shape[0], a.shape[1] // LANES, LANES)


# jitted, as the kernels of `ops/pallas_attention.py`: a step calls each program
# twelve times or more, and an inner jit is lowered to Mosaic once a signature
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _gather(src, token, count, w, y, dtype, interpret):
    """`dispatch` (w and y `None`) or `combine`'s transpose: `src` `[tokens,
    hidden]`, `token` `[n]`, `count` a scalar; -> `[n, hidden]` of `dtype`, and
    weighted dw `[n, 1]` float32 too."""
    weighted = w is not None
    n, hidden = token.shape[0], src.shape[1]
    tile = pl.BlockSpec((ROW_TILE, hidden), lambda i, *_: (i, 0))
    col = pl.BlockSpec((ROW_TILE, 1), lambda i, *_: (i, 0))
    operands = [w, y] if weighted else []
    out = pl.pallas_call(
        functools.partial(_gather_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // ROW_TILE,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [col, tile] * weighted,
            out_specs=[tile, col][: 1 + weighted],
            scratch_shapes=[pltpu.VMEM((2, ROW_TILE, hidden // LANES, LANES), src.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_out_shapes((src, *operands), ((n, hidden), dtype),
                              ((n, 1), jnp.float32))[: 1 + weighted],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="moe_gather_weighted" if weighted else "moe_gather",
    )(token.astype(jnp.int32), jnp.reshape(count, (1,)).astype(jnp.int32), _as_rows(src),
      *operands)
    return out if weighted else out[0]


@functools.partial(jax.jit, static_argnames=("tokens", "weighted", "interpret"))
def _sum_by_token(y, lst, tokens, weighted, interpret):
    """`combine` (weighted by `lst.w`) or `dispatch`'s transpose (weights of
    one): `y` `[n, hidden]` -> `[tokens, hidden]` float32."""
    n, hidden = y.shape
    # the barrier keeps the copy to rows of whole tiles a copy: fused into the
    # sum of the grouped products' two transposes that makes `dx`, it cost that
    # fusion three times what it costs alone, under `moe_experts` (my chip
    # run, PR 32). Not on `_gather`'s side: there XLA folds the source's cast
    # into the fusion before it, which a barrier forbids (5.8 ms a step)
    y = lax.optimization_barrier(y)
    whole = pl.BlockSpec((n // CHUNK, CHUNK), lambda v, *_: (0, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(lst.tile.shape[0],),
            in_specs=[whole] * (1 + weighted) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TOKEN_TILE, hidden), lambda v, rows, tile, *_: (tile[v], 0)),
            scratch_shapes=[pltpu.VMEM((2, CHUNK, hidden // LANES, LANES), y.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_out_shapes((y, lst.tok), ((tokens, hidden), jnp.float32))[0],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="moe_combine" if weighted else "moe_gather_transpose",
    )(lst.rows, lst.tile, lst.chunk, lst.base, lst.first, lst.tok, *([lst.w] * weighted),
      _as_rows(y))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch(src, token, count, lst, dtype, interpret):
    return _gather(src, token, count, None, None, dtype, interpret)


def _dispatch_fwd(src, token, count, lst, dtype, interpret):
    # the transpose needs the rows' list and the source's type, nothing of the source
    return _dispatch(src, token, count, lst, dtype, interpret), (lst, jnp.zeros((0,), src.dtype))


def _dispatch_bwd(dtype, interpret, residuals, dx):
    lst, like = residuals
    # the rows from `count` on are last in the list and under no token: they add nothing
    du = _sum_by_token(dx, lst, _tokens_of(lst), False, interpret)
    return du.astype(like.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(y, w, token, count, lst, interpret):
    return _sum_by_token(y, lst, _tokens_of(lst), True, interpret)


def _combine_fwd(y, w, token, count, lst, interpret):
    return _combine(y, w, token, count, lst, interpret), (y, w, token, count)


def _combine_bwd(interpret, residuals, dout):
    y, w, token, count = residuals
    dy, dw = _gather(dout, token, count, w.astype(jnp.float32), y, y.dtype, interpret)
    return dy, dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch(src: jax.Array, token: jax.Array, count: jax.Array, lst: Listing, *, dtype,
             interpret: bool = False) -> jax.Array:
    """`where(r < count, src[token[r]], 0).astype(dtype)`: `src` `[tokens,
    hidden]` in any float type (read as it is, cast on the tile), `token` `[n]`
    -> `[n, hidden]`. `lst` is `listing(token, count, w, tokens)`, which the
    transpose walks: `d src[t]` is the float32 sum of the cotangent's rows of
    token `t`, rounded once to `src`'s type. Shapes as `dispatch_plan` sends
    here."""
    return _dispatch(src, token, count, lst, jnp.dtype(dtype), interpret)


def combine(y: jax.Array, w: jax.Array, token: jax.Array, count: jax.Array, lst: Listing, *,
            interpret: bool = False) -> jax.Array:
    """`zeros([tokens, hidden], float32).at[token].add(f32(y) * w)` over the
    rows before `count`: `y` `[n, hidden]`, `w` `[n, 1]` float32 and zero from
    `count` on -> `[tokens, hidden]` float32. `lst` is `listing(token, count,
    w, tokens)` of the same `w`. The transpose hands `y` its cotangent in
    `y`'s type and `w` its own."""
    return _combine(y, w, token, count, lst, interpret)
