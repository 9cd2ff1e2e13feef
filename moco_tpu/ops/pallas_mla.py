"""Pallas TPU kernels: causal latent attention (multi-head latent attention,
DeepSeek-V3's MLA after its up-projections) with the scores in VMEM.

What differs from `ops/pallas_attention.py`'s tiled pair is the head: q and k
are `nope + rope` wide (128 + 64 in the published model) where v and o are
`v_dim` (128), and the rotary part of k is ONE head that every query head
reads. As `[B, L, heads * 192]` a head would straddle the 128-lane tiles, and a
k of `heads * 192` would hold 32 copies of the rotary key. So the caller hands
the parts apart, each on lane tiles of its own:

  - `q_nope`, `k_nope` `[B, L, heads * nope]`, `v` and the result
    `[B, L, heads * v_dim]`: a head is `nope` (or `v_dim`) lanes, as in the
    tiled pair;
  - `q_pe` `[B, L, heads * rope]` with `2 * rope == 128`: two heads share a lane
    tile, head `2i` in its low half and `2i + 1` in its high half (the
    projection's own layout, nothing is moved);
  - `k_pe` as `[B, L, 256]`: the rotary key in lanes 0..63 and again in lanes
    192..255, zeros between (`_wide`). Its first 128 lanes against a pair's tile
    are `q_pe[2i] . k_pe`, its last 128 `q_pe[2i + 1] . k_pe`: two products of
    one lane tile each, and no lane is moved inside the kernel. A program reads
    it once a key chunk for all of its heads: 4 copies of 64 lanes in memory
    where the flat layout would hold 32.

A score is `(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)`: one 128-deep
product and one 128-deep product of which half is zeros. The MXU takes a
64-deep product at the cost of a 128-deep one, so the zeros cost no time there.

The tiling is the tiled pair's: a forward program serves one batch row, `n`
query heads, one q tile of `FWD_Q_TILE` rows and one key chunk of `KEY_CHUNK`
keys a grid step (online softmax: the running maximum and sum `[rows, 128]`
with a row's value on every lane, the unnormalised mix in float32), and does
nothing for a chunk wholly above the diagonal (its blocks' index maps repeat
the chunk before, so nothing is fetched). The mask is causal and a function of
positions: a program makes it from iotas once a grid step and adds it to every
head's scores; no mask operand is read. The arithmetic is the tiled pair's:
`dtype` operands and float32 accumulation, float32 max / exp / sum, the weights
rounded to `dtype` before they meet v, the mix divided by the sum at the end.

The backward pass is one kernel on a grid of `BWD_Q_TILE` rows and the same
chunks, scores `[keys, queries]`: dq summed over a q tile's chunks in VMEM,
dk_nope and dv over a head's q tiles in a float32 `[L, n * D]` scratch each, and
the rotary key's gradient over the program's heads and q tiles straight into a
float32 `[L, 256]` output block, one a group of heads: XLA sums the groups. The
same lane halves carry dq_pe: a pair's tile is the sum of its even head's ds
against the first 128 lanes of the wide key and its odd head's against the last.

The differentiated call's forward rule names `o` and the log-sum-exp as the
tiled pair does (`KEPT_OUT`, `KEPT_LSE`): a rematerialised layer whose policy
keeps those names (`models/sdar.py::KEPT`) runs one backward kernel and no
second forward. `interpret=True` runs the same code on the CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from moco_tpu.ops.pallas_attention import (_NT, KEPT_LSE, KEPT_OUT, MASKED, TILE, _dot, _lane_row,
                                            _lanes, _out_shapes)

KEY_CHUNK = 512
FWD_Q_TILE = 512
BWD_Q_TILE = 256
# query heads a program serves, side by side on the lanes: even, so that the
# rotary parts of q come as whole lane tiles of two heads each
FWD_HEADS, BWD_HEADS = 8, 4
# forward: q/k/v blocks of 3.75 MB twice for the pipeline, the o block, three
# float32 scratches of 6 MB and a head's [512, 512] scores beside them
_FWD_VMEM = 48 * 2 ** 20
# backward at 4 096 tokens: dk_nope and dv of 4 heads, float32 scratches of 8 MB
# each and output blocks of 4 MB twice for the pipeline, the rotary key's float32
# [L, 256] output block twice, the tiles
_BWD_VMEM = 96 * 2 ** 20


def mla_plan(length: int, nope: int, rope: int, v_dim: int, backend: str | None = None) -> dict:
    """Which path causal latent attention takes for these shapes, and how many
    `TILE` x `TILE` score tiles it computes and skips. `tiled` needs a TPU,
    whole lane tiles for a head's nope and v parts, two rotary parts to a lane
    tile, and a view of whole key chunks and q tiles; `einsum` elsewhere."""
    side = -(-length // TILE)
    ok = ((backend or jax.default_backend()) == "tpu" and nope % TILE == 0
          and v_dim % TILE == 0 and 2 * rope == TILE
          and all(length % n == 0 for n in (KEY_CHUNK, FWD_Q_TILE, BWD_Q_TILE)))
    if not ok:
        return {"path": "einsum", "tiles": side * side, "tiles_skipped": 0}
    # every key chunk that reaches under the diagonal of a forward q tile, whole
    computed = sum((FWD_Q_TILE // TILE) * (KEY_CHUNK // TILE) * (_last(t, FWD_Q_TILE) + 1)
                   for t in range(length // FWD_Q_TILE))
    return {"path": "tiled", "tiles": side * side, "tiles_skipped": side * side - computed}


def _last(t, tq: int):
    """The last key chunk that reaches under the diagonal of q tile `t`."""
    return ((t + 1) * tq - 1) // KEY_CHUNK


def _wide(k_pe: jax.Array) -> jax.Array:
    """`[B, L, rope]` -> `[B, L, 4 * rope]`: the key in the first and the last
    quarter, zeros between (see the module's docstring)."""
    zeros = jnp.zeros(k_pe.shape[:-1] + (2 * k_pe.shape[-1],), k_pe.dtype)
    return jnp.concatenate([k_pe, zeros, k_pe], -1)


def _causal_bias(t, c, tq: int, tk: int, keys_on_rows: bool):
    """0 where the key is not after the query, `MASKED` elsewhere: float32
    `[tq, tk]` (`[tk, tq]` with keys on rows)."""
    shape = (tk, tq) if keys_on_rows else (tq, tk)
    q_axis, k_axis = (1, 0) if keys_on_rows else (0, 1)
    query = t * tq + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    key = c * tk + lax.broadcasted_iota(jnp.int32, shape, k_axis)
    return jnp.where(key <= query, 0.0, MASKED)


def _fwd_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref, *rest, nope, v_dim, scale):
    """One batch row, `n` heads, one q tile, one key chunk: qn/kn refs
    `[rows, n * nope]`, qp `[rows, n * rope]`, kp `[keys, 256]`, v/o
    `[rows, n * v_dim]`, lse `[n, 1, rows]` (a differentiated call's), then the
    scratch: running maximum and sum `[n, rows, 128]`, mix `[rows, n * v_dim]`."""
    *lse_ref, m_scr, l_scr, acc_scr = rest
    t, c = pl.program_id(2), pl.program_id(3)
    tq, tk = qn_ref.shape[0], kn_ref.shape[0]
    last = _last(t, tq)
    n = qn_ref.shape[1] // nope

    @pl.when(c == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, MASKED, jnp.float32)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(c <= last)
    def _():
        bias = _causal_bias(t, c, tq, tk, keys_on_rows=False)
        kp = kp_ref[...]
        halves = (kp[:, :TILE], kp[:, TILE:])
        for j in range(n):
            hn, hv = slice(j * nope, (j + 1) * nope), slice(j * v_dim, (j + 1) * v_dim)
            pair = qp_ref[:, (j // 2) * TILE:(j // 2 + 1) * TILE]
            s = _dot(qn_ref[:, hn], kn_ref[:, hn], _NT) + _dot(pair, halves[j % 2], _NT)
            s = s * scale + bias
            m_old = m_scr[j]
            m = jnp.maximum(m_old, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - _lanes(m, tk))
            alpha = jnp.exp(m_old - m)
            l_scr[j] = alpha * l_scr[j] + jnp.sum(p, -1, keepdims=True)
            acc_scr[:, hv] = (_lanes(alpha, v_dim) * acc_scr[:, hv]
                              + _dot(p.astype(v_ref.dtype), v_ref[:, hv]))
            m_scr[j] = m

    @pl.when(c == last)
    def _():
        for j in range(n):
            hv = slice(j * v_dim, (j + 1) * v_dim)
            total = l_scr[j]
            o_ref[:, hv] = (acc_scr[:, hv] * _lanes(1.0 / total, v_dim)).astype(o_ref.dtype)
            if lse_ref:
                lse_ref[0][j] = (m_scr[j] + jnp.log(total)).T[:1, :]


def _bwd_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref, do_ref, lse_ref,
                dqn_ref, dqp_ref, dkn_ref, dkp_ref, dv_ref,
                dqn_acc, dqp_acc, dkn_acc, dv_acc, delta_scr, *, nope, v_dim, scale):
    """As `_fwd_kernel`, scores `[keys, queries]`; dkn/dv refs `[L, n * D]` and
    dkp `[L, 256]` float32, written (dkp: summed) over every q tile."""
    t, c = pl.program_id(2), pl.program_id(3)
    tq, tk = qn_ref.shape[0], kn_ref.shape[0]
    last = _last(t, tq)
    n = qn_ref.shape[1] // nope

    @pl.when((t == 0) & (c == 0))
    def _():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dkp_ref[...] = jnp.zeros_like(dkp_ref)

    @pl.when(c == 0)
    def _():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        dqp_acc[...] = jnp.zeros_like(dqp_acc)
        for j in range(n):
            hv = slice(j * v_dim, (j + 1) * v_dim)
            delta = jnp.sum(o_ref[:, hv].astype(jnp.float32) * do_ref[:, hv].astype(jnp.float32),
                            -1, keepdims=True)
            delta_scr[j] = _lane_row(delta)

    @pl.when(c <= last)
    def _():
        bias = _causal_bias(t, c, tq, tk, keys_on_rows=True)
        kp = kp_ref[...]
        halves = (kp[:, :TILE], kp[:, TILE:])
        keys = pl.ds(pl.multiple_of(c * tk, tk), tk)
        dkp = [jnp.zeros((tk, TILE), jnp.float32) for _ in halves]
        for j in range(n):
            hn, hv = slice(j * nope, (j + 1) * nope), slice(j * v_dim, (j + 1) * v_dim)
            hp = slice((j // 2) * TILE, (j // 2 + 1) * TILE)
            qn, pair, do = qn_ref[:, hn], qp_ref[:, hp], do_ref[:, hv]
            kn = kn_ref[:, hn]
            s = _dot(kn, qn, _NT) + _dot(halves[j % 2], pair, _NT)
            p = jnp.exp(s * scale + bias - lse_ref[j])
            ds = (p * (_dot(v_ref[:, hv], do, _NT) - delta_scr[j])).astype(qn.dtype)
            dv_acc[keys, hv] += _dot(p.astype(do.dtype), do)
            dkn_acc[keys, hn] += _dot(ds, qn)
            # the even head's half of the pair's lanes is its own, the odd head's the other
            dkp[j % 2] = dkp[j % 2] + _dot(ds, pair)
            dqn_acc[:, hn] += _dot(ds.T, kn)
            dqp_acc[:, hp] += _dot(ds.T, halves[j % 2])
        dkp_ref[keys, :TILE] += dkp[0] * scale
        dkp_ref[keys, TILE:] += dkp[1] * scale

    @pl.when(c == last)
    def _():
        dqn_ref[...] = (dqn_acc[...] * scale).astype(dqn_ref.dtype)
        dqp_ref[...] = (dqp_acc[...] * scale).astype(dqp_ref.dtype)

    @pl.when((t == pl.num_programs(2) - 1) & (c == last))
    def _():
        dkn_ref[...] = (dkn_acc[...] * scale).astype(dkn_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _specs(q_nope, v, heads, n, tq):
    """The grid `(batch, head groups, q tiles, key chunks)` and the block specs
    of one group's `n` heads. A chunk above the diagonal repeats the chunk before."""
    b, length, _ = q_nope.shape
    nope, v_dim = q_nope.shape[2] // heads, v.shape[2] // heads
    rope2 = TILE // 2 * n            # a group's rotary lanes: n / 2 pair tiles

    def chunk(t, c):
        return jnp.minimum(c, _last(t, tq))

    def rows(width):
        return pl.BlockSpec((None, tq, width), lambda b, g, t, c: (b, t, g))

    def keys(width):
        return pl.BlockSpec((None, KEY_CHUNK, width), lambda b, g, t, c: (b, chunk(t, c), g))

    def whole(width):
        return pl.BlockSpec((None, length, width), lambda b, g, t, c: (b, 0, g))

    specs = dict(
        qn=rows(n * nope), qp=rows(rope2), o=rows(n * v_dim),
        kn=keys(n * nope), v=keys(n * v_dim),
        kp=pl.BlockSpec((None, KEY_CHUNK, 2 * TILE), lambda b, g, t, c: (b, chunk(t, c), 0)),
        lse=pl.BlockSpec((None, n, 1, tq), lambda b, g, t, c: (b, g, 0, t)),
        kn_whole=whole(n * nope), v_whole=whole(n * v_dim),
        kp_group=pl.BlockSpec((None, None, length, 2 * TILE), lambda b, g, t, c: (b, g, 0, 0)))
    grid = (b, heads // n, length // tq, length // KEY_CHUNK)
    return grid, specs, nope, v_dim


@functools.partial(jax.jit, static_argnames=("heads", "interpret", "with_lse"))
def _forward(qn, qp, kn, kp, v, heads, interpret, with_lse):
    n = math.gcd(heads, FWD_HEADS)
    grid, s, nope, v_dim = _specs(qn, v, heads, n, FWD_Q_TILE)
    b, length, _ = qn.shape
    n_out = 2 if with_lse else 1
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nope=nope, v_dim=v_dim,
                          scale=1.0 / math.sqrt(nope + qp.shape[2] // heads)),
        grid=grid,
        in_specs=[s["qn"], s["qp"], s["kn"], s["kp"], s["v"]],
        out_specs=[s["o"], s["lse"]][:n_out],
        out_shape=_out_shapes((qn, qp, kn, kp, v), ((b, length, heads * v_dim), v.dtype),
                              ((b, heads, 1, length), jnp.float32))[:n_out],
        scratch_shapes=[pltpu.VMEM((n, FWD_Q_TILE, TILE), jnp.float32)] * 2
        + [pltpu.VMEM((FWD_Q_TILE, n * v_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FWD_VMEM),
        interpret=interpret,
        name="mla_attention_fwd",
    )(qn, qp, kn, kp, v)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _backward(qn, qp, kn, kp, v, o, lse, do, heads, interpret):
    n = math.gcd(heads, BWD_HEADS)
    grid, s, nope, v_dim = _specs(qn, v, heads, n, BWD_Q_TILE)
    b, length, _ = qn.shape
    dqn, dqp, dkn, dkp, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, nope=nope, v_dim=v_dim,
                          scale=1.0 / math.sqrt(nope + qp.shape[2] // heads)),
        grid=grid,
        in_specs=[s["qn"], s["qp"], s["kn"], s["kp"], s["v"], s["o"], s["o"], s["lse"]],
        out_specs=[s["qn"], s["qp"], s["kn_whole"], s["kp_group"], s["v_whole"]],
        out_shape=_out_shapes((qn, qp, kn, kp, v, do), (qn.shape, qn.dtype), (qp.shape, qp.dtype),
                              (kn.shape, kn.dtype), ((b, heads // n, length, 2 * TILE), jnp.float32),
                              (v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((BWD_Q_TILE, n * nope), jnp.float32),
                        pltpu.VMEM((BWD_Q_TILE, TILE // 2 * n), jnp.float32),
                        pltpu.VMEM((length, n * nope), jnp.float32),
                        pltpu.VMEM((length, n * v_dim), jnp.float32),
                        pltpu.VMEM((n, 1, BWD_Q_TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM),
        interpret=interpret,
        name="mla_attention_bwd",
    )(qn, qp, kn, kp, v, o, do, lse)
    return dqn, dqp, dkn, jnp.sum(dkp, 1).astype(kp.dtype), dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mla(qn, qp, kn, kp, v, heads, interpret):
    return _forward(qn, qp, kn, kp, v, heads, interpret, with_lse=False)[0]


def _mla_fwd(qn, qp, kn, kp, v, heads, interpret):
    from jax.ad_checkpoint import checkpoint_name   # no attribute of `jax`

    o, lse = _forward(qn, qp, kn, kp, v, heads, interpret, with_lse=True)
    o, lse = checkpoint_name(o, KEPT_OUT), checkpoint_name(lse, KEPT_LSE)
    return o, (qn, qp, kn, kp, v, o, lse)


def _mla_bwd(heads, interpret, residuals, do):
    return _backward(*residuals, do, heads=heads, interpret=interpret)


_mla.defvjp(_mla_fwd, _mla_bwd)


def causal_latent_attention(q_nope: jax.Array, q_pe: jax.Array, k_nope: jax.Array,
                            k_pe: jax.Array, v: jax.Array, *, heads: int,
                            interpret: bool = False) -> jax.Array:
    """`softmax_causal((q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)) v`.

    q_nope, k_nope `[B, L, heads * nope]`, q_pe `[B, L, heads * rope]`, k_pe
    `[B, L, rope]` (one head for all), v `[B, L, heads * v_dim]`, one dtype,
    rotary already applied; returns `[B, L, heads * v_dim]` in that dtype. The
    shapes are those `mla_plan` sends here."""
    return _mla(q_nope, q_pe, k_nope, _wide(k_pe), v, heads, interpret)
