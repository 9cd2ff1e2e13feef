"""ISSUE 28: the fused block-causal attention kernel (`ops/pallas_attention.py`)
against the einsum path of `models/sdar.py` that it replaces on the TPU. On the
CPU the kernel runs interpreted, outside any `shard_map`; inside one it is only
traced (interpret-mode Pallas does not run there: `data/augment.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.models import sdar
from moco_tpu.ops import pallas_attention as pa

BLOCK = 4
# [B, L, heads, kv_heads]; head_dim 128, the published arch's
SHAPES = {"b2_l256_h4_kv2": (2, 256, 4, 2), "b1_l512_h8_kv1": (1, 512, 8, 1)}
# the largest gap of any element over the largest element of the einsum path's
# result. float32: both paths are float32 throughout and differ in summation
# order. bfloat16: both round the weights and the results to 8 bits (2^-8 of an
# element, a few times that where a rounding flips); the kernel's ds meets q
# and k in bfloat16 where the CPU's einsum path keeps it float32 (the TPU's
# default precision rounds it there too)
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


def inputs(shape, dtype, seed=0):
    b, length, heads, kv_heads = shape
    keys = jax.random.split(jax.random.key(seed), 4)
    sizes = [(b, length, heads, 128), (b, length, kv_heads, 128), (b, length, kv_heads, 128),
             (b, length, heads, 128)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, sizes)]


def kernel(q, k, v):
    return pa.block_causal_attention(q, k, v, block_length=BLOCK, interpret=True)


def einsums(q, k, v):
    return sdar.einsum_attention(q, k, v, BLOCK)


_RESULTS: dict = {}


def results(shape_name, dtype):
    """`(o, dq, dk, dv)` of the kernel and of the einsums, under one random cotangent."""
    if (shape_name, dtype) not in _RESULTS:
        q, k, v, ct = inputs(SHAPES[shape_name], jnp.dtype(dtype))
        both = []
        for fn in (kernel, einsums):
            o, vjp = jax.vjp(fn, q, k, v)
            both.append([np.asarray(x, np.float32) for x in (o, *vjp(ct))])
        _RESULTS[shape_name, dtype] = both
    return _RESULTS[shape_name, dtype]


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_the_kernel_agrees_with_the_einsum_path(shape_name, dtype, which):
    i = ["o", "dq", "dk", "dv"].index(which)
    got, want = (r[i] for r in results(shape_name, dtype))
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_later_blocks_tokens_leave_every_earlier_block_bit_identical(dtype):
    """The mask, tile skipping included: positions from block 37 on (inside q
    tile 1, so both a tile on the diagonal and the tiles after it change) are
    replaced in q, k and v; no earlier position's output moves by a bit, and the
    changed block's own output does."""
    q, k, v, _ = inputs(SHAPES["b1_l512_h8_kv1"], jnp.dtype(dtype), seed=1)
    first = 37 * BLOCK
    q2, k2, v2, _ = inputs(SHAPES["b1_l512_h8_kv1"], jnp.dtype(dtype), seed=2)
    later = (jnp.arange(512) >= first)[None, :, None, None]
    changed = kernel(*(jnp.where(later, new, old) for new, old in ((q2, q), (k2, k), (v2, v))))
    base = kernel(q, k, v)
    assert (np.asarray(base[:, :first]) == np.asarray(changed[:, :first])).all()
    assert (np.asarray(base[:, first:first + BLOCK]) != np.asarray(changed[:, first:first + BLOCK])).any()
    # and inside the block the view is whole: its last key moves its first query
    k3 = k.at[:, first + BLOCK - 1].set(k2[:, first + BLOCK - 1])
    assert (np.asarray(kernel(q, k3, v)[:, first]) != np.asarray(base[:, first])).any()


TINY = sdar.SDAR_SIZES["sdar_tiny"]
REAL = sdar.SDAR_SIZES["sdar_30b_a3b"]


@pytest.mark.parametrize("case, length, head_dim, block_length, backend, path, skipped", [
    ("sdar_tiny", 16, TINY["head_dim"], TINY["block_length"], "tpu", "einsum", 0),
    ("ragged_length", 500, 128, 4, "tpu", "einsum", 0),
    ("half_lane_head", 512, 64, 4, "tpu", "einsum", 0),
    ("block_straddles_tile", 512, 128, 48, "tpu", "einsum", 0),
    ("cpu_backend", 512, REAL["head_dim"], REAL["block_length"], "cpu", "einsum", 0),
    ("the_cell", 512, REAL["head_dim"], REAL["block_length"], "tpu", "fused", 6),
    ("two_tiles", 256, 128, 128, "tpu", "fused", 1),
])
def test_the_dispatch_rule(case, length, head_dim, block_length, backend, path, skipped):
    plan = pa.attention_plan(length, head_dim, block_length, backend=backend)
    side = -(-length // 128)
    assert plan == {"path": path, "tiles": side * side, "tiles_skipped": skipped}


def test_this_backend_takes_the_einsums_and_the_module_follows_the_rule(monkeypatch):
    """No knob: `Attention` asks the rule, and the rule asks the backend."""
    assert pa.attention_plan(512, 128, 4)["path"] == "einsum"          # the tests' CPU
    assert sdar.attention_path("sdar_30b_a3b", 512)["path"] == "einsum"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sdar.attention_path("sdar_30b_a3b", 512) == {"path": "fused", "tiles": 16,
                                                          "tiles_skipped": 6}
    assert sdar.attention_path("sdar_tiny", 16)["path"] == "einsum"
    calls = []
    monkeypatch.setattr(sdar, "block_causal_attention",
                        lambda q, k, v, block_length: calls.append(q.shape) or q)
    module = sdar.Attention(2, 1, 128, 4, 1e6, 1e-6)
    h = jnp.zeros((1, 128, 32))
    jax.eval_shape(lambda: module.init_with_output(jax.random.key(0), h)[0])
    assert calls == [(1, 128, 2, 128)]


def test_outputs_carry_vma_under_a_two_device_shard_map_with_the_check_on():
    """Trace only, forward and backward, as the step's region does: with
    `check_vma` on, a `pallas_call` whose `out_shape` lacks the inputs' `vma`
    does not type-check (jax's splash attention: ROADMAP S5)."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.mesh import DATA_AXIS, create_mesh

    mesh = create_mesh(devices=jax.devices()[:2])
    q, k, v, ct = inputs((2, 128, 2, 1), jnp.bfloat16)
    seen = []

    def region(q, k, v, ct):
        o, vjp = jax.vjp(lambda q, k, v: pa.block_causal_attention(q, k, v, block_length=BLOCK),
                         q, k, v)
        grads = vjp(ct)
        seen.extend(jax.typeof(x).vma for x in (o, *grads))
        return (o, *grads)

    spec = P(DATA_AXIS)
    sharded = jax.shard_map(region, mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 4,
                            check_vma=True)
    out = jax.eval_shape(sharded, q, k, v, ct)
    assert [x.shape for x in out] == [q.shape, q.shape, k.shape, v.shape]
    assert seen == [frozenset({DATA_AXIS})] * 4
    jaxpr = str(jax.make_jaxpr(sharded)(q, k, v, ct))
    assert jaxpr.count("pallas_call") == 2 and "check_vma=True" in jaxpr


def test_the_cells_shapes_lower_for_the_tpu_forward_and_backward():
    """`[32, 512, 32 / 4, 128]` in bfloat16, exported for the TPU platform from
    the CPU (as `test_fused_conv.py` does for the image step): a tracing or
    typing break of the two Mosaic kernels fails here, not on the chip."""
    q, k = (jax.ShapeDtypeStruct((32, 512, h, 128), jnp.bfloat16) for h in (32, 4))

    def both(q, k, v, ct):
        o, vjp = jax.vjp(lambda q, k, v: pa.block_causal_attention(q, k, v, block_length=BLOCK),
                         q, k, v)
        return (o, *vjp(ct))

    exported = jax.export.export(jax.jit(both), platforms=["tpu"])(q, k, k, q)
    assert exported.mlir_module().count("tpu_custom_call") == 2
    assert [x.shape for x in exported.out_avals] == [q.shape, q.shape, k.shape, k.shape]
