"""ISSUE 28: the fused block-causal attention kernel (`ops/pallas_attention.py`)
against the einsum path of `models/sdar.py` that it replaces on the TPU; ISSUE
30: `norm_rotary`, the per-head RMSNorm, rotary and cast of q and k on the
projections' own layout, against `rotary(RMSNorm(...))`. On the CPU the kernels
run interpreted, outside any `shard_map`; inside one they are only traced
(interpret-mode Pallas does not run there: `data/augment.py`)."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu import models
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


def flat(x):
    return x.reshape(*x.shape[:2], -1)


def attention(q, k, v, interpret=False):
    """The kernel on `[B, L, heads, D]` arrays: it takes and gives the
    projections' `[B, L, heads * D]`."""
    o = pa.block_causal_attention(flat(q), flat(k), flat(v), heads=q.shape[2],
                                  kv_heads=k.shape[2], block_length=BLOCK, interpret=interpret)
    return o.reshape(q.shape)


def kernel(q, k, v):
    return attention(q, k, v, interpret=True)


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
    assert plan == {"path": path, "tiles": side * side, "tiles_skipped": skipped,
                    "qk_prep": "fused" if path == "fused" else "xla"}


def test_this_backend_takes_the_einsums_and_the_module_follows_the_rule(monkeypatch):
    """No knob: `Attention` asks the rule, and the rule asks the backend."""
    assert pa.attention_plan(512, 128, 4)["path"] == "einsum"          # the tests' CPU
    assert models.attention_path("sdar_30b_a3b", 512)["path"] == "einsum"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert models.attention_path("sdar_30b_a3b", 512) == {
        "path": "fused", "tiles": 16, "tiles_skipped": 6, "qk_prep": "fused"}
    assert models.attention_path("sdar_tiny", 16)["path"] == "einsum"
    assert models.attention_path("sdar_tiny", 16)["qk_prep"] == "xla"
    calls = []
    monkeypatch.setattr(sdar, "block_causal_attention",
                        lambda q, k, v, **kw: calls.append((q.shape, k.shape, kw)) or q)
    monkeypatch.setattr(sdar, "norm_rotary",
                        lambda y, scale, **kw: calls.append((y.shape, scale.shape, kw)) or y)
    module = sdar.Attention(2, 1, 128, 4, 1e6, 1e-6)
    h = jnp.zeros((1, 128, 32))
    params = jax.eval_shape(lambda: module.init_with_output(jax.random.key(0), h)[1])
    # where attention is fused the preparation of q and k is too, on flat arrays
    prep = dict(dtype=jnp.float32, theta=1e6, eps=1e-6, head_dim=128)
    assert calls == [((1, 128, 256), (128,), prep), ((1, 128, 128), (128,), prep),
                     ((1, 128, 256), (1, 128, 128), dict(heads=2, kv_heads=1, block_length=4))]
    # and the parameter tree is the einsum path's
    monkeypatch.undo()
    plain = jax.eval_shape(lambda: module.init_with_output(jax.random.key(0), h)[1])
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    assert jax.tree.leaves(params) == jax.tree.leaves(plain)


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
        o, vjp = jax.vjp(attention, q, k, v)
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
        o, vjp = jax.vjp(attention, q, k, v)
        return (o, *vjp(ct))

    exported = jax.export.export(jax.jit(both), platforms=["tpu"])(q, k, k, q)
    assert exported.mlir_module().count("tpu_custom_call") == 2
    assert [x.shape for x in exported.out_avals] == [q.shape, q.shape, k.shape, k.shape]


# ---------------------------------------------------------------------------
# ISSUE 30: norm_rotary
# ---------------------------------------------------------------------------

THETA, EPS = REAL["rope_theta"], REAL["eps"]
# [B, L, heads] of 128 lanes: a q shape and a k shape, two lengths
PREP_SHAPES = {"q_b2_l256_h8": (2, 256, 8), "k_b1_l512_h2": (1, 512, 2)}


def xla_path(y, scale, dtype=None):
    """What `Attention` does to q and k wherever the kernel does not run."""
    b, length, width = y.shape
    x = sdar.RMSNorm(EPS).apply({"params": {"scale": scale}}, y.reshape(b, length, -1, 128))
    return sdar.rotary(x, THETA).astype(dtype or y.dtype).reshape(b, length, width)


def prep_kernel(y, scale, dtype=None):
    return pa.norm_rotary(y, scale, dtype=dtype or y.dtype, theta=THETA, eps=EPS, interpret=True)


def prep_inputs(shape, dtype, seed=0):
    """y with rows of every size from far under `sqrt(eps)` up (so `eps`
    matters in some and not in others), a scale that is not 1."""
    b, length, heads = shape
    ky, kr, ks = jax.random.split(jax.random.key(seed), 3)
    size = jnp.exp(3.0 * jax.random.normal(kr, (b, length, 1)) - 6.0)
    y = (jax.random.normal(ky, (b, length, heads * 128)) * size).astype(dtype)
    return y, 1.0 + 0.3 * jax.random.normal(ks, (128,))


# y's dtype -> the result's: float32 throughout; what `Attention` runs in
# bfloat16 (y as `RMSNorm` takes it, cast to float32); y in bfloat16 itself
PREP_DTYPES = {"float32": ("float32", "float32"), "bfloat16": ("float32", "bfloat16"),
               "bfloat16_in": ("bfloat16", "bfloat16")}
_PREP: dict = {}


def prep_results(shape_name, case):
    """`(o, dx, dscale)` of the kernel, of the XLA path, and of the XLA path in
    float32 on the same inputs with its result left in float32 (the oracle).
    All three jitted: XLA draws the angles' powers one way eagerly and another
    way compiled, 1e-5 of an element apart in float32. The XLA path's dx is
    rounded to the cotangent's dtype, as the transpose of the projection's cast
    rounds it in the step, and as the kernel writes it."""
    if (shape_name, case) not in _PREP:
        y_dtype, dtype = (jnp.dtype(d) for d in PREP_DTYPES[case])
        y, scale = prep_inputs(PREP_SHAPES[shape_name], y_dtype)
        g = jax.random.normal(jax.random.key(9), y.shape).astype(dtype)
        def forward_and_transpose(fn, out, y, g):
            o, vjp = jax.vjp(lambda y, s: fn(y, s, out), y, scale)
            return (o, *vjp(g))

        runs = []
        for fn, out, wide in ((prep_kernel, dtype, False), (xla_path, dtype, False),
                              (xla_path, jnp.float32, True)):
            yy, gg = (y.astype(jnp.float32), g.astype(jnp.float32)) if wide else (y, g)
            o, dx, dscale = jax.jit(forward_and_transpose, static_argnums=(0, 1))(fn, out, yy, gg)
            assert (o.dtype, dx.dtype, dscale.dtype) == (out, yy.dtype, jnp.float32)
            if fn is xla_path and not wide:
                dx = dx.astype(dtype)
            runs.append([np.asarray(x, np.float64) for x in (o, dx, dscale)])
        _PREP[shape_name, case] = runs
    return _PREP[shape_name, case]


@pytest.mark.parametrize("which", ["o", "dx", "dscale"])
@pytest.mark.parametrize("case", list(PREP_DTYPES))
@pytest.mark.parametrize("shape_name", list(PREP_SHAPES))
def test_norm_rotary_agrees_with_rotary_of_rmsnorm(shape_name, case, which):
    """float32: the same arithmetic in the same order but for the lanes' sum
    and the reciprocal root, so a few float32 steps of the largest element.
    bfloat16: both paths compute in float32 and round once; the kernel is held
    to the XLA path's own distance from the float32 oracle."""
    i = ["o", "dx", "dscale"].index(which)
    got, want, oracle = (r[i] for r in prep_results(shape_name, case))
    assert got.shape == want.shape and np.isfinite(got).all()
    if case == "float32" or which == "dscale":
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    else:
        ours, theirs = (np.linalg.norm(x - oracle) for x in (got, want))
        assert theirs > 0 and ours <= 1.02 * theirs
        assert np.abs(got - oracle).max() <= 1.02 * np.abs(want - oracle).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_eps_matters_where_a_row_is_small_and_nowhere_else(dtype):
    """A row of zeros comes out as zeros with a zero gradient (1 / sqrt(eps) is
    finite), a row far under sqrt(eps) is scaled by 1 / sqrt(eps) and not to
    unit size, and a row of ordinary size does not see `eps`."""
    y, _ = prep_inputs((1, 128, 2), jnp.dtype(dtype), seed=3)
    g = jax.random.normal(jax.random.key(9), y.shape).astype(dtype)
    scale = jnp.ones((128,))          # rotary turns pairs of lanes: a head's size stays
    rows = jnp.arange(128)[None, :, None]
    y = jnp.where(rows == 0, 0, jnp.where(rows == 1, 1e-6 * jnp.sign(y),
                                          jnp.where(rows == 2, jnp.sign(y), y))).astype(dtype)
    o, vjp = jax.vjp(prep_kernel, y, scale)
    dx, _ = vjp(g)
    o, dx = (np.asarray(x, np.float32) for x in (o, dx))
    assert np.isfinite(o).all() and np.isfinite(dx).all()
    assert (o[0, 0] == 0).all()
    size = lambda row: np.sqrt((row.reshape(2, 128) ** 2).mean(-1))
    rtol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(size(o[0, 1]), 1e-6 / np.sqrt(1e-12 + EPS), rtol=rtol)
    np.testing.assert_allclose(size(o[0, 2]), 1.0, rtol=rtol)
    want = np.asarray(jax.jit(xla_path)(y, scale), np.float32)
    assert np.abs(o - want).max() <= (2e-6 if dtype == "float32" else 2 ** -7) * np.abs(want).max()


_MODULE: dict = {}
MODULE_LEAVES = ["out", "dh", "q/kernel", "k/kernel", "v/kernel", "o/kernel", "q_norm/scale",
                 "k_norm/scale"]


def module_results(monkeypatch):
    """Output, input gradient and every parameter gradient of `Attention` as the
    chip builds it (`norm_rotary` twice and `block_causal_attention`, here
    interpreted) and as plain einsums, on the same float32 parameters."""
    if not _MODULE:
        module = sdar.Attention(4, 2, 128, BLOCK, THETA, EPS)
        kp, kh, ks, kc = jax.random.split(jax.random.key(11), 4)
        h = jax.random.normal(kh, (2, 256, 64))
        params = module.init(kp, h)["params"]
        for name, key in (("q_norm", ks), ("k_norm", kc)):
            params[name]["scale"] = 1.0 + 0.3 * jax.random.normal(key, (128,))
        ct = jax.random.normal(kc, h.shape)

        def run():
            out, vjp = jax.vjp(lambda p, h: module.apply({"params": p}, h), params, h)
            dp, dh = vjp(ct)
            return {"out": out, "dh": dh,
                    **{f"{m}/{leaf}": v for m, sub in dp.items() for leaf, v in sub.items()}}

        _MODULE["einsum"] = {k: np.asarray(v) for k, v in run().items()}
        with monkeypatch.context() as patch:
            patch.setattr(sdar, "attention_plan", lambda *a: {"path": "fused"})
            patch.setattr(sdar, "norm_rotary", functools.partial(pa.norm_rotary, interpret=True))
            patch.setattr(sdar, "block_causal_attention",
                          functools.partial(pa.block_causal_attention, interpret=True))
            _MODULE["fused"] = {k: np.asarray(v) for k, v in run().items()}
    return _MODULE["fused"], _MODULE["einsum"]


@pytest.mark.parametrize("leaf", MODULE_LEAVES)
def test_the_fused_module_agrees_with_the_einsum_module_on_the_same_parameters(leaf, monkeypatch):
    fused, einsum = module_results(monkeypatch)
    assert sorted(fused) == sorted(einsum) == sorted(MODULE_LEAVES)
    got, want = fused[leaf], einsum[leaf]
    assert got.shape == want.shape and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 5e-5 * np.abs(want).max()


_LAYER: dict = {}
LAYER_SIZES = dict(hidden=128, heads=2, kv_heads=1, head_dim=128, experts=8, top_k=4,
                   expert_width=32, rope_theta=THETA, eps=EPS, block_length=BLOCK)
LAYER_LEAVES = ["out", "dx", "norm1/scale", "norm2/scale", "attn/q/kernel", "attn/k/kernel",
                "attn/v/kernel", "attn/o/kernel", "attn/q_norm/scale", "attn/k_norm/scale",
                "moe/router/kernel", "moe/gate", "moe/up", "moe/down"]


def layer_results(monkeypatch):
    """ISSUE 32: the whole `Layer` (attention and the routed experts, all of
    them held, so the router trains) as the chip builds it, the four attention
    kernels and `ops/pallas_dispatch.py`'s two interpreted, and as plain XLA,
    on the same float32 parameters: output, input gradient, every parameter's."""
    if not _LAYER:
        from moco_tpu.ops import pallas_dispatch as pd

        module = sdar.Layer(tuple(sorted(LAYER_SIZES.items())), LAYER_SIZES["experts"])
        kp, kx, kc = jax.random.split(jax.random.key(13), 3)
        x = jax.random.normal(kx, (2, 128, LAYER_SIZES["hidden"]))
        params = module.init(kp, x)["params"]
        ct = jax.random.normal(kc, x.shape)

        def run():
            out, vjp = jax.vjp(lambda p, x: module.apply({"params": p}, x), params, x)
            dp, dx = vjp(ct)
            flat = {"/".join(k.key for k in path): v
                    for path, v in jax.tree_util.tree_leaves_with_path(dp)}
            return {"out": out, "dx": dx, **flat}

        _LAYER["xla"] = {k: np.asarray(v) for k, v in run().items()}
        with monkeypatch.context() as patch:
            patch.setattr(sdar, "attention_plan", lambda *a: {"path": "fused"})
            patch.setattr(sdar, "norm_rotary", functools.partial(pa.norm_rotary, interpret=True))
            patch.setattr(sdar, "block_causal_attention",
                          functools.partial(pa.block_causal_attention, interpret=True))
            patch.setattr(sdar, "dispatch_plan", lambda *a, **k: "kernels")
            patch.setattr(sdar, "dispatch", functools.partial(pd.dispatch, interpret=True))
            patch.setattr(sdar, "combine", functools.partial(pd.combine, interpret=True))
            _LAYER["kernels"] = {k: np.asarray(v) for k, v in run().items()}
    return _LAYER["kernels"], _LAYER["xla"]


@pytest.mark.parametrize("leaf", LAYER_LEAVES)
def test_the_fused_layer_agrees_with_the_xla_layer_on_the_same_parameters(leaf, monkeypatch):
    kernels, xla = layer_results(monkeypatch)
    assert sorted(kernels) == sorted(xla) == sorted(LAYER_LEAVES)
    got, want = kernels[leaf], xla[leaf]
    assert got.shape == want.shape and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 5e-5 * np.abs(want).max()


def test_norm_rotary_outputs_carry_vma_under_a_two_device_shard_map_with_the_check_on():
    """Forward and transpose, as the step's region calls them: y varies over
    the data axis, and so does the scale (`collectives.device_local`: the
    region differentiates with respect to a device-local view of the
    parameters), so dx and the scale's gradient do."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.collectives import device_local
    from moco_tpu.parallel.mesh import DATA_AXIS, create_mesh

    mesh = create_mesh(devices=jax.devices()[:2])
    y, scale = prep_inputs((2, 128, 2), jnp.float32)
    g = y.astype(jnp.bfloat16)
    seen = []

    def region(y, scale, g):
        o, vjp = jax.vjp(lambda y, s: pa.norm_rotary(y, s, dtype=g.dtype, theta=THETA, eps=EPS),
                         y, device_local(scale, DATA_AXIS))
        dx, dscale = vjp(g)
        seen.extend(jax.typeof(x).vma for x in (o, dx, dscale))
        return o, dx, dscale[None]

    spec = P(DATA_AXIS)
    sharded = jax.shard_map(region, mesh=mesh, in_specs=(spec, P(), spec),
                            out_specs=(spec,) * 3, check_vma=True)
    out = jax.eval_shape(sharded, y, scale, g)
    assert [x.shape for x in out] == [y.shape, y.shape, (2, 128)]
    assert seen == [frozenset({DATA_AXIS})] * 3
    jaxpr = str(jax.make_jaxpr(sharded)(y, scale, g))
    assert jaxpr.count("pallas_call") == 2 and "check_vma=True" in jaxpr


@pytest.mark.parametrize("name, heads", [("q", 32), ("k", 4)])
def test_norm_rotary_lowers_for_the_tpu_at_the_cells_shapes_forward_and_backward(name, heads):
    """`[32, 512, heads * 128]`, float32 in and bfloat16 out, exported for the
    TPU platform from the CPU: two Mosaic kernels, each under its own name in a
    trace."""
    y = jax.ShapeDtypeStruct((32, 512, heads * 128), jnp.float32)
    g = jax.ShapeDtypeStruct(y.shape, jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32)

    def both(y, scale, g):
        o, vjp = jax.vjp(lambda y, s: pa.norm_rotary(y, s, dtype=g.dtype, theta=THETA, eps=EPS),
                         y, scale)
        return (o, *vjp(g))

    exported = jax.export.export(jax.jit(both), platforms=["tpu"])(y, scale, g)
    text = exported.mlir_module()
    assert text.count("tpu_custom_call") == 2
    assert text.count('kernel_name = "qk_norm_rotary"') == 1
    assert text.count('kernel_name = "qk_norm_rotary_bwd"') == 1
    assert [(x.shape, x.dtype) for x in exported.out_avals] == [
        (y.shape, g.dtype), (y.shape, y.dtype), ((128,), jnp.float32)]


# sha256 of `Attention`'s lowered forward-and-backward program at `sdar_tiny`'s
# sizes on this backend, printed without locations, read on the parent of ISSUE
# 30 (commit 4221496): the einsum path is the oracle and did not move. Whoever
# changes `RMSNorm`, `rotary`, `einsum_attention` or the order `Attention` calls
# them in changes this on purpose, and says so.
TINY_ATTENTION_SHA256 = "37b6ee1a844e7875383de51d1c99220169b27f9d037fc8fc24c2bc74a5f50bdf"


def test_this_backend_prepares_q_and_k_in_xla_and_its_program_is_the_parents():
    assert pa.attention_plan(512, 128, 4)["qk_prep"] == "xla"          # the tests' CPU
    module = sdar.Attention(TINY["heads"], TINY["kv_heads"], TINY["head_dim"],
                            TINY["block_length"], TINY["rope_theta"], TINY["eps"])
    h = jnp.zeros((2, 16, TINY["hidden"]))
    params = jax.eval_shape(lambda: module.init(jax.random.key(0), h))

    def both(params, h, ct):
        out, vjp = jax.vjp(module.apply, params, h)
        return (out, *vjp(ct))

    text = jax.jit(both).lower(params, h, h).as_text()
    assert "pallas" not in text and "custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == TINY_ATTENTION_SHA256


# -- ISSUE 31: the kernels at a looped encoder's shapes ----------------------------------
# `block_length` 1 (a plain causal mask), as many key/value heads as query heads
# (a group of one) and rotary WITHOUT the per-head norm: what `models/ouro.py`
# asks of `block_causal_attention` and `norm_rotary(y, None, ...)`.


def _causal_inputs(dtype, b=1, length=256, heads=16, seed=4):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (b, length, heads, 128), jnp.float32).astype(dtype) for k in keys]


def _rotary_attention(which, q, k, v, g, dtype):
    """q and k as the projections hand them over (float32), through rotary and
    the cast and causal attention over 16 heads and 16 key/value heads."""
    def kernels(q, k, v):
        q, k = (pa.norm_rotary(flat(x), None, dtype=dtype, theta=THETA, head_dim=128,
                               interpret=True) for x in (q, k))
        return pa.block_causal_attention(q, k, flat(v), heads=16, kv_heads=16, block_length=1,
                                         interpret=True).reshape(v.shape)

    def einsums(q, k, v):
        q, k = (sdar.rotary(x.astype(jnp.float32), THETA).astype(dtype) for x in (q, k))
        return sdar.einsum_attention(q, k, v, 1)

    o, vjp = jax.vjp({"kernels": kernels, "einsums": einsums}[which], q, k, v)
    return (o, *vjp(g))


_ROTARY: dict = {}


def rotary_results(dtype):
    if dtype not in _ROTARY:
        q, k, v, g = _causal_inputs(jnp.dtype(dtype))
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        runs = [jax.jit(_rotary_attention, static_argnums=(0, 5))(which, q, k, v, g, jnp.dtype(dtype))
                for which in ("kernels", "einsums")]
        _ROTARY[dtype] = [[np.asarray(x, np.float64) for x in run] for run in runs]
    return _ROTARY[dtype]


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_16_over_16_heads_with_rotary_without_norm_agrees_with_the_einsum_oracle(dtype, which):
    """Interpret mode, forward and gradients, against `rotary` +
    `einsum_attention` at `block_length` 1."""
    i = ["o", "dq", "dk", "dv"].index(which)
    got, want = (run[i] for run in rotary_results(dtype))
    assert got.shape == want.shape and np.isfinite(got).all() and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max()


def test_rotary_without_norm_keeps_nothing_and_is_its_own_kernel_name():
    """The same kernel body with the norm switched off: no scale, no gradient
    for one, no residual of y (rotary is linear), and the trace names it apart."""
    y = jax.random.normal(jax.random.key(1), (2, 128, 256), jnp.float32)
    g = jax.random.normal(jax.random.key(2), y.shape).astype(jnp.bfloat16)

    def alone(y):
        return pa.norm_rotary(y, None, dtype=jnp.bfloat16, theta=THETA, head_dim=128, interpret=True)

    o, vjp = jax.vjp(alone, y)
    (dx,) = vjp(g)
    want = sdar.rotary(y.reshape(2, 128, 2, 128), THETA).reshape(y.shape)
    assert o.dtype == jnp.bfloat16 and dx.dtype == y.dtype
    np.testing.assert_allclose(np.asarray(o, np.float32), want, atol=2 ** -7 * float(jnp.abs(want).max()))
    # rotary is a rotation: its transpose undoes it
    back = sdar.rotary(np.asarray(dx, np.float32).reshape(2, 128, 2, 128), THETA).reshape(y.shape)
    np.testing.assert_allclose(back, np.asarray(g, np.float32), atol=2 ** -6 * float(jnp.abs(back).max()))
    # the backward pass keeps no copy of y: its residuals hold no array of y's size
    residuals = jax.tree.leaves(jax.vjp(alone, y)[1])
    assert all(r.size < y.size for r in residuals if hasattr(r, "size"))
    # a norm of ones with eps 0 on unit-size heads is the same numbers, through the other switch
    unit = y / jnp.sqrt(jnp.mean(jnp.square(y.reshape(2, 128, 2, 128)), -1, keepdims=True)).repeat(128, -1).reshape(y.shape)
    normed = pa.norm_rotary(unit, jnp.ones((128,)), dtype=jnp.float32, theta=THETA, eps=0.0, interpret=True)
    plain = pa.norm_rotary(unit, None, dtype=jnp.float32, theta=THETA, head_dim=128, interpret=True)
    np.testing.assert_allclose(normed, plain, rtol=2e-6, atol=2e-6)


def test_the_looped_cells_shapes_lower_for_the_tpu_forward_and_backward():
    """`[16, 512, 16 * 128]`, q and k float32 in and bfloat16 out of rotary, a
    group of one: exported for the TPU platform from the CPU, four Mosaic
    kernels under their own names."""
    y = jax.ShapeDtypeStruct((16, 512, 16 * 128), jnp.float32)
    v = g = jax.ShapeDtypeStruct(y.shape, jnp.bfloat16)

    def both(q, k, v, g):
        def layer(q, k, v):
            q, k = (pa.norm_rotary(x, None, dtype=g.dtype, theta=THETA, head_dim=128) for x in (q, k))
            return pa.block_causal_attention(q, k, v, heads=16, kv_heads=16, block_length=1)

        o, vjp = jax.vjp(layer, q, k, v)
        return (o, *vjp(g))

    exported = jax.export.export(jax.jit(both), platforms=["tpu"])(y, y, v, g)
    text = exported.mlir_module()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert sorted(set(names)) == ["_bwd_kernel", "_fwd_kernel", "qk_rotary", "qk_rotary_bwd"]
    assert "qk_norm_rotary" not in text
    assert [(x.shape, x.dtype) for x in exported.out_avals] == [
        (y.shape, g.dtype), (y.shape, y.dtype), (y.shape, y.dtype), (y.shape, g.dtype)]


# -- what a rematerialised caller keeps of the tiled pair (ISSUE 34) ------------------


def _pallas_calls(jaxpr, name):
    """Mosaic calls of the kernel `name` in a jaxpr and every jaxpr inside it."""
    here = sum(eqn.params["name"] == name for eqn in jaxpr.eqns
               if eqn.primitive.name == "pallas_call")
    return here + sum(_pallas_calls(sub, name) for eqn in jaxpr.eqns
                      for sub in jax.core.jaxprs_in_params(eqn.params))


def test_a_checkpointed_masked_attention_keeps_its_results_by_name_and_runs_one_forward():
    """Under `save_only_these_names` of the tiled pair's names the gradient of a
    `jax.checkpoint`ed call holds ONE forward kernel where a plain
    `jax.checkpoint` holds two (the second makes `o` and the log-sum-exp again
    for the backward kernel), and dq, dk, dv are the same bits; without a policy
    the names change nothing."""
    b, length, heads, kv, dim = 1, 256, 8, 2, 128
    q, k, v, g = (jax.random.normal(jax.random.key(i), (b, length, n * dim)) for i, n in
                  enumerate((heads, kv, kv, heads)))
    live = jnp.tril(jnp.ones((length, length), jnp.int8))[None]

    def loss(q, k, v):     # the product stands for what a layer does before the kernel
        return jnp.sum(pa.masked_attention(q * 1.5, k, v, live, heads=heads, kv_heads=kv,
                                           interpret=True) * g)

    policy = jax.checkpoint_policies.save_only_these_names(pa.KEPT_OUT, pa.KEPT_LSE)
    grads, forwards = {}, {}
    for label, f in (("kept", jax.checkpoint(loss, policy=policy)),
                     ("plain", jax.checkpoint(loss)), ("whole", loss)):
        grad = jax.grad(f, (0, 1, 2))
        jaxpr = jax.make_jaxpr(grad)(q, k, v).jaxpr
        assert _pallas_calls(jaxpr, "masked_attention_bwd") == 1, label
        forwards[label], grads[label] = _pallas_calls(jaxpr, "masked_attention_fwd"), grad(q, k, v)
    assert forwards == {"kept": 1, "plain": 2, "whole": 1}
    for other in ("plain", "whole"):
        for a, b_ in zip(grads["kept"], grads[other]):
            np.testing.assert_array_equal(a, b_)


# -- the whole-row pair in the token cells' steps ----------------------------------------

WHOLE_ROW = ("_fwd_kernel", "_bwd_kernel", "qk_norm_rotary", "qk_norm_rotary_bwd", "qk_rotary",
             "qk_rotary_bwd")


def _public_entries(arch, batch, length):
    """A layer's whole-row calls through the module's public entries at an
    encoder's shapes, exported for the TPU platform: the key encoder's forward
    (no log-sum-exp) and the query encoder's forward and transpose, q and k from
    float32 projections through `norm_rotary` (with the encoder's per-head norm
    where it has one)."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.collectives import device_local
    from moco_tpu.parallel.mesh import DATA_AXIS, create_mesh

    z = models.token_sizes(arch)
    heads, kv, dim, norm = z["heads"], z["kv_heads"], z["head_dim"], z.get("qk_norm", True)
    q, g = (jax.ShapeDtypeStruct((batch, length, heads * dim), d) for d in (jnp.float32, jnp.bfloat16))
    k = jax.ShapeDtypeStruct((batch, length, kv * dim), jnp.float32)
    v = jax.ShapeDtypeStruct(k.shape, jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((dim,), jnp.float32)

    def layer(q, k, v, scale_q, scale_k):
        q, k = (pa.norm_rotary(x, s if norm else None, dtype=v.dtype, theta=z["rope_theta"],
                               eps=z["eps"], head_dim=dim) for x, s in ((q, scale_q), (k, scale_k)))
        return pa.block_causal_attention(q, k, v, heads=heads, kv_heads=kv,
                                         block_length=z["block_length"])

    def both(q, k, v, scale_q, scale_k, g):
        # the step differentiates each device's own view of the parameters
        scale_q, scale_k = device_local((scale_q, scale_k), DATA_AXIS)
        o, vjp = jax.vjp(layer, q, k, v, scale_q, scale_k)
        return layer(q, k, v, scale_q, scale_k), o, vjp(g)

    # inside a one-device `shard_map`, as the step calls them: the calls' values
    # vary over the data axis there, which the lowering of a body sees
    rows, whole = P(DATA_AXIS), P()
    region = jax.shard_map(both, mesh=create_mesh(1), in_specs=(rows, rows, rows, whole, whole, rows),
                           out_specs=(rows, rows, (rows, rows, rows, rows, rows)))
    return jax.export.export(jax.jit(region), platforms=["tpu"])(q, k, v, scale, scale, g).mlir_module()


@pytest.mark.parametrize("cell", ["sdar-30b-a3b-ep8", "ouro-2.6b-l6"])
def test_the_token_cells_steps_hold_the_whole_row_kernels_of_the_public_entries(cell):
    """The routed and the looped cell's step programs, lowered for the TPU from
    the CPU, hold the whole-row kernels (`block_causal_attention`, `norm_rotary`)
    and no other attention kernel, and each body (printed without its call
    sites' locations) is one that a call of the module's public entries at the
    same shapes makes: nothing the tiled pair's forward does reaches them."""
    from moco_tpu.parallel.mesh import create_mesh
    from step_lowering import cell_config, kernel_bodies, lowered_for_tpu

    config = cell_config(cell, batch_size=8)
    step = kernel_bodies(lowered_for_tpu(config, create_mesh(1)))
    alone = kernel_bodies(_public_entries(config.arch, 8, config.seq_len))
    assert set(alone) <= set(WHOLE_ROW) and not {"masked_attention_fwd", "masked_attention_bwd"} & set(step)
    assert {name: step[name] for name in step if name in WHOLE_ROW} == alone
    # two forward bodies, with and without the log-sum-exp; q's and k's blocks of norm_rotary
    assert len(alone["_fwd_kernel"]) == 2 and len(alone["_bwd_kernel"]) == 1
