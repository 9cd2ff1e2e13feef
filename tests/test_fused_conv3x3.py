"""Equivalence pins for the fused bn→relu→3x3-conv (stride 1) interior
fusion (ops/pallas_fused_conv3x3.py + models/fused_block.py).

Same proof ladder as the 1x1 tail (test_fused_conv.py): interpret-mode
kernel equivalence (incl. batch boundaries — zero padding must happen at
IMAGE edges, never leak across the folded batch), custom-VJP vs autodiff,
and hardware-free TPU (Mosaic) lowering at the real R50 conv2 shapes.
The module-level integration (param-tree identity, grads, running stats,
shard_map composition) is covered by test_fused_conv.py's Bottleneck tests,
which exercise BOTH fusions on stride-1 blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.models.fused_block import _bn_relu_conv3x3_train
from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3, conv3x3_dw


def _ref(x, a, b, w):
    z = jnp.maximum(x.astype(jnp.float32) * a + b, 0.0)
    return jax.lax.conv_general_dilated(
        z, w.astype(jnp.float32), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@pytest.mark.parametrize(
    "shape", [(2, 8, 8, 16, 32), (3, 12, 10, 8, 16), (1, 4, 16, 32, 8)]
)
def test_kernel_matches_conv_interpret(shape):
    bsz, h, w_, k, n = shape
    x = jax.random.normal(jax.random.key(0), (bsz, h, w_, k), jnp.float32)
    a = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (k,))
    b = 0.1 * jax.random.normal(jax.random.key(2), (k,))
    w = 0.1 * jax.random.normal(jax.random.key(3), (3, 3, k, n))
    got = bn_relu_conv3x3(x, a, b, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref(x, a, b, w)), rtol=1e-4, atol=1e-4
    )


def test_batch_boundary_no_halo_leak_interpret():
    """Two images whose edge rows are wildly different: each image's output
    must equal its own single-image conv — any halo leak across the folded
    batch dimension shows up immediately."""
    k, n = 8, 8
    x0 = jnp.full((1, 4, 4, k), 100.0, jnp.float32)
    x1 = jnp.full((1, 4, 4, k), -100.0, jnp.float32)
    a = jnp.ones((k,))
    b = jnp.zeros((k,))
    w = 0.1 * jax.random.normal(jax.random.key(4), (3, 3, k, n))
    both = bn_relu_conv3x3(
        jnp.concatenate([x0, x1]), a, b, w, out_dtype=jnp.float32,
        interpret=True,
    )
    solo0 = bn_relu_conv3x3(x0, a, b, w, out_dtype=jnp.float32, interpret=True)
    solo1 = bn_relu_conv3x3(x1, a, b, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(solo0[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(both[1]), np.asarray(solo1[0]),
                               rtol=1e-5, atol=1e-5)


def _ref_s2(x, a, b, w):
    z = jnp.maximum(x.astype(jnp.float32) * a + b, 0.0)
    return jax.lax.conv_general_dilated(
        z, w.astype(jnp.float32), (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@pytest.mark.parametrize(
    "shape", [(2, 8, 8, 16, 32), (3, 12, 10, 8, 16), (1, 4, 16, 32, 8)]
)
def test_s2_kernel_matches_conv_interpret(shape):
    from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3_s2

    bsz, h, w_, k, n = shape
    x = jax.random.normal(jax.random.key(40), (bsz, h, w_, k), jnp.float32)
    a = 1.0 + 0.1 * jax.random.normal(jax.random.key(41), (k,))
    b = 0.1 * jax.random.normal(jax.random.key(42), (k,))
    w = 0.1 * jax.random.normal(jax.random.key(43), (3, 3, k, n))
    got = bn_relu_conv3x3_s2(x, a, b, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref_s2(x, a, b, w)), rtol=1e-4, atol=1e-4
    )


def test_s2_batch_boundary_no_halo_leak_interpret():
    """Stride-2 variant of the halo-leak probe: the di=-1 taps of each
    image's first output row must read PADDING (zero), not the previous
    image's last row."""
    from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3_s2

    k, n = 8, 8
    x0 = jnp.full((1, 4, 4, k), 100.0, jnp.float32)
    x1 = jnp.full((1, 4, 4, k), -100.0, jnp.float32)
    a = jnp.ones((k,))
    b = jnp.zeros((k,))
    w = 0.1 * jax.random.normal(jax.random.key(44), (3, 3, k, n))
    both = bn_relu_conv3x3_s2(
        jnp.concatenate([x0, x1]), a, b, w, out_dtype=jnp.float32,
        interpret=True,
    )
    for i, xi in enumerate((x0, x1)):
        solo = bn_relu_conv3x3_s2(xi, a, b, w, out_dtype=jnp.float32,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(both[i]), np.asarray(solo[0]),
                                   rtol=1e-5, atol=1e-5)


def test_s2_custom_vjp_matches_autodiff():
    from moco_tpu.models.fused_block import _bn_relu_conv3x3s2_train

    eps = 1e-5
    x = jax.random.normal(jax.random.key(46), (2, 8, 8, 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(47), (16,))
    bias = 0.1 * jax.random.normal(jax.random.key(48), (16,))
    w = 0.1 * jax.random.normal(jax.random.key(49), (3, 3, 16, 8))

    def unfused(x, scale, bias, w):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean
        z = jnp.maximum(
            (xf - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias, 0.0
        )
        return jax.lax.conv_general_dilated(
            z, w, (2, 2), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def loss_fused(args):
        y, _, _ = _bn_relu_conv3x3s2_train(*args, eps, jnp.float32)
        return jnp.sum(y * jnp.sin(y))

    def loss_ref(args):
        return jnp.sum(unfused(*args) * jnp.sin(unfused(*args)))

    args = (x, scale, bias, w)
    lf, gf = jax.value_and_grad(loss_fused)(args)
    lr_, gr = jax.value_and_grad(loss_ref)(args)
    np.testing.assert_allclose(float(lf), float(lr_), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(gf), jax.tree.leaves(gr), strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-4
        )


def test_s2_kernel_lowers_for_tpu_at_r50_shapes():
    from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3_s2

    # the three stage-first conv2 sites of R50@224
    for (bsz, h, w_, k) in [(128, 56, 56, 128), (128, 28, 28, 256),
                            (128, 14, 14, 512)]:
        x = jax.ShapeDtypeStruct((bsz, h, w_, k), jnp.bfloat16)
        a = jax.ShapeDtypeStruct((k,), jnp.float32)
        b = jax.ShapeDtypeStruct((k,), jnp.float32)
        w = jax.ShapeDtypeStruct((3, 3, k, k), jnp.bfloat16)
        fn = lambda x, a, b, w: bn_relu_conv3x3_s2(x, a, b, w,
                                                   out_dtype=jnp.bfloat16)
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x, a, b, w)
        assert "tpu_custom_call" in exp.mlir_module(), (bsz, h, w_, k)


@pytest.mark.parametrize(
    "shape", [(2, 8, 8, 16, 32), (3, 12, 10, 8, 16), (1, 4, 16, 32, 8)]
)
def test_dw_kernel_matches_conv_filter_grad_interpret(shape):
    """conv3x3_dw == autodiff's filter gradient of relu(x·a+b) ⊛ w."""
    bsz, h, w_, k, n = shape
    x = jax.random.normal(jax.random.key(10), (bsz, h, w_, k), jnp.float32)
    a = 1.0 + 0.1 * jax.random.normal(jax.random.key(11), (k,))
    b = 0.1 * jax.random.normal(jax.random.key(12), (k,))
    w = 0.1 * jax.random.normal(jax.random.key(13), (3, 3, k, n))
    dy = jax.random.normal(jax.random.key(14), (bsz, h, w_, n), jnp.float32)
    _, vjp = jax.vjp(lambda w_: _ref(x, a, b, w_), w)
    (want,) = vjp(dy)
    got = conv3x3_dw(x, a, b, dy, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_dw_kernel_batch_boundary_no_halo_leak_interpret():
    """The tap gradients must pair z and dy WITHIN an image only — summing
    per-image filter grads of wildly different images equals the batched
    call iff no halo leaks across the folded batch dimension."""
    k, n = 8, 8
    x0 = jax.random.normal(jax.random.key(15), (1, 4, 4, k)) * 100.0
    x1 = -x0 + jax.random.normal(jax.random.key(16), (1, 4, 4, k))
    a = jnp.ones((k,))
    b = jnp.zeros((k,))
    dy = jax.random.normal(jax.random.key(17), (2, 4, 4, n), jnp.float32)
    both = conv3x3_dw(jnp.concatenate([x0, x1]), a, b, dy, interpret=True)
    solo = (conv3x3_dw(x0, a, b, dy[:1], interpret=True)
            + conv3x3_dw(x1, a, b, dy[1:], interpret=True))
    np.testing.assert_allclose(np.asarray(both), np.asarray(solo),
                               rtol=1e-5, atol=1e-4)


def test_dw_kernel_lowers_for_tpu_at_r50_shapes():
    for (bsz, h, w_, k) in [
        (128, 56, 56, 64), (128, 28, 28, 128),
        (128, 14, 14, 256), (128, 7, 7, 512),
    ]:
        x = jax.ShapeDtypeStruct((bsz, h, w_, k), jnp.bfloat16)
        a = jax.ShapeDtypeStruct((k,), jnp.float32)
        b = jax.ShapeDtypeStruct((k,), jnp.float32)
        dy = jax.ShapeDtypeStruct((bsz, h, w_, k), jnp.bfloat16)
        fn = lambda x, a, b, dy: conv3x3_dw(x, a, b, dy)
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x, a, b, dy)
        assert "tpu_custom_call" in exp.mlir_module(), (bsz, h, w_, k)


def test_custom_vjp_matches_autodiff():
    eps = 1e-5
    x = jax.random.normal(jax.random.key(6), (2, 6, 6, 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(7), (16,))
    bias = 0.1 * jax.random.normal(jax.random.key(8), (16,))
    w = 0.1 * jax.random.normal(jax.random.key(9), (3, 3, 16, 8))

    def unfused(x, scale, bias, w):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean
        z = jnp.maximum(
            (xf - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias, 0.0
        )
        return jax.lax.conv_general_dilated(
            z, w, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def loss_fused(args):
        y, _, _ = _bn_relu_conv3x3_train(*args, eps, jnp.float32)
        return jnp.sum(y * jnp.sin(y))

    def loss_ref(args):
        y = unfused(*args)
        return jnp.sum(y * jnp.sin(y))

    args = (x, scale, bias, w)
    lf, gf = jax.value_and_grad(loss_fused)(args)
    lr_, gr = jax.value_and_grad(loss_ref)(args)
    np.testing.assert_allclose(float(lf), float(lr_), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(gf), jax.tree.leaves(gr), strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-4
        )


def test_kernel_lowers_for_tpu_at_r50_shapes():
    for (bsz, h, w_, k) in [
        (128, 56, 56, 64), (128, 28, 28, 128),
        (128, 14, 14, 256), (128, 7, 7, 512),
    ]:
        x = jax.ShapeDtypeStruct((bsz, h, w_, k), jnp.bfloat16)
        a = jax.ShapeDtypeStruct((k,), jnp.float32)
        b = jax.ShapeDtypeStruct((k,), jnp.float32)
        w = jax.ShapeDtypeStruct((3, 3, k, k), jnp.bfloat16)
        fn = lambda x, a, b, w: bn_relu_conv3x3(x, a, b, w, out_dtype=jnp.bfloat16)
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x, a, b, w)
        assert "tpu_custom_call" in exp.mlir_module(), (bsz, h, w_, k)


@pytest.mark.parametrize("train", [True, False])
def test_bottleneck_stride2_fused_equivalent(train):
    """The stage-first (stride-2) Bottleneck with fused_tail: identical
    param/stat tree, matching outputs/grads/running stats vs unfused —
    the r4 fusion site (previously these blocks kept the unfused path)."""
    from functools import partial

    import flax.linen as nn

    from moco_tpu.models.resnet import Bottleneck

    conv = partial(nn.Conv, use_bias=False, dtype=jnp.float32,
                   param_dtype=jnp.float32)
    norm = partial(nn.BatchNorm, use_running_average=not train, momentum=0.9,
                   epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32)
    kw = dict(filters=8, strides=2, conv=conv, norm=norm)
    plain = Bottleneck(**kw)
    fused = Bottleneck(fused_tail=True, bn_momentum=0.9, dtype=jnp.float32,
                       **kw)
    x = jax.random.normal(jax.random.key(50), (2, 8, 8, 16), jnp.float32)
    v = plain.init(jax.random.key(51), x)
    v2 = fused.init(jax.random.key(51), x)
    assert jax.tree.structure(v) == jax.tree.structure(v2)

    if train:
        out_a, mut_a = plain.apply(v, x, mutable=["batch_stats"])
        out_b, mut_b = fused.apply(v, x, mutable=["batch_stats"])
        for a, b_ in zip(jax.tree.leaves(mut_a), jax.tree.leaves(mut_b),
                         strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-6)

        def loss(params, model):
            out, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, mutable=["batch_stats"],
            )
            return jnp.sum(out ** 2)

        ga = jax.grad(loss)(v["params"], plain)
        gb = jax.grad(loss)(v["params"], fused)
        for (pa, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(ga),
            jax.tree_util.tree_leaves_with_path(gb),
            strict=True,
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4, err_msg=str(pa))
    else:
        out_a = plain.apply(v, x)
        out_b = fused.apply(v, x)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("train", [True, False])
def test_basicblock_fused_equivalent(train):
    """BasicBlock's bn1→relu→conv2 fusion (R18/34 path): identical
    param/stat tree, matching outputs/grads/running stats vs unfused."""
    from functools import partial

    import flax.linen as nn

    from moco_tpu.models.resnet import BasicBlock

    conv = partial(nn.Conv, use_bias=False, dtype=jnp.float32,
                   param_dtype=jnp.float32)
    norm = partial(nn.BatchNorm, use_running_average=not train, momentum=0.9,
                   epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32)
    kw = dict(filters=16, strides=1, conv=conv, norm=norm)
    plain = BasicBlock(**kw)
    fused = BasicBlock(fused_tail=True, bn_momentum=0.9, dtype=jnp.float32, **kw)
    x = jax.random.normal(jax.random.key(30), (2, 8, 8, 16), jnp.float32)
    v = plain.init(jax.random.key(31), x)
    v2 = fused.init(jax.random.key(31), x)
    assert jax.tree.structure(v) == jax.tree.structure(v2)

    if train:
        out_a, mut_a = plain.apply(v, x, mutable=["batch_stats"])
        out_b, mut_b = fused.apply(v, x, mutable=["batch_stats"])
        for a, b_ in zip(jax.tree.leaves(mut_a), jax.tree.leaves(mut_b),
                         strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-6)

        def loss(params, model):
            out, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, mutable=["batch_stats"],
            )
            return jnp.sum(out ** 2)

        ga = jax.grad(loss)(v["params"], plain)
        gb = jax.grad(loss)(v["params"], fused)
        for (pa, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(ga),
            jax.tree_util.tree_leaves_with_path(gb),
            strict=True,
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4, err_msg=str(pa))
    else:
        out_a = plain.apply(v, x)
        out_b = fused.apply(v, x)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)
