"""Equivalence pins for the fused bn→relu→1x1-conv tail
(ops/pallas_fused_conv.py + models/fused_block.py; VERDICT r2 #2 lever).

Three layers of proof, all CPU-runnable:
1. the Pallas kernel (interpret mode) against the plain jnp math;
2. the custom VJP (closed-form BN chain + recomputed-z matmuls) against
   autodiff of the unfused composition;
3. the Bottleneck module with `fused_tail=True`: identical param/stat tree
   and matching outputs/grads/running-stat updates vs the unfused block.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.models.fused_block import _bn_relu_conv_train
from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul


def _ref_math(x, a, b, w):
    z = jnp.maximum(x.astype(jnp.float32) * a + b, 0.0)
    return z @ w.astype(jnp.float32)


def test_kernel_matches_reference_interpret():
    key = jax.random.key(0)
    m, k, n = 128, 64, 256
    x = jax.random.normal(jax.random.key(1), (m, k), jnp.float32)
    a = jax.random.normal(jax.random.key(2), (k,)) * 0.5 + 1.0
    b = jax.random.normal(jax.random.key(3), (k,)) * 0.1
    w = jax.random.normal(key, (k, n)) * 0.05
    got = bn_relu_matmul(x, a, b, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref_math(x, a, b, w)), rtol=1e-5, atol=1e-5
    )


def test_kernel_ragged_tiles_interpret():
    """Tile pickers must handle non-power-of-two dims (fall back to full)."""
    x = jax.random.normal(jax.random.key(4), (96, 24), jnp.float32)
    a = jnp.ones((24,))
    b = jnp.zeros((24,))
    w = jax.random.normal(jax.random.key(5), (24, 40)) * 0.1
    got = bn_relu_matmul(x, a, b, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref_math(x, a, b, w)), rtol=1e-5, atol=1e-5
    )


def test_custom_vjp_matches_autodiff():
    """The closed-form backward (BN chain + recomputed-z matmuls) equals
    autodiff of the unfused normalize→relu→conv composition."""
    eps = 1e-5
    x = jax.random.normal(jax.random.key(6), (4, 6, 6, 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(7), (16,))
    bias = 0.1 * jax.random.normal(jax.random.key(8), (16,))
    w = 0.1 * jax.random.normal(jax.random.key(9), (1, 1, 16, 32))

    def unfused(x, scale, bias, w):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean
        z = nn.relu((xf - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias)
        return jax.lax.conv_general_dilated(
            z, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    def loss_fused(args):
        y, _, _ = _bn_relu_conv_train(*args, eps, jnp.float32)
        return jnp.sum(y * jnp.cos(y))  # non-trivial cotangent

    def loss_ref(args):
        y = unfused(*args)
        return jnp.sum(y * jnp.cos(y))

    args = (x, scale, bias, w)
    lf, gf = jax.value_and_grad(loss_fused)(args)
    lr_, gr = jax.value_and_grad(loss_ref)(args)
    np.testing.assert_allclose(float(lf), float(lr_), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(gf), jax.tree.leaves(gr), strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize("train", [True, False])
def test_bottleneck_fused_tail_equivalent(train):
    """Same param/stat tree, same outputs, same grads, same running-stat
    updates as the unfused Bottleneck (CPU: plain fwd + closed-form bwd)."""
    from moco_tpu.models.resnet import Bottleneck

    from functools import partial

    conv = partial(nn.Conv, use_bias=False, dtype=jnp.float32,
                   param_dtype=jnp.float32)
    norm = partial(nn.BatchNorm, use_running_average=not train, momentum=0.9,
                   epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32)
    kw = dict(filters=8, strides=1, conv=conv, norm=norm)
    plain = Bottleneck(**kw)
    fused = Bottleneck(fused_tail=True, bn_momentum=0.9, dtype=jnp.float32, **kw)
    x = jax.random.normal(jax.random.key(10), (2, 8, 8, 32), jnp.float32)
    v = plain.init(jax.random.key(11), x)
    v2 = fused.init(jax.random.key(11), x)
    assert jax.tree.structure(v) == jax.tree.structure(v2)
    for (pa, la), (pb, lb) in zip(
        jax.tree_util.tree_leaves_with_path(v),
        jax.tree_util.tree_leaves_with_path(v2),
        strict=True,
    ):
        assert la.shape == lb.shape, (pa, la.shape, lb.shape)

    if train:
        out_a, mut_a = plain.apply(v, x, mutable=["batch_stats"])
        out_b, mut_b = fused.apply(v, x, mutable=["batch_stats"])
        for a, b_ in zip(
            jax.tree.leaves(mut_a), jax.tree.leaves(mut_b), strict=True
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-6)
    else:
        out_a = plain.apply(v, x)
        out_b = fused.apply(v, x)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)

    if train:
        def loss(params, model):
            out, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, mutable=["batch_stats"],
            )
            return jnp.sum(out ** 2)

        ga = jax.grad(loss)(v["params"], plain)
        gb = jax.grad(loss)(v["params"], fused)
        for (pa, a), (pb, b_) in zip(
            jax.tree_util.tree_leaves_with_path(ga),
            jax.tree_util.tree_leaves_with_path(gb),
            strict=True,
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-4,
                err_msg=str(pa),
            )


def test_fused_tail_inside_shard_map_step(mesh8):
    """The fused custom-VJP tail composes with the full hybrid jit/shard_map
    v2 training step (manual params + running-stat updates + donation +
    pmean'd grads). The backend gate is patched so the fused DECLARATION
    path runs here with the jnp fallback math (the Pallas lowering itself is
    TPU-only and covered by interpret-mode tests above)."""
    import unittest.mock as mock

    import moco_tpu.models.fast_bn as fbn
    import moco_tpu.models.fused_block as fb
    from moco_tpu.config import PretrainConfig
    from moco_tpu.models.resnet import Bottleneck, ResNet
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_optimizer, build_train_step

    B, IMG, DIM, K = 16, 16, 16, 64
    config = PretrainConfig(variant="v1", arch="resnet_tiny", cifar_stem=True,
                            num_negatives=K, embed_dim=DIM, batch_size=B, lr=0.1)
    model = ResNet(stage_sizes=(1, 1), block_cls=Bottleneck, width=8,
                   num_classes=DIM, cifar_stem=True, fused_bn_conv=True)
    tx, sched = build_optimizer(config, 8)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
         mock.patch.object(fb, "_use_pallas", lambda: False), \
         mock.patch.object(fbn, "_use_pallas", lambda: False):
        state = create_train_state(
            jax.random.key(0), model, tx, (2, IMG, IMG, 3), K, DIM
        )
        step = build_train_step(config, model, tx, mesh8, 8, sched)
        im_q = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
        im_k = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
        state, metrics = step(state, im_q, im_k)
        state, metrics = step(state, im_q, im_k)
    assert np.isfinite(float(metrics["loss"]))
    # the fused tail's running stats live exactly where bn2's would
    assert "bn2" in state.batch_stats_q["layer1_0"]
    assert int(state.step) == 2


def test_kernel_lowers_for_tpu_at_r50_shapes():
    """Cross-platform export compiles the Pallas kernel to Mosaic IR (the
    stage where block/tile errors surface) for every R50 bottleneck-tail
    shape at per-chip batch 128 — hardware-free assurance that the TPU path
    will build (the Mosaic→binary stage only a chip run covers:
    `chip_smoke.py`)."""
    shapes = [
        (128 * 56 * 56, 64, 256),
        (128 * 28 * 28, 128, 512),
        (128 * 14 * 14, 256, 1024),
        (128 * 7 * 7, 512, 2048),
    ]
    for m, k, n in shapes:
        x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
        a = jax.ShapeDtypeStruct((k,), jnp.float32)
        b = jax.ShapeDtypeStruct((k,), jnp.float32)
        w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
        fn = lambda x, a, b, w: bn_relu_matmul(x, a, b, w, out_dtype=jnp.bfloat16)
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x, a, b, w)
        mod = exp.mlir_module()
        assert "tpu_custom_call" in mod or "mosaic" in mod.lower(), (m, k, n)


def test_full_benchmark_step_lowers_for_tpu():
    """The SHIPPING-DEFAULT benchmark program — `imagenet-moco-v2` at
    ResNet-50 / 224² / K=65536 / bf16 / batch 128 on a 1-device mesh: uint8
    ImageFolder staging canvas → two-crop bf16 augmentation (Pallas blur)
    → both R50 forwards → backward → SGD → donated queue update — exports
    for the TPU platform from CPU. This is the program the first command
    sent to a chip runs; a tracing or typing break in it (the BN custom-VJP
    vma mismatch of PR 21) fails here, in tier-1, not on the chip. The one
    Mosaic kernel on the default path must be there, not interpreted."""
    import re
    import unittest.mock as mock
    from collections import Counter

    from moco_tpu.config import get_preset
    from moco_tpu.data.augment import (
        aug_config_for, build_two_crops_sharded, with_dtype,
    )
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import (
        build_encoder, build_fused_step, build_optimizer, build_train_step,
    )

    B = 128
    config = get_preset("imagenet-moco-v2").replace(batch_size=B)
    assert not config.fused_bn_conv  # the default program, not a candidate
    mesh = create_mesh(1)
    # the blur gate and its interpret flag key on the backend; everything
    # else in the step is the same traced program on CPU and TPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = build_encoder(config)
        tx, sched = build_optimizer(config, 1000)
        state = jax.eval_shape(lambda: create_train_state(
            jax.random.key(0), model, tx, (B, 224, 224, 3),
            config.num_negatives, config.embed_dim))
        step_fn = build_train_step(config, model, tx, mesh, 1000, sched)
        two = build_two_crops_sharded(
            with_dtype(aug_config_for(config), config.compute_dtype), mesh)
        fused = build_fused_step(step_fn, two, jax.random.key(1))
        exp = jax.export.export(fused, platforms=["tpu"])(
            state,
            jax.ShapeDtypeStruct((B, 512, 1024, 3), jnp.uint8),
            jax.ShapeDtypeStruct((B, 3), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
    mod = exp.mlir_module()
    names = Counter(re.findall(r'kernel_name = "([^"]+)"', mod))
    assert names == {"_blur_kernel": 1}, names
    assert mod.count("tpu_custom_call") == 1


def test_dw_kernel_matches_reference_interpret():
    """The backward twin: dW = relu(x·a+b)ᵀ @ dy, ẑ recomputed in VMEM."""
    from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul_dw

    x = jax.random.normal(jax.random.key(20), (256, 64), jnp.float32)
    a = 1.0 + 0.1 * jax.random.normal(jax.random.key(21), (64,))
    b = 0.1 * jax.random.normal(jax.random.key(22), (64,))
    dy = jax.random.normal(jax.random.key(23), (256, 128), jnp.float32)
    got = bn_relu_matmul_dw(x, a, b, dy, interpret=True)
    z = jnp.maximum(x * a + b, 0.0)
    want = z.T @ dy
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_dw_kernel_lowers_for_tpu_at_r50_shapes():
    from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul_dw

    for m, k, n in [(128 * 56 * 56, 64, 256), (128 * 7 * 7, 512, 2048)]:
        x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
        a = jax.ShapeDtypeStruct((k,), jnp.float32)
        b = jax.ShapeDtypeStruct((k,), jnp.float32)
        dy = jax.ShapeDtypeStruct((m, n), jnp.bfloat16)
        exp = jax.export.export(
            jax.jit(lambda x, a, b, dy: bn_relu_matmul_dw(x, a, b, dy)),
            platforms=["tpu"],
        )(x, a, b, dy)
        assert "tpu_custom_call" in exp.mlir_module(), (m, k, n)
