"""ResNet/head structure tests. Param counts are pinned against torchvision's
published totals (the reference's backbone source) so the flax rebuild is
structurally identical: torchvision resnet18/50 with a 1000-way fc have
11,689,512 / 25,557,032 parameters; swapping fc for a 128-d head changes only
the fc term (512·128+128 / 2048·128+128)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.models import ResNet18, ResNet50, V3Predictor, V3Projector, build_resnet


def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def r18_vars():
    model = ResNet18(num_classes=128, cifar_stem=True)
    v = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)), train=False)
    return model, v


@pytest.mark.parametrize("arch, torchvision_total", [
    ("resnet18", 11_689_512),
    ("resnet34", 21_797_672),
    ("resnet50", 25_557_032),
    ("resnet101", 44_549_160),
    ("resnet152", 60_192_808),
])
def test_param_count_matches_torchvision(arch, torchvision_total):
    """Every registry entry, ImageNet stem, against torchvision's published
    total with its 1000-way fc swapped for the 128-d head."""
    from moco_tpu.models.resnet import FEATURE_DIMS

    v = jax.eval_shape(
        lambda: build_resnet(arch, num_classes=128).init(
            jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False
        )
    )
    d = FEATURE_DIMS[arch]
    assert _count(v["params"]) == torchvision_total - (d * 1000 + 1000) + (d * 128 + 128)


def test_mlp_head_param_count():
    # v2 head: Linear(2048,2048)+ReLU+Linear(2048,128) replaces Linear(2048,128)
    plain = jax.eval_shape(
        lambda: ResNet50(num_classes=128).init(
            jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False
        )
    )
    mlp = jax.eval_shape(
        lambda: ResNet50(num_classes=128, mlp_head=True).init(
            jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False
        )
    )
    assert _count(mlp["params"]) - _count(plain["params"]) == 2048 * 2048 + 2048


def test_forward_shapes_and_feature_mode(r18_vars):
    model, v = r18_vars
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    out = model.apply(v, x, train=False)
    assert out.shape == (2, 128)
    feat_model = ResNet18(num_classes=None, cifar_stem=True)
    fv = feat_model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)), train=False)
    feats = feat_model.apply(fv, x, train=False)
    assert feats.shape == (2, 512)


def test_batch_stats_update_in_train_mode(r18_vars):
    model, v = r18_vars
    x = jax.random.normal(jax.random.key(2), (4, 32, 32, 3)) * 5 + 3
    out, mutated = model.apply(v, x, train=True, mutable=["batch_stats"])
    before = jax.tree.leaves(v["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))
    # eval mode must NOT touch stats and must be deterministic
    out1 = model.apply(v, x, train=False)
    out2 = model.apply(v, x, train=False)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_bfloat16_activations_f32_params():
    model = ResNet18(num_classes=64, cifar_stem=True, dtype=jnp.bfloat16)
    v = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)), train=False)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(v["params"]))
    out = model.apply(v, jnp.zeros((2, 32, 32, 3)), train=False)
    assert out.dtype == jnp.float32  # head math promoted back to f32


def test_build_resnet_registry():
    with pytest.raises(ValueError, match="unknown arch"):
        build_resnet("resnet1337")
    m = build_resnet("resnet34", num_classes=10)
    assert m.stage_sizes == (3, 4, 6, 3)


def test_v3_heads_shapes():
    proj = V3Projector()
    pv = proj.init(jax.random.key(0), jnp.zeros((2, 384)), train=False)
    out = proj.apply(pv, jnp.ones((2, 384)), train=False)
    assert out.shape == (2, 256)
    pred = V3Predictor()
    qv = pred.init(jax.random.key(0), jnp.zeros((2, 256)), train=False)
    out2 = pred.apply(qv, out, train=False)
    assert out2.shape == (2, 256)


def test_s2d_stem_equals_plain_conv_stem():
    """The space-to-depth stem computes the SAME convolution as the plain
    7x7/2 conv (products regrouped only): same param tree, matching outputs,
    matching gradients — so checkpoints and training dynamics are unchanged
    while the MXU contracts over 12 channels instead of 3."""
    from moco_tpu.models.resnet import BasicBlock, ResNet

    kw = dict(stage_sizes=(1,), block_cls=BasicBlock, width=8, num_classes=16)
    plain = ResNet(s2d_stem=False, **kw)
    s2d = ResNet(s2d_stem=True, **kw)
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3))
    v = plain.init(jax.random.key(1), x, train=False)
    # identical param trees (s2d re-tiles at trace time, not in the params)
    v2 = s2d.init(jax.random.key(1), x, train=False)
    assert jax.tree.structure(v) == jax.tree.structure(v2)
    assert v["params"]["conv1"]["kernel"].shape == (7, 7, 3, 8)

    out_a = plain.apply(v, x, train=False)
    out_b = s2d.apply(v, x, train=False)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=2e-5, atol=2e-5)

    def loss(params, model):
        return jnp.sum(model.apply({"params": params,
                                    "batch_stats": v["batch_stats"]},
                                   x, train=False) ** 2)

    ga = jax.grad(loss)(v["params"], plain)
    gb = jax.grad(loss)(v["params"], s2d)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_s2d_stem_falls_back_on_odd_sizes():
    from moco_tpu.models.resnet import BasicBlock, ResNet

    model = ResNet(stage_sizes=(1,), block_cls=BasicBlock, width=8,
                   num_classes=16, s2d_stem=True)
    x = jnp.zeros((2, 33, 33, 3))
    v = model.init(jax.random.key(0), x, train=False)
    out = model.apply(v, x, train=False)
    assert out.shape == (2, 16)


def test_remat_blocks_identical_outputs_and_grads():
    """Per-block rematerialization is a pure memory/compute trade: the same
    ops re-executed in the backward — outputs and gradients must be
    IDENTICAL to the unrematted model (param tree included)."""
    from moco_tpu.models.resnet import BasicBlock, ResNet

    kw = dict(stage_sizes=(1, 1), block_cls=BasicBlock, width=8,
              num_classes=16, cifar_stem=True)
    plain = ResNet(remat=False, **kw)
    rm = ResNet(remat=True, **kw)
    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 3))
    v = plain.init(jax.random.key(1), x, train=False)
    assert jax.tree.structure(v) == jax.tree.structure(
        rm.init(jax.random.key(1), x, train=False)
    )
    out_a = plain.apply(v, x, train=False)
    out_b = rm.apply(v, x, train=False)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

    def loss(params, model):
        out, _ = model.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return jnp.sum(out ** 2)

    ga = jax.grad(loss)(v["params"], plain)
    gb = jax.grad(loss)(v["params"], rm)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _package_sources(*subdirs):
    import pathlib

    import moco_tpu

    root = pathlib.Path(moco_tpu.__file__).parent
    for sub in subdirs:
        for path in sorted((root / sub).rglob("*.py")):
            yield path.relative_to(root).as_posix(), path.read_text(encoding="utf-8")


def test_the_package_has_four_files_of_mosaic_kernels():
    """PERF.md's layers name them (the blur kernel's own layer; `token encoder`
    for the attention kernels and, since PR 32, the routed layer's row movers;
    since PR 33 the sparse attention, index score and selection kernels' own);
    a `pallas_call` anywhere else in the package is a new layer and says so
    there first. The latent attention kernels have a file of their own, a fifth."""
    holders = {name for name, text in _package_sources("") if "pallas_call" in text}
    assert holders == {"ops/pallas_blur.py", "ops/pallas_attention.py", "ops/pallas_dispatch.py",
                       "ops/pallas_select.py", "ops/pallas_mla.py"}


def test_no_switch_is_read_from_the_environment_in_models_and_ops():
    """What a model or an op computes follows from its arguments and the
    backend: the program a test pins is the program the chip runs."""
    readers = [name for name, text in _package_sources("models", "ops")
               if "environ" in text.replace("environment", "") or "getenv" in text]
    assert readers == []
