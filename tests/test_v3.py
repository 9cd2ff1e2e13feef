"""MoCo v3 tests: ViT structure, frozen patch embed, symmetric step on the
8-device mesh (BASELINE config 5; SURVEY §2.9/§3.5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import PretrainConfig
from moco_tpu.models.vit import ViT, sincos_2d_position_embedding
from moco_tpu.ops.ema import ema_update
from moco_tpu.train_step import build_encoder, build_optimizer, build_train_step
from moco_tpu.v3_step import (
    V3Model,
    create_v3_train_state,
    encoder_subtree,
    patch_embed_trainable_mask,
)

IMG, B = 16, 16  # 16x16 imgs, patch 8 → 2x2=4 tokens + cls


def tiny_vit(**kw):
    return ViT(patch_size=8, width=32, depth=2, num_heads=2, **kw)


def tiny_config(**kw):
    base = dict(
        variant="v3", arch="vit_small", embed_dim=16, momentum_ema=0.99,
        momentum_ramp=True, temperature=0.2, optimizer="adamw", lr=1e-3,
        weight_decay=0.1, batch_size=B, epochs=2, warmup_epochs=1,
    )
    base.update(kw)
    return PretrainConfig(**base)


def test_sincos_embedding_shape_and_determinism():
    e1 = sincos_2d_position_embedding(4, 4, 32)
    e2 = sincos_2d_position_embedding(4, 4, 32)
    assert e1.shape == (1, 16, 32)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_vit_forward_shapes():
    model = tiny_vit(num_classes=None)
    v = model.init(jax.random.key(0), jnp.zeros((2, IMG, IMG, 3)), train=False)
    out = model.apply(v, jnp.ones((2, IMG, IMG, 3)), train=False)
    assert out.shape == (2, 32)


def test_patch_embed_gets_no_gradient():
    model = tiny_vit(num_classes=16, frozen_patch_embed=True)
    v = model.init(jax.random.key(0), jnp.zeros((2, IMG, IMG, 3)), train=False)

    def loss(params):
        out = model.apply({"params": params}, jnp.ones((2, IMG, IMG, 3)), train=False)
        return jnp.sum(out**2)

    g = jax.grad(loss)(v["params"])
    np.testing.assert_array_equal(np.asarray(g["patch_embed"]["kernel"]), 0.0)
    # other layers DO get gradient
    assert float(jnp.abs(g["block0"]["mlp_fc1"]["kernel"]).max()) > 0


def test_patch_embed_mask_marks_only_patch_embed():
    model = tiny_vit(num_classes=None)
    v = model.init(jax.random.key(0), jnp.zeros((2, IMG, IMG, 3)), train=False)
    mask = patch_embed_trainable_mask(v["params"])
    flat = jax.tree_util.tree_leaves_with_path(mask)
    frozen = [jax.tree_util.keystr(p) for p, m in flat if not m]
    assert frozen and all("patch_embed" in f for f in frozen)


@pytest.fixture(scope="module")
def v3_setup(mesh8):
    config = tiny_config()
    model = V3Model(tiny_vit(num_classes=None), embed_dim=16, hidden_dim=32)
    tx, sched = build_optimizer(config, steps_per_epoch=4)
    state = create_v3_train_state(
        jax.random.key(0), model, tx, (B // 8, IMG, IMG, 3)
    )
    step_raw = build_train_step(config, model, tx, mesh8, steps_per_epoch=4, sched=sched)

    def step(s, x1, x2):
        return step_raw(jax.tree.map(jnp.copy, s), x1, x2)

    x1 = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
    x2 = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
    return config, state, step, (x1, x2)


def test_vit_large_huge_geometry():
    """The paper's scaling-study archs (moco-v3 Table 3): ViT-L/16 and
    ViT-H/14 build with the standard timm geometry and the sin-cos grid
    matches the patch count — checked shape-only (eval_shape; a real L/H
    forward is too heavy for the 1-core sandbox)."""
    import jax

    from moco_tpu.models.vit import VIT_FEATURE_DIMS, build_vit

    for arch, width, depth, heads, patch, grid in (
        ("vit_large", 1024, 24, 16, 16, 14),
        ("vit_huge", 1280, 32, 16, 14, 16),
    ):
        model = build_vit(arch, num_classes=None)
        assert model.width == width and model.depth == depth
        assert model.num_heads == heads and model.patch_size == patch
        assert VIT_FEATURE_DIMS[arch] == width
        shapes = jax.eval_shape(
            lambda m=model: m.init(
                jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False
            )
        )
        pos = shapes["params"]["pos_embed"] if "pos_embed" in shapes["params"] else None
        # feature output is [1, width]
        out = jax.eval_shape(
            lambda v, m=model: m.apply(v, jnp.zeros((1, 224, 224, 3)),
                                       train=False),
            shapes,
        )
        assert out.shape == (1, width), (arch, out.shape)
        n_blocks = sum(1 for k in shapes["params"] if k.startswith("block"))
        assert n_blocks == depth, (arch, n_blocks)
        del pos, grid


def test_v3_state_has_no_queue_and_no_predictor_in_k(v3_setup):
    _, state, _, _ = v3_setup
    assert state.queue is None and state.queue_ptr is None
    assert "predictor" in state.params_q
    assert "predictor" not in state.params_k
    assert set(state.params_k) == set(encoder_subtree(state.params_q))


def test_v3_step_runs_and_updates(v3_setup):
    config, state, step, (x1, x2) = v3_setup
    s, metrics = step(state, x1, x2)
    assert int(s.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["acc1"]) <= 100.0
    # momentum at step 0 equals base (ramp starts at 0.99)
    assert np.isclose(float(metrics["momentum"]), 0.99, atol=1e-6)
    # linear warmup: lr is exactly 0 at step 0 (faithful to the reference's
    # per-iteration warmup), so params move only from step 2 on
    assert float(metrics["lr"]) == 0.0
    s, metrics = step(s, x1, x2)
    assert float(metrics["lr"]) > 0.0
    # params moved (except frozen patch embed)
    pe_before = np.asarray(state.params_q["backbone"]["patch_embed"]["kernel"])
    pe_after = np.asarray(s.params_q["backbone"]["patch_embed"]["kernel"])
    np.testing.assert_array_equal(pe_before, pe_after)
    proj_before = np.asarray(state.params_q["projector"]["mlp"]["fc0"]["kernel"])
    proj_after = np.asarray(s.params_q["projector"]["mlp"]["fc0"]["kernel"])
    assert not np.allclose(proj_before, proj_after)


def test_v3_key_params_move_only_by_ema(v3_setup):
    config, state, step, (x1, x2) = v3_setup
    s, _ = step(state, x1, x2)
    expected = ema_update(state.params_k, encoder_subtree(state.params_q), 0.99)
    for a, b in zip(jax.tree.leaves(s.params_k), jax.tree.leaves(expected)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_remat_vit_same_params_and_grads():
    """remat=True must not change the parameter tree or the math — only the
    memory/recompute trade (it made v3 ViT-S batch 512 compile on the v5e
    where the non-remat version exhausted compile resources)."""
    x = jnp.ones((2, IMG, IMG, 3))
    plain = tiny_vit(num_classes=16)
    rem = tiny_vit(num_classes=16, remat=True)
    v = plain.init(jax.random.key(0), x, train=False)
    v2 = rem.init(jax.random.key(0), x, train=False)
    assert jax.tree.structure(v) == jax.tree.structure(v2)

    def loss(m, params):
        return jnp.sum(m.apply({"params": params}, x, train=False) ** 2)

    g1 = jax.grad(lambda p: loss(plain, p))(v["params"])
    g2 = jax.grad(lambda p: loss(rem, p))(v2["params"])
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_v3_resnet_backbone_via_build_encoder(mesh8):
    """v3 also supports ResNet backbones (paper's MoCo v3 R50 recipe)."""
    config = tiny_config(arch="resnet18", cifar_stem=True)
    model = build_encoder(config)
    assert isinstance(model, V3Model)
    v = model.init(
        jax.random.key(0), jnp.zeros((2, IMG, IMG, 3)), train=False, predict=True
    )
    assert "predictor" in v["params"]


def test_v3_r50_lars_step_on_mesh(mesh8):
    """The v3-ResNet/LARS leg (imagenet-moco-v3-r50 preset shape): one step
    runs on the 8-device mesh, and the LARS trust-ratio scaling produces a
    genuinely different update than SGD with the same lr/grads."""
    from moco_tpu.config import get_preset

    preset = get_preset("imagenet-moco-v3-r50")
    assert preset.optimizer == "lars" and preset.variant == "v3"
    assert preset.weight_decay == 1.5e-6 and preset.crop_min == 0.2
    # lr follows the linear-scaling rule from the ACTUAL batch (base_lr)
    assert preset.effective_lr == pytest.approx(0.3 * preset.batch_size / 256)

    def run(optimizer):
        config = preset.replace(
            arch="resnet_tiny", cifar_stem=True, embed_dim=16, batch_size=B,
            compute_dtype="float32", optimizer=optimizer,
            lr=0.1, warmup_epochs=0, epochs=2,
        )
        model = build_encoder(config)
        tx, sched = build_optimizer(config, steps_per_epoch=4)
        state = create_v3_train_state(
            jax.random.key(0), model, tx, (B // 8, IMG, IMG, 3)
        )
        step = build_train_step(config, model, tx, mesh8, steps_per_epoch=4, sched=sched)
        x1 = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
        x2 = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
        # the step donates its input state — keep a live copy for comparison
        s, metrics = step(jax.tree.map(jnp.copy, state), x1, x2)
        return state, s, metrics

    init_lars, s_lars, m_lars = run("lars")
    init_sgd, s_sgd, m_sgd = run("sgd")
    assert np.isfinite(float(m_lars["loss"]))
    assert int(s_lars.step) == 1 and s_lars.queue is None
    # identical init (same seed) but different step direction: the trust
    # ratio rescales per-layer updates
    before = np.asarray(init_lars.params_q["backbone"]["conv1"]["kernel"])
    after_lars = np.asarray(s_lars.params_q["backbone"]["conv1"]["kernel"])
    after_sgd = np.asarray(s_sgd.params_q["backbone"]["conv1"]["kernel"])
    d_lars = after_lars - before
    d_sgd = after_sgd - before
    assert np.abs(d_lars).max() > 0  # LARS actually moved the params
    assert not np.allclose(d_lars, d_sgd)
    # LARS normalizes the update to ~trust_coefficient * ||w|| / ||u|| * lr:
    # the scale of the two updates must differ materially, not just noise
    ratio = np.linalg.norm(d_lars) / max(np.linalg.norm(d_sgd), 1e-12)
    assert ratio < 0.5 or ratio > 2.0, ratio


@pytest.mark.parametrize("name, devices", [
    ("imagenet-moco-v3-vits", 1),
    ("imagenet-moco-v3-vitb", 1),      # remat
    ("imagenet-moco-v3-r50", 1),       # LARS over the ResNet
    ("imagenet-moco-v3-vits", 8),      # the in-batch key gather across devices
])
def test_step_program_lowers_for_tpu(name, devices, mesh8):
    """The v3 programs (asymmetric pair of views, symmetric loss, AdamW or
    LARS) export for the TPU platform from the CPU. The blur's Mosaic kernel
    serves view 1 only: view 2 solarizes, which keeps the in-pipeline blur."""
    from step_lowering import census_for_tpu

    assert census_for_tpu(name, devices, mesh8, batch_size=16) == {"_blur_kernel": 1}
