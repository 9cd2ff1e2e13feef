"""Pallas blur kernel: equivalence with the portable shifted-add blur
(interpret mode on CPU), weight semantics, per-sample independence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.data.augment import _use_pallas_blur, augment_batch, v2_aug_config
from moco_tpu.ops.pallas_blur import blur_radius, blur_weights, gaussian_blur_batch


def test_identity_kernel_is_noop():
    imgs = jax.random.normal(jax.random.key(0), (2, 16, 16, 3))
    radius = 2
    identity = jnp.zeros((2, 2 * radius + 1)).at[:, radius].set(1.0)
    out = gaussian_blur_batch(imgs, identity, radius, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(imgs), atol=1e-6)


def test_blur_weights_semantics():
    radius = 3
    w_on = blur_weights(jax.random.key(1), radius, (0.5, 1.5), prob=1.0)
    w_off = blur_weights(jax.random.key(1), radius, (0.5, 1.5), prob=0.0)
    np.testing.assert_allclose(float(jnp.sum(w_on)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_on), np.asarray(w_on[::-1]), rtol=1e-5)
    assert float(w_on[radius]) < 1.0  # actually blurs
    np.testing.assert_allclose(
        np.asarray(w_off), np.eye(2 * radius + 1)[radius], atol=1e-7
    )


def test_per_sample_sigmas_differ():
    imgs = jnp.broadcast_to(
        jax.random.normal(jax.random.key(2), (1, 16, 16, 3)), (3, 16, 16, 3)
    )
    radius = 2
    keys = jax.random.split(jax.random.key(3), 3)
    weights = jax.vmap(lambda k: blur_weights(k, radius, (0.1, 2.0), 1.0))(keys)
    out = np.asarray(gaussian_blur_batch(imgs, weights, radius, interpret=True))
    assert not np.allclose(out[0], out[1])


def test_pallas_pipeline_matches_portable_blur():
    """Full v2 augmentation with pallas_blur='on' (interpret) must match the
    portable shifted-add path bit-for-tolerance: same PRNG stream, and the
    blur commutes with flip/normalize as documented."""
    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.randint(0, 256, (4, 40, 40, 3), dtype=np.uint8))
    key = jax.random.key(4)
    cfg_off = v2_aug_config(out_size=32)._replace(pallas_blur="off")
    cfg_on = v2_aug_config(out_size=32)._replace(pallas_blur="on")
    a = np.asarray(augment_batch(imgs, key, cfg_off))
    b = np.asarray(augment_batch(imgs, key, cfg_on))
    np.testing.assert_allclose(
        a, b, atol=2e-4, err_msg=f"max abs diff {np.abs(a - b).max()}"
    )


def test_sharded_two_crops_matches_unsharded(mesh8):
    """build_two_crops_sharded derives per-sample keys from GLOBAL indices,
    so its output must equal plain two_crops on the same global batch (the
    multichip path loses no semantics — and the Pallas blur stays local)."""
    from moco_tpu.data.augment import build_two_crops_sharded, two_crops

    rng = np.random.RandomState(1)
    imgs = jnp.asarray(rng.randint(0, 256, (16, 24, 24, 3), dtype=np.uint8))
    key = jax.random.key(5)
    cfg = v2_aug_config(out_size=16)._replace(pallas_blur="on")
    q_ref, k_ref = two_crops(imgs, key, cfg)
    fn = build_two_crops_sharded(cfg, mesh8)
    q_sh, k_sh = fn(imgs, key)
    np.testing.assert_allclose(np.asarray(q_sh), np.asarray(q_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(k_sh), np.asarray(k_ref), atol=2e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_blur_kernel_lowers_for_tpu_at_cell_shapes(dtype):
    """`[128, 224, 224, 3]` with the 23 taps of a 224-pixel crop, the one Mosaic
    kernel of `r50-v2-f32.synthetic` (and, in bfloat16, of the image presets),
    exported for the TPU platform from the CPU: a tracing or typing break of
    the kernel fails here, not on the chip."""
    radius = blur_radius(224)
    images = jax.ShapeDtypeStruct((128, 224, 224, 3), jnp.dtype(dtype))
    weights = jax.ShapeDtypeStruct((128, 2 * radius + 1), jnp.float32)
    exported = jax.export.export(
        jax.jit(lambda x, w: gaussian_blur_batch(x, w, radius)), platforms=["tpu"]
    )(images, weights)
    module = exported.mlir_module()
    assert module.count("tpu_custom_call") == 1 and 'kernel_name = "_blur_kernel"' in module
    assert [(o.shape, str(o.dtype)) for o in exported.out_avals] == [(images.shape, dtype)]


def test_blur_gate_is_config_and_backend_only(monkeypatch):
    """`AugConfig.pallas_blur` (auto | on | off) and the backend decide, as
    `attention_plan` does for the other kernel; the environment is not asked
    (the kill switches of `utils/envflags.py` went with it)."""
    cfg = v2_aug_config(out_size=16)
    monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS", "1")
    monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS_BLUR", "1")
    assert not _use_pallas_blur(cfg)                                  # auto, on the tests' CPU
    assert _use_pallas_blur(cfg._replace(pallas_blur="on"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _use_pallas_blur(cfg)
    assert not _use_pallas_blur(cfg._replace(pallas_blur="off"))
    assert not _use_pallas_blur(cfg._replace(blur_prob=0.0))
    assert not _use_pallas_blur(cfg._replace(solarize_prob=0.2))     # v3's second view
