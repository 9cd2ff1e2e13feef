"""Driver-level coverage for paths the main smoke test doesn't touch:
the v3 variant through train() (composite state, symmetric step, momentum
metric, backbone export) and the ImageFolder real-data path (JPEG decode →
staging → on-device aug → SPMD step)."""

import os

import numpy as np
import pytest

from moco_tpu.config import get_preset
from moco_tpu.train import train


@pytest.mark.slow
def test_v3_through_driver(mesh8, tmp_path):
    config = get_preset("imagenet-moco-v3-vits").replace(
        arch="resnet_tiny",            # v3 supports ResNet backbones (paper R50 recipe)
        cifar_stem=True,
        embed_dim=16,
        dataset="synthetic",
        image_size=16,
        batch_size=32,
        lr=1e-3,
        epochs=2,
        warmup_epochs=1,
        steps_per_epoch=8,
        compute_dtype="float32",
        knn_monitor=True,
        ckpt_dir=str(tmp_path / "ckpt"),
        export_path=str(tmp_path / "v3_backbone.safetensors"),
        print_freq=4,
        num_classes=10,
    )
    state, metrics = train(config, mesh8)
    assert int(state.step) == 16
    assert np.isfinite(metrics["loss"])
    assert "momentum" in metrics  # the v3 cosine ramp is live
    assert 0.0 < metrics["knn_train_top1"] <= 1.0
    assert state.queue is None
    assert os.path.exists(config.export_path)


@pytest.mark.slow
def test_midepoch_resume_no_replay(mesh8, tmp_path):
    """A checkpoint saved after a mid-epoch max_steps break must resume at
    the NEXT batch of that epoch, not replay the epoch from its start
    (ADVICE r1): an interrupted run continued to step 6 must be bit-identical
    to an uninterrupted 6-step run."""
    import jax

    base = dict(
        arch="resnet_tiny",
        dataset="synthetic",
        image_size=16,
        batch_size=32,
        num_negatives=64,
        embed_dim=16,
        epochs=2,
        steps_per_epoch=4,
        compute_dtype="float32",
        knn_monitor=False,
        print_freq=100,
    )
    uninterrupted = get_preset("cifar10-moco-v1").replace(**base, ckpt_dir="")
    state_a, _ = train(uninterrupted, mesh8, max_steps=6)

    interrupted = get_preset("cifar10-moco-v1").replace(
        **base, ckpt_dir=str(tmp_path / "ckpt")
    )
    state_mid, _ = train(interrupted, mesh8, max_steps=2)  # breaks mid-epoch 0
    assert int(state_mid.step) == 2
    state_b, _ = train(interrupted.replace(resume="auto"), mesh8, max_steps=6)

    assert int(state_a.step) == int(state_b.step) == 6
    for pa, pb in zip(
        jax.tree.leaves(state_a.params_q), jax.tree.leaves(state_b.params_q)
    ):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    np.testing.assert_array_equal(np.asarray(state_a.queue), np.asarray(state_b.queue))


def test_imagefolder_through_driver(mesh8, tmp_path):
    """Real-data path: JPEG tree → (native or PIL) staging → device aug →
    step. Images are written per class from distinct base colors so the
    pipeline has real class signal."""
    PIL = pytest.importorskip("PIL")
    from PIL import Image

    root = tmp_path / "data" / "train"
    rng = np.random.RandomState(0)
    colors = [(200, 40, 40), (40, 200, 40), (40, 40, 200)]
    for c, color in enumerate(colors):
        d = root / f"class{c}"
        d.mkdir(parents=True)
        for i in range(12):
            img = np.clip(
                np.array(color)[None, None] + rng.randint(-30, 30, (48, 48, 3)),
                0, 255,
            ).astype(np.uint8)
            Image.fromarray(img).save(str(d / f"{i}.jpg"), quality=90)

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny",
        dataset="imagefolder",
        data_dir=str(tmp_path / "data"),
        image_size=16,
        batch_size=32,
        num_negatives=64,
        embed_dim=16,
        epochs=2,
        steps_per_epoch=None,   # derived: 36 imgs // 32 = 1 step/epoch
        knn_monitor=False,
        ckpt_dir="",
        print_freq=1,
        num_classes=3,
    )
    state, metrics = train(config, mesh8)
    assert int(state.step) == 2
    assert np.isfinite(metrics["loss"])


def test_steps_per_epoch_clamped_to_loader(mesh8):
    """A steps_per_epoch above what the dataset can yield used to silently
    truncate epochs (and stretch the lr schedule); it now clamps to the
    loader's real batch count, so configured epochs mean what they say."""
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16,
        batch_size=256, num_negatives=512, embed_dim=16,
        epochs=2, steps_per_epoch=10_000,   # >> 2048/256 = 8 available
        knn_monitor=False, ckpt_dir="", print_freq=100,
    )
    state, _ = train(config, mesh8)
    assert int(state.step) == 2 * 8  # 2 real epochs of the 8 real batches


def test_knn_monitor_synthetic_texture_val_split(mesh8):
    """synthetic_texture gets a held-out-seed val split (fixed class tiles
    keep the label space aligned across seeds): the monitor reports real
    val tags plus the untrained baseline row (VERDICT r3 weak #3)."""
    from moco_tpu.data.datasets import SyntheticTextureDataset

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic_texture", image_size=16,
        batch_size=32, num_negatives=64, embed_dim=16, epochs=1,
        knn_monitor=True, knn_bank_size=64, ckpt_dir="", print_freq=1,
        num_classes=4,
    )
    data = SyntheticTextureDataset(num_samples=64, image_size=16,
                                   num_classes=4, seed=0)
    _, metrics = train(config, mesh8, dataset=data)
    assert "knn_val_top1" in metrics and "knn_train_top1" not in metrics
    assert "knn_val_top1_untrained" in metrics
    assert 0.0 <= metrics["knn_val_top1"] <= 1.0


def test_knn_monitor_uses_val_split_when_present(mesh8, tmp_path):
    """With an imagefolder val/ dir the monitor reports a REAL val metric
    (knn_val_top1); without one it holds out train data (knn_train_top1)."""
    PIL = pytest.importorskip("PIL")
    from PIL import Image

    rng = np.random.RandomState(1)
    colors = [(220, 30, 30), (30, 220, 30), (30, 30, 220)]
    for split, count in (("train", 12), ("val", 6)):
        for c, color in enumerate(colors):
            d = tmp_path / "data" / split / f"class{c}"
            d.mkdir(parents=True)
            for i in range(count):
                img = np.clip(
                    np.array(color)[None, None] + rng.randint(-25, 25, (32, 32, 3)),
                    0, 255,
                ).astype(np.uint8)
                Image.fromarray(img).save(str(d / f"{i}.jpg"), quality=90)

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny",
        dataset="imagefolder",
        data_dir=str(tmp_path / "data"),
        image_size=16,
        batch_size=32,
        num_negatives=64,
        embed_dim=16,
        epochs=1,
        knn_monitor=True,
        knn_bank_size=36,
        ckpt_dir="",
        print_freq=1,
        num_classes=3,
    )
    _, metrics = train(config, mesh8)
    assert "knn_val_top1" in metrics and "knn_train_top1" not in metrics
    assert 0.0 <= metrics["knn_val_top1"] <= 1.0

    # a val/ whose class listing differs from train/ would shift every
    # label id — the monitor must refuse it and fall back to the train
    # hold-out (labeled accordingly)
    extra = tmp_path / "data" / "val" / "class_extra"
    extra.mkdir()
    img = np.full((32, 32, 3), 128, np.uint8)
    Image.fromarray(img).save(str(extra / "0.jpg"), quality=90)
    _, metrics = train(config, mesh8)
    assert "knn_train_top1" in metrics and "knn_val_top1" not in metrics
