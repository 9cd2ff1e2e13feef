"""Integration smoke (SURVEY §4 item 3, BASELINE config-1 criterion): run the
REAL train() driver end-to-end on clusterable synthetic data, then feed its
exported checkpoint through the real linear-probe and kNN eval drivers — the
complete user journey. Uses the micro arch so the single-core CPU sandbox
finishes in a couple of minutes."""

import os

import numpy as np
import pytest

from moco_tpu.config import EvalConfig, get_preset
from moco_tpu.train import train


@pytest.fixture(scope="module")
def trained(mesh8, tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("smoke")
    export = str(tmp_path / "encoder_q.safetensors")
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny",
        dataset="synthetic",
        image_size=16,
        batch_size=32,
        num_negatives=128,
        embed_dim=32,
        lr=0.12,
        epochs=3,
        steps_per_epoch=16,
        knn_monitor=True,
        ckpt_dir=str(tmp_path / "ckpt"),
        tb_dir=str(tmp_path / "tb"),
        export_path=export,
        print_freq=8,
        num_classes=10,
    )
    state, metrics = train(config, mesh8)
    return config, state, metrics, export, tmp_path


@pytest.mark.slow
def test_moco_v1_smoke_loss_falls_knn_above_chance(trained):
    config, state, metrics, export, tmp_path = trained
    assert int(state.step) == 48
    assert np.isfinite(metrics["loss"])
    # 10-class synthetic data, chance = 10%. Healthy runs measure kNN
    # 0.95-0.99 here across seeds (runs/README.md; 3-seed r2 measurement),
    # so 0.9 catches subtle algorithmic regressions (aug order, EMA rate)
    # that the old above-chance bar (0.2) would have passed
    assert metrics["knn_train_top1"] > 0.9, f"kNN top-1 {metrics['knn_train_top1']} below healthy range"
    assert os.path.exists(export)
    try:
        import tensorboardX  # noqa: F401  (optional dep; writer no-ops without it)
    except ImportError:
        pass
    else:
        tb_files = os.listdir(tmp_path / "tb")
        assert any("tfevents" in f for f in tb_files), tb_files


def test_lincls_on_trained_export(trained, mesh8):
    """Probe on PRETRAINED features must beat chance comfortably — the full
    pretrain→export→surgery→probe pipeline (config 4 on config 1's output)."""
    from moco_tpu.evals.lincls import train_lincls

    config, state, metrics, export, tmp_path = trained
    eval_cfg = EvalConfig().replace(
        arch="resnet_tiny", pretrained=export, dataset="synthetic",
        image_size=16, cifar_stem=True, num_classes=10, batch_size=64,
        epochs=2, lr=0.03, print_freq=32, ckpt_dir="",
    )
    fc, best_acc1 = train_lincls(eval_cfg, mesh8, max_steps=64)
    # probe recipe re-derived after the symmetric-padding parity fix shifted
    # micro-scale feature magnitudes (lr 1.0 diverged): lr 0.03 x 64 steps
    # measures 67-76% across 3 seeds (runs/README.md)
    assert best_acc1 > 50.0, f"probe on pretrained features only {best_acc1}%"


@pytest.mark.slow
def test_texture_learning_detector(mesh8):
    """Frozen-encoder regression detector on the honest (non-separable)
    dataset — VERDICT r4 #5: the plain-synthetic smoke above cannot notice
    an encoder that silently stops learning.

    Thresholds are MEASURED, not aspirational (tools/_texture_smoke_measure
    .py, 3 seeds x {live lr=0.12, frozen-null lr=1e-9}, 256 steps,
    runs/texture_smoke_r5.jsonl): positive-pair alignment `pos_sim` ends in
    [0.955, 0.970] live vs [0.650, 0.821] frozen → assert > 0.88 (worst-gap
    midpoint); loss ends 6.14-6.18 live vs 6.97-8.74 frozen → assert < 6.6.
    Class-level kNN is deliberately NOT asserted here: at CI scale the live
    delta is NEGATIVE (the clustering dip the r5 horizon sweep shows at 320
    steps), while the frozen null's kNN RISES +6-11 pts from BN running-
    stat calibration alone — kNN-vs-baseline becomes the criterion only at
    horizon scale (tools/_horizon_run.py), judged against the BN-calibrated
    null (runs/horizon_frozen_null_r5.log)."""
    from moco_tpu.data.datasets import SyntheticTextureDataset

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, dataset="synthetic_texture",
        image_size=32, batch_size=32, num_negatives=512, embed_dim=64,
        lr=0.12, momentum_ema=0.99, cos=True, epochs=8,
        knn_monitor=True, knn_every_epochs=8, knn_bank_size=768,
        num_classes=16, ckpt_dir="", tb_dir="", print_freq=31, seed=0,
    )
    data = SyntheticTextureDataset(num_samples=1024, image_size=32,
                                   num_classes=16, seed=0)
    state, metrics = train(config, mesh8, dataset=data)
    assert int(state.step) == 256
    # both sides of the learning evidence must have been computed
    assert 0.0 <= metrics["knn_val_top1_untrained"] <= 1.0
    assert 0.0 <= metrics["knn_val_top1"] <= 1.0
    # the two measured detectors: alignment and queue-hardened loss
    assert metrics["pos_sim"] > 0.88, (
        f"pos_sim {metrics['pos_sim']:.3f} is in the frozen-encoder band "
        "(measured frozen max 0.821, live min 0.955)")
    assert metrics["loss"] < 6.6, (
        f"loss {metrics['loss']:.3f} is in the frozen-encoder band "
        "(measured frozen min 6.97, live max 6.18)")


def test_knn_every_epochs_zero_rejected(mesh8):
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16,
        batch_size=32, num_negatives=128, knn_monitor=True,
        knn_every_epochs=0, ckpt_dir="", tb_dir="",
    )
    with pytest.raises(ValueError, match="knn_every_epochs"):
        train(config, mesh8)


def test_knn_on_trained_export(trained):
    from moco_tpu.evals.knn import run_knn

    config, state, metrics, export, tmp_path = trained
    eval_cfg = EvalConfig().replace(
        arch="resnet_tiny", pretrained=export, dataset="synthetic",
        image_size=16, cifar_stem=True, num_classes=10, knn_k=20, ckpt_dir="",
    )
    acc = run_knn(eval_cfg)
    # healthy runs measure 100% here (runs/README.md)
    assert acc > 0.9, f"kNN on pretrained features only {acc}"
