"""The horizon tool's honesty-gate plumbing (review, r5): the untrained-
baseline sidecar must survive preemption (atomic write, corrupt-tolerant
restore) and a resume must be provably the SAME run (flag fingerprint) —
otherwise the gate compares against a baseline nobody measured, or gates a
spliced cosine schedule nobody ran.

The fail-fast paths run the tool as a subprocess: both exit 4 BEFORE any
training step, which is the point (discovering a dead sidecar after the
remaining epochs wastes the whole run).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "_horizon_run.py")


def _run_tool(ckpt_dir, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MOCO_TPU_NO_CACHE="1")
    return subprocess.run(
        [sys.executable, TOOL, "--steps", "4", "--batch", "16",
         "--samples", "16", "--ckpt-dir", ckpt_dir, *extra],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)


def _fake_ckpt(tmp_path, run_args=None):
    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "100").mkdir()  # orbax step dir: marks "a checkpoint exists"
    if run_args is not None:
        (ck / "horizon_args.json").write_text(json.dumps(run_args))
    return str(ck)


# the tool's own fingerprint for --steps 4 --batch 16 --samples 16:
# samples=16, steps_per_epoch=1, epochs=4, total=4 (cpu: the subprocess
# runs under JAX_PLATFORMS=cpu)
ARGS_4_16 = {"steps": 4, "batch": 16, "samples": 16,
             "arch": "resnet18", "image_size": 32, "lr": 0.03,
             "momentum_ema": 0.99, "backend": "cpu",
             "compute_dtype": "float32"}


def test_resume_refuses_changed_flags(tmp_path):
    ck = _fake_ckpt(tmp_path, dict(ARGS_4_16, steps=4608))
    r = _run_tool(ck)
    assert r.returncode == 4, r.stdout + r.stderr
    assert "resume refused: flags changed" in r.stdout


def test_resume_refuses_missing_args_fingerprint(tmp_path):
    ck = _fake_ckpt(tmp_path, run_args=None)
    r = _run_tool(ck)
    assert r.returncode == 4, r.stdout + r.stderr
    assert "horizon_args.json missing/corrupt" in r.stdout


def test_resume_refuses_dead_baseline_sidecar(tmp_path):
    ck = _fake_ckpt(tmp_path, ARGS_4_16)
    (tmp_path / "ck" / "untrained_baseline.json").write_text('{"knn_val')
    r = _run_tool(ck)
    assert r.returncode == 4, r.stdout + r.stderr
    assert "untrained_baseline.json missing/corrupt" in r.stdout


@pytest.mark.slow
def test_baseline_sidecar_roundtrip(tmp_path):
    """train()-level: fresh run writes the sidecar atomically; a corrupt
    sidecar on resume yields NO baseline key (the tool then refuses to
    gate); a healthy one restores the recorded value verbatim."""
    from moco_tpu.config import get_preset
    from moco_tpu.data.datasets import SyntheticTextureDataset
    from moco_tpu.train import train

    ck = str(tmp_path / "sck")
    cfg = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, dataset="synthetic_texture",
        image_size=16, batch_size=16, num_negatives=32, embed_dim=32,
        lr=0.03, epochs=1, steps_per_epoch=None, knn_monitor=True,
        knn_every_epochs=1, knn_bank_size=32, num_classes=16,
        ckpt_dir=ck, ckpt_every_epochs=1, resume="", tb_dir="",
        print_freq=100, num_workers=0, compute_dtype="float32",
    )
    data = SyntheticTextureDataset(num_samples=32, image_size=16,
                                   num_classes=16)
    state, metrics = train(cfg, dataset=data)
    side = os.path.join(ck, "untrained_baseline.json")
    assert os.path.exists(side) and not os.path.exists(side + ".tmp")
    tag = ("knn_val_top1_untrained"
           if "knn_val_top1_untrained" in metrics else
           "knn_train_top1_untrained")
    assert json.load(open(side))[tag] == pytest.approx(metrics[tag])

    # corrupt -> resumed metrics carry NO baseline (no fabrication)
    with open(side, "w") as f:
        f.write('{"knn_val_top1_untr')
    _, m2 = train(cfg.replace(resume="auto", epochs=2), dataset=data)
    assert "knn_val_top1_untrained" not in m2
    assert "knn_train_top1_untrained" not in m2

    # healthy -> restored verbatim
    with open(side, "w") as f:
        json.dump({"knn_val_top1_untrained": 0.123}, f)
    _, m3 = train(cfg.replace(resume="auto", epochs=3), dataset=data)
    assert m3["knn_val_top1_untrained"] == pytest.approx(0.123)


def test_resume_accepts_pre_arch_fingerprint(tmp_path):
    """Fingerprints written before the --arch/--image-size flags lack the
    two keys; those runs WERE resnet18@32, so the migration must default
    them rather than refuse (review, r5). Proven by reaching the NEXT
    refusal (corrupt sidecar) instead of 'flags changed'."""
    old = {k: v for k, v in ARGS_4_16.items()
           if k not in ("arch", "image_size")}
    ck = _fake_ckpt(tmp_path, old)
    (tmp_path / "ck" / "untrained_baseline.json").write_text('{"knn_val')
    r = _run_tool(ck)
    assert r.returncode == 4, r.stdout + r.stderr
    assert "untrained_baseline.json missing/corrupt" in r.stdout
    assert "flags changed" not in r.stdout
