"""Eval drivers: linear probe mechanics/semantics + full kNN eval
(BASELINE config 4; `main_lincls.py` rebuild)."""

import jax
import numpy as np
import optax
import pytest

from moco_tpu.checkpoint import export_encoder_q
from moco_tpu.config import EvalConfig
from moco_tpu.evals.knn import run_knn
from moco_tpu.evals.lincls import load_frozen_backbone, train_lincls
from moco_tpu.models.resnet import ResNetTiny
from moco_tpu.train_state import create_train_state


@pytest.fixture(scope="module")
def exported_ckpt(tmp_path_factory):
    model = ResNetTiny(num_classes=32, cifar_stem=True)
    tx = optax.sgd(0.1)
    state = create_train_state(jax.random.key(0), model, tx, (2, 16, 16, 3), 64, 32)
    path = str(tmp_path_factory.mktemp("ckpt") / "encoder.safetensors")
    export_encoder_q(state, path)
    return path


def eval_config(path, **kw):
    base = dict(
        arch="resnet_tiny", pretrained=path, dataset="synthetic",
        image_size=16, cifar_stem=True, num_classes=10, batch_size=64,
        epochs=1, lr=1.0, print_freq=4, ckpt_dir="",
    )
    base.update(kw)
    return EvalConfig().replace(**base)


def test_load_frozen_backbone_surgery(exported_ckpt):
    config = eval_config(exported_ckpt)
    model, params, stats = load_frozen_backbone(config)
    assert "fc" not in params
    assert "conv1" in params and "layer1_0" in params
    assert stats["bn1"]["mean"].shape == (16,)


def test_load_frozen_backbone_arch_mismatch(exported_ckpt):
    config = eval_config(exported_ckpt, arch="resnet18")
    with pytest.raises(ValueError, match="surgery mismatch"):
        load_frozen_backbone(config)


def test_lincls_end_to_end(mesh8, exported_ckpt):
    """Probe on RANDOM frozen features of clusterable data still beats
    chance (random projections are linearly separable enough), proving the
    whole train/validate/sanity-check path."""
    config = eval_config(exported_ckpt)
    fc, best_acc1 = train_lincls(config, mesh8, max_steps=24)
    assert np.isfinite(best_acc1)
    assert best_acc1 > 15.0, f"probe top-1 {best_acc1} not above 10% chance"
    assert fc["w"].shape == (32, 10)


def test_knn_eval_end_to_end(exported_ckpt):
    config = eval_config(exported_ckpt, knn_k=20)
    acc = run_knn(config)
    assert acc > 0.15, f"kNN top-1 {acc} not above chance"


def test_v3_backbone_dialect_roundtrip(tmp_path):
    """v3 export (backbone tree dialect, projector/predictor dropped) loads
    back through the same lincls surgery path — for ResNet AND ViT-style
    backbones (same code path; ResNetTiny keeps the test fast)."""
    from moco_tpu.checkpoint import export_v3_backbone, flatten_tree, unflatten_tree
    from moco_tpu.v3_step import V3Model, create_v3_train_state

    model = V3Model(
        ResNetTiny(num_classes=None, cifar_stem=True), embed_dim=16, hidden_dim=32
    )
    tx = optax.sgd(0.1)
    state = create_v3_train_state(jax.random.key(0), model, tx, (2, 16, 16, 3))
    path = str(tmp_path / "v3_backbone.safetensors")
    flat = export_v3_backbone(state, path)
    assert all(k.startswith(("backbone/", "backbone_stats/")) for k in flat)
    assert not any("projector" in k or "predictor" in k for k in flat)

    config = eval_config(path)
    m, params, stats = load_frozen_backbone(config)
    for a, b in zip(
        jax.tree.leaves(params),
        jax.tree.leaves(state.params_q["backbone"]),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unflatten(flatten(x)) == x
    tree = {"a": {"b": np.ones((2, 2)), "c": np.zeros(3)}, "d": np.arange(4)}
    back = unflatten_tree(flatten_tree(tree))
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(back),
        jax.tree_util.tree_leaves_with_path(tree),
    ):
        np.testing.assert_array_equal(a, b)


def test_lincls_checkpoint_resume(mesh8, exported_ckpt, tmp_path):
    """Probe checkpointing + --resume auto (the reference's main_lincls
    saves fc/optimizer/epoch/best every epoch)."""
    cfg = eval_config(exported_ckpt, ckpt_dir=str(tmp_path / "probe"), epochs=2)
    fc1, best1 = train_lincls(cfg, mesh8, max_steps=32)
    import os

    steps = sorted(int(d) for d in os.listdir(tmp_path / "probe"))
    assert steps, "no probe checkpoints written"
    # resume: continues PAST the first run's last checkpoint (a restore
    # that silently restarted from scratch would stop at the same step)
    cfg2 = cfg.replace(resume="auto", epochs=3)
    fc2, best2 = train_lincls(cfg2, mesh8, max_steps=96)
    steps2 = sorted(int(d) for d in os.listdir(tmp_path / "probe"))
    assert max(steps2) > max(steps), (steps, steps2)
    import pytest as _pytest

    with _pytest.raises(ValueError, match="requires a ckpt_dir"):
        train_lincls(cfg.replace(ckpt_dir="", resume="auto"), mesh8, max_steps=1)


def test_lincls_evaluate_only(mesh8, exported_ckpt, tmp_path):
    """--evaluate (reference -e): validate the resumed probe, no training —
    the returned acc matches the training run's last validation, and the
    classifier is untouched."""
    cfg = eval_config(exported_ckpt, ckpt_dir=str(tmp_path / "probe"), epochs=1)
    fc_trained, best = train_lincls(cfg, mesh8, max_steps=32)
    fc_eval, acc = train_lincls(
        cfg.replace(resume="auto", evaluate=True), mesh8
    )
    assert acc == pytest.approx(best, abs=1e-6)
    for a, b in zip(jax.tree.leaves(fc_trained), jax.tree.leaves(fc_eval),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_val_split_preserves_synthetic_texture_kind():
    """The synthetic val split must be the SAME dataset kind as training:
    a synthetic_texture probe validated on SyntheticDataset images scores
    the head against labels from a different generator (below-chance val
    with near-perfect train — the r5 signature). Class tiles are fixed
    across seeds, so a
    held-out texture instance shares the train classes."""
    import numpy as np

    from moco_tpu.config import get_preset
    from moco_tpu.data.datasets import SyntheticTextureDataset
    from moco_tpu.evals.lincls import _val_split

    # the dangerous default: imagenet-lincls leaves num_classes at 1000,
    # but the train split is built with the dataset's own default class
    # count — the val label space must follow the TRAIN SET, not config
    cfg = get_preset("imagenet-lincls").replace(
        dataset="synthetic_texture", image_size=32)
    train = SyntheticTextureDataset(num_samples=64, image_size=32, seed=0)
    val = _val_split(cfg, train)
    assert isinstance(val, SyntheticTextureDataset)
    assert val.num_classes == train.num_classes == 16

    # same class tiles across seeds (the fixed-tile-seed contract)
    np.testing.assert_array_equal(
        np.asarray(train.class_tiles), np.asarray(val.class_tiles))

    # non-default class count follows the train set too
    train24 = SyntheticTextureDataset(num_samples=48, image_size=32,
                                      num_classes=24, seed=0)
    assert _val_split(cfg, train24).num_classes == 24
