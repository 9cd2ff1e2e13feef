"""Every named preset must construct a valid model + optimizer (shape-level
only — eval_shape keeps ResNet-50/ViT-S init free)."""

import jax
import jax.numpy as jnp
import pytest

from moco_tpu.config import PRESETS, PretrainConfig, get_preset
from moco_tpu.models import is_token_encoder
from moco_tpu.train_step import build_encoder, build_optimizer


@pytest.mark.parametrize(
    "name", [n for n, c in PRESETS.items() if isinstance(c, PretrainConfig)]
)
def test_pretrain_preset_builds(name):
    config = get_preset(name)
    model = build_encoder(config)
    tx, sched = build_optimizer(config, steps_per_epoch=100)
    s = config.image_size
    kwargs = {"predict": True} if config.variant == "v3" else {}
    # a token encoder is fed ids, an image encoder pictures
    dummy = (jnp.zeros((1, config.seq_len), jnp.int32) if is_token_encoder(config.arch)
             else jnp.zeros((1, s, s, 3)))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), dummy, train=False, **kwargs)
    )
    assert "params" in shapes
    # schedule evaluates finitely at the start/end of training
    assert float(sched(0)) >= 0.0
    assert float(sched(100 * config.epochs - 1)) >= 0.0


def test_reference_v1_v2_deltas():
    """The entire v1→v2 delta is 3 flags + temperature (SURVEY §2.1)."""
    v1 = get_preset("imagenet-moco-v1")
    v2 = get_preset("imagenet-moco-v2")
    assert (v1.mlp_head, v1.aug_plus, v1.cos, v1.temperature) == (
        False, False, False, 0.07,
    )
    assert (v2.mlp_head, v2.aug_plus, v2.cos, v2.temperature) == (
        True, True, True, 0.2,
    )
    # everything else identical
    for field in ("arch", "num_negatives", "momentum_ema", "lr", "batch_size",
                  "epochs", "weight_decay", "sgd_momentum"):
        assert getattr(v1, field) == getattr(v2, field), field


def test_unknown_preset():
    with pytest.raises(ValueError, match="unknown preset"):
        get_preset("nope")


def test_v3_preset_lr_scales_with_batch():
    """The v3 presets follow the linear-scaling rule: a --batch-size override
    must rescale the effective lr (reference: `args.lr * args.batch_size/256`
    computed from the ACTUAL batch; VERDICT r2 weak #4)."""
    for name, base in (("imagenet-moco-v3-vits", 1.5e-4),
                       ("imagenet-moco-v3-r50", 0.3)):
        cfg = get_preset(name)
        assert cfg.effective_lr == pytest.approx(base * 4096 / 256)
        halved = cfg.replace(batch_size=1024)
        assert halved.effective_lr == pytest.approx(base * 1024 / 256)
        # an explicit lr still wins over the scaling rule
        assert cfg.replace(lr=0.5).effective_lr == 0.5


def test_v3_preset_lr_in_schedule():
    """build_optimizer's schedule must use the batch-resolved lr."""
    cfg = get_preset("imagenet-moco-v3-vits").replace(
        batch_size=512, warmup_epochs=0, cos=True
    )
    _, sched = build_optimizer(cfg, steps_per_epoch=10)
    assert float(sched(0)) == pytest.approx(1.5e-4 * 512 / 256)


def test_v3_lincls_preset():
    """The moco-v3 probe recipe: batch-scaled SGD lr 3/256-per-sample,
    90 epochs, cosine (VERDICT r2 missing #2)."""
    cfg = get_preset("imagenet-lincls-v3")
    assert cfg.epochs == 90 and cfg.cos
    assert cfg.effective_lr == pytest.approx(3.0 * 1024 / 256)
    assert cfg.replace(batch_size=256).effective_lr == pytest.approx(3.0)


def test_effective_lr_requires_some_lr():
    with pytest.raises(ValueError, match="lr or base_lr"):
        _ = PretrainConfig(lr=0.0, base_lr=0.0).effective_lr
