"""Linear probe on frozen features (layer L4; rebuild of `main_lincls.py` —
the driver behind the 67.5% north-star metric).

Reference semantics reproduced exactly (SURVEY §2.4, §3.2):
- checkpoint surgery: keep `module.encoder_q.*` backbone weights, DROP the
  contrastive head, assert the only missing params are the new classifier
  (`main_lincls.py:≈L176-200`);
- classifier init `fc.weight ~ N(0, 0.01)`, `fc.bias = 0` (`≈L150-175`);
- only 2 trainable tensors — SGD(lr 30, momentum .9, wd 0), x0.1 at epochs
  60/80, 100 epochs (`≈L40-90`, `≈L205-215`);
- "`model.eval()` during training": the frozen backbone runs with BN RUNNING
  stats even on training batches (`≈L300-340`);
- center-crop validation reporting acc1/acc5 (`≈L342-380`);
- `sanity_check`: after training, every backbone weight must be bit-identical
  to the pretrain checkpoint (`≈L390-415`).

TPU shape: features are computed under `stop_gradient` inside the jitted
step; only the classifier sees gradients, so XLA compiles the backbone as
pure inference (no activation stash) and the whole step is one SPMD program
over the data mesh — no parameter-freezing machinery needed.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from moco_tpu.checkpoint import load_for_inference, load_pretrained_backbone
from moco_tpu.config import EvalConfig
from moco_tpu.data import (
    augment_batch,
    build_dataset,
    epoch_loader,
    eval_aug_config,
    v1_aug_config,
)
from moco_tpu.ops.losses import contrastive_accuracy
from moco_tpu.ops.schedules import cosine_lr, step_lr
from moco_tpu.parallel.mesh import create_mesh, local_batch_size
from moco_tpu.utils.logging import info
from moco_tpu.utils.meters import AverageMeter, ProgressMeter


def load_frozen_backbone(config: EvalConfig):
    """Backbone (feature mode) + pretrained weights via checkpoint surgery.

    Thin wrapper over `checkpoint.load_for_inference` — the shared
    dialect-table loader the serve/ subsystem uses too (ISSUE 5), so both
    checkpoint dialects (`module.encoder_q.*` torchvision names and the
    timm fused-qkv / `backbone/*` tree exports) and the surgery's
    exact-backbone-tree check live in exactly one place."""
    return load_for_inference(
        config.pretrained,
        config.arch,
        image_size=config.image_size,
        cifar_stem=config.cifar_stem,
    )


def init_classifier(rng, feat_dim: int, num_classes: int):
    """`fc.weight ~ N(0, 0.01)`, zero bias."""
    w = 0.01 * jax.random.normal(rng, (feat_dim, num_classes), jnp.float32)
    return {"w": w, "b": jnp.zeros((num_classes,), jnp.float32)}


def build_lincls_steps(model, tx):
    """Jitted train/eval steps. Sharding is data-parallel via the automatic
    partitioner (no shard_map needed: BN is frozen, so there are no
    per-device-statistics semantics to preserve — the mesh enters only via
    the input shardings the caller applies to each batch)."""

    def features(params, stats, images):
        # eval-mode BN even while training the probe (`model.eval()`)
        return jax.lax.stop_gradient(
            model.apply({"params": params, "batch_stats": stats}, images, train=False)
        )

    @jax.jit
    def train_step(fc, opt_state, backbone_params, backbone_stats, images, labels):
        feats = features(backbone_params, backbone_stats, images)

        def loss_fn(fc):
            logits = feats @ fc["w"] + fc["b"]
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
            return loss, logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(fc)
        updates, opt_state = tx.update(grads, opt_state, fc)
        fc = optax.apply_updates(fc, updates)
        acc1, acc5 = contrastive_accuracy(logits, labels)
        return fc, opt_state, {"loss": loss, "acc1": acc1, "acc5": acc5}

    @jax.jit
    def eval_step(fc, backbone_params, backbone_stats, images, labels):
        feats = features(backbone_params, backbone_stats, images)
        logits = feats @ fc["w"] + fc["b"]
        acc1, acc5 = contrastive_accuracy(logits, labels)
        return {
            "correct1": acc1 * labels.shape[0] / 100.0,
            "correct5": acc5 * labels.shape[0] / 100.0,
        }

    return train_step, eval_step


def validate(eval_step, fc, params, stats, dataset, config: EvalConfig, mesh) -> tuple[float, float]:
    """Center-crop validation (`main_lincls.py:≈L342-380`)."""
    from moco_tpu.data.augment import default_eval_crop_frac

    cfg = eval_aug_config(
        config.image_size, crop_frac=default_eval_crop_frac(config.image_size)
    )
    key = jax.random.key(0)
    n = len(dataset)
    b = config.batch_size
    from moco_tpu.parallel.mesh import batch_sharded

    # config.batch_size is mesh-divisible (train_lincls checks local_batch_size)
    sharding = batch_sharded(mesh) if mesh is not None and mesh.size > 1 else None
    c1 = c5 = seen = 0.0
    from moco_tpu.data.loader import stage_eval_batch

    for start in range(0, n, b):
        idx = np.arange(start, min(start + b, n))
        # pad the label tail with -1 (never matches a prediction) so every
        # image is scored and shapes stay fixed
        imgs, labels, extents = stage_eval_batch(
            dataset.get_batch(idx), b, sharding, pad_label=-1
        )
        valid = len(idx)
        images = augment_batch(imgs, key, cfg, extents)
        m = eval_step(fc, params, stats, images, jnp.asarray(labels))
        c1 += float(m["correct1"])
        c5 += float(m["correct5"])
        seen += valid
    return 100.0 * c1 / max(seen, 1), 100.0 * c5 / max(seen, 1)


def sanity_check(params_after, params_pretrained) -> None:
    """Backbone must be untouched after probe training
    (`main_lincls.py:≈L390-415`). strict zip: an empty or mismatched reload
    must fail loudly, not silently compare nothing."""
    leaves_after = jax.tree_util.tree_leaves_with_path(params_after)
    leaves_ref = jax.tree_util.tree_leaves_with_path(params_pretrained)
    if not leaves_ref:
        raise AssertionError("sanity_check got an empty pretrained tree")
    for (pa, a), (pb, b) in zip(leaves_after, leaves_ref, strict=True):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(
                f"backbone weight changed during linear probe: {jax.tree_util.keystr(pa)}"
            )


def train_lincls(config: EvalConfig, mesh=None, max_steps: int | None = None):
    """Returns (fc_params, best_acc1). Train transform is the reference's
    supervised stack (random crop + flip); eval is center crop."""
    if mesh is None:
        mesh = create_mesh()
    local_batch_size(config.batch_size, mesh)  # divisibility check

    train_set = build_dataset(
        config.dataset, config.data_dir, image_size=config.image_size,
        stage_size=config.stage_size, num_workers=config.num_workers,
    )
    val_set = _val_split(config, train_set)
    model, backbone_params, backbone_stats = load_frozen_backbone(config)
    # pin the frozen backbone REPLICATED across the mesh once — otherwise the
    # uncommitted host arrays get re-placed on every jitted step
    from moco_tpu.parallel.mesh import replicated

    backbone_params = jax.device_put(backbone_params, replicated(mesh))
    backbone_stats = jax.device_put(backbone_stats, replicated(mesh))

    feat_dim = model.apply(
        {"params": backbone_params, "batch_stats": backbone_stats},
        jnp.zeros((1, config.image_size, config.image_size, 3)),
        train=False,
    ).shape[-1]
    fc = init_classifier(jax.random.key(config.seed), feat_dim, config.num_classes)

    steps_per_epoch = max(len(train_set) // config.batch_size, 1)

    lr = config.effective_lr  # resolves base_lr × batch/256 presets (v3 probe)

    def sched(step):
        epoch = jnp.floor(step / steps_per_epoch)
        if config.cos:
            return cosine_lr(lr, epoch, config.epochs)
        return step_lr(lr, epoch, config.schedule)

    tx = optax.chain(
        optax.add_decayed_weights(config.weight_decay),
        optax.sgd(sched, momentum=config.sgd_momentum),
    )
    opt_state = tx.init(fc)
    train_step, eval_step = build_lincls_steps(model, tx)

    # reference train transform: RandomResizedCrop(scale 0.08-1) + flip
    aug = v1_aug_config(config.image_size)._replace(
        min_scale=0.08, jitter_prob=0.0, grayscale_prob=0.0,
        brightness=0.0, contrast=0.0, saturation=0.0, hue=0.0,
    )
    key = jax.random.key(config.seed + 1)
    best_acc1 = 0.0
    step = 0
    start_epoch = 0
    total = max_steps or config.epochs * steps_per_epoch

    # probe checkpointing (the reference saves fc/optimizer/epoch/best_acc1
    # every epoch and supports --resume, `main_lincls.py:≈L120-140, L280`)
    if config.resume and not config.ckpt_dir:
        raise ValueError("--resume requires a ckpt_dir to resume from")
    mgr = None
    if config.ckpt_dir:
        import orbax.checkpoint as ocp

        from moco_tpu.checkpoint import checkpoint_manager

        mgr = checkpoint_manager(config.ckpt_dir)
        if config.resume == "auto" and mgr.latest_step() is not None:
            probe = {"fc": fc, "opt_state": opt_state,
                     "best_acc1": jnp.zeros(())}
            restored = mgr.restore(
                mgr.latest_step(), args=ocp.args.StandardRestore(probe)
            )
            fc, opt_state = restored["fc"], restored["opt_state"]
            # Orbax restores onto device 0; re-place replicated to match the
            # mesh-replicated backbone
            fc, opt_state = jax.device_put((fc, opt_state), replicated(mesh))
            best_acc1 = float(restored["best_acc1"])
            # epoch-granular resume (reference semantics): a mid-epoch save
            # (max_steps break) resumes from its epoch's START — keeping the
            # raw saved step would skip data and desync the LR schedule
            start_epoch = mgr.latest_step() // steps_per_epoch
            step = start_epoch * steps_per_epoch

    if config.evaluate:
        # reference `-e/--evaluate`: one center-crop validation pass over
        # the (resumed) probe, no training (`main_lincls.py:≈L95, ≈L280`)
        acc1, acc5 = validate(eval_step, fc, backbone_params, backbone_stats,
                              val_set, config, mesh)
        info(f"Evaluate: val Acc@1 {acc1:.2f} Acc@5 {acc5:.2f}")
        return fc, acc1

    for epoch in range(start_epoch, config.epochs):
        losses = AverageMeter("Loss", ":.4e")
        top1 = AverageMeter("Acc@1", ":6.2f")
        progress = ProgressMeter(steps_per_epoch, [losses, top1], f"Epoch: [{epoch}]")
        loader = epoch_loader(train_set, epoch, config.seed, config.batch_size,
                              mesh, depth=config.prefetch_depth,
                              workers=config.staging_workers)
        try:
            for i, (imgs, labels, extents) in enumerate(loader):
                images = augment_batch(
                    imgs, jax.random.fold_in(key, step), aug, extents
                )
                fc, opt_state, metrics = train_step(
                    fc, opt_state, backbone_params, backbone_stats, images, labels
                )
                step += 1
                if i % config.print_freq == 0:
                    losses.update(float(metrics["loss"]), config.batch_size)
                    top1.update(float(metrics["acc1"]), config.batch_size)
                    progress.display(i)
                if step >= total:
                    break
        finally:
            # quietly: a pending staged-read error would mask an in-flight
            # exception here, and the early `step >= total` break makes a
            # stale error for an unconsumed batch possible on success too
            loader.close_quietly()
        acc1, acc5 = validate(eval_step, fc, backbone_params, backbone_stats,
                              val_set, config, mesh)
        best_acc1 = max(best_acc1, acc1)
        info(f"Epoch [{epoch}] val Acc@1 {acc1:.2f} Acc@5 {acc5:.2f} (best {best_acc1:.2f})")
        if mgr is not None:
            import orbax.checkpoint as ocp

            mgr.save(
                step,
                args=ocp.args.StandardSave(
                    {"fc": fc, "opt_state": opt_state,
                     "best_acc1": jnp.asarray(best_acc1)}
                ),
            )
        if step >= total:
            break
    if mgr is not None:
        mgr.wait_until_finished()
    # reference `sanity_check`: reload the pretrain checkpoint from disk and
    # compare (in this functional design the backbone is structurally
    # immutable, but the check still guards against buffer aliasing bugs)
    reloaded, _ = load_pretrained_backbone(
        config.pretrained, num_heads=getattr(model, "num_heads", 12)
    )
    sanity_check(backbone_params, reloaded)
    return fc, best_acc1


def _val_split(config: EvalConfig, train_set=None):
    """Validation dataset: `val/` dir for imagefolder, test split for
    CIFAR-10, a held-out SAME-KIND synthetic set otherwise.

    The synthetic branch must preserve the dataset KIND: the texture
    dataset's class tiles come from a fixed internal seed exactly so a
    different-`seed` instance is a held-out split of the SAME classes
    (datasets.py::SyntheticTextureDataset). Before r5 this fell through
    to `SyntheticDataset` for `synthetic_texture` probes, scoring the
    head against labels from a different generator — the first on-chip
    probe of the gate-passing horizon encoder showed the signature
    (train Acc 99.7%, val Acc 0.39%, BELOW the 6.25% chance) that
    exposed it."""
    if config.dataset == "imagefolder":
        import os

        return build_dataset(
            "imagefolder", os.path.join(config.data_dir, "val"),
            image_size=config.image_size,
            stage_size=config.stage_size, num_workers=config.num_workers,
        )
    if config.dataset == "cifar10":
        from moco_tpu.data.datasets import CIFAR10

        return CIFAR10(config.data_dir, train=False)
    if config.dataset == "synthetic_texture":
        from moco_tpu.data.datasets import SyntheticTextureDataset

        # label space must MATCH the train split, which train_lincls
        # builds with the dataset's own default class count — deriving
        # from config.num_classes (1000 on the imagenet presets) would
        # recreate the exact train/val label mismatch this branch fixes
        # (review, r5); same convention as train.py::_monitor_val_split
        train_nc = getattr(train_set, "num_classes", None)
        kw = {"num_classes": train_nc} if train_nc else {}
        return SyntheticTextureDataset(
            num_samples=512, image_size=config.image_size, seed=999, **kw)
    from moco_tpu.data.datasets import SyntheticDataset

    return SyntheticDataset(num_samples=512, image_size=config.image_size, seed=999)


def main(argv=None):
    from moco_tpu.config import PRESETS, add_config_flags, collect_overrides, get_preset

    parser = argparse.ArgumentParser(description="moco_tpu linear probe")
    eval_presets = sorted(
        n for n, c in PRESETS.items() if isinstance(c, EvalConfig)
    )
    parser.add_argument("--preset", default="imagenet-lincls", choices=eval_presets)
    add_config_flags(parser, EvalConfig)
    parser.add_argument("--max-steps", type=int, default=None)
    parser.add_argument("--fake-devices", type=int, default=0)
    args = parser.parse_args(argv)
    if args.fake_devices:
        from moco_tpu.parallel.mesh import force_cpu_devices

        force_cpu_devices(args.fake_devices)
    from moco_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    config = get_preset(args.preset).replace(**collect_overrides(args, EvalConfig))
    info(f"config: {config}")
    _, best = train_lincls(config, max_steps=args.max_steps)
    info(f"best val Acc@1: {best:.2f}")


if __name__ == "__main__":
    main()
