"""Full kNN evaluation on frozen features (BASELINE config 4; SURVEY §2.5,
§3.3 — InstDisc protocol: top-200 cosine neighbors, votes weighted
exp(sim/0.07)).

Pipeline (all on device): encode the ENTIRE train set with the frozen query
encoder into an L2-normalized bank, then score every val image by one
`[B, dim] x [N_bank, dim]^T` matmul + `top_k` + weighted class vote. Unlike
the linear probe this has zero trainable parameters.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from moco_tpu.config import EvalConfig
from moco_tpu.data import augment_batch, build_dataset, eval_aug_config
from moco_tpu.evals.lincls import _val_split, load_frozen_backbone
from moco_tpu.ops.knn import knn_accuracy


def build_feature_fn(model):
    """The frozen-encoder eval program: eval-mode forward + L2 norm, jitted
    once and reused across batches (the during-training kNN monitor passes
    it back in). Module-level so tools/progcheck can audit the SAME
    program the evals run (ISSUE 9)."""

    @jax.jit
    def feature_fn(params, stats, images):
        out = model.apply(
            {"params": params, "batch_stats": stats}, images, train=False
        )
        return out / jnp.linalg.norm(out, axis=-1, keepdims=True)

    return feature_fn


def encode_dataset(
    model,
    params,
    stats,
    dataset,
    config,
    batch: int = 256,
    indices: np.ndarray | None = None,
    feature_fn=None,
    mesh=None,
):
    """L2-normalized frozen-encoder features (center-crop transform,
    eval-mode BN) for `dataset` (or a subset via `indices`); the tail chunk
    is padded so the forward compiles once. Pass a precompiled `feature_fn`
    (signature `(params, stats, images)`) to reuse a jit cache across calls —
    the during-training kNN monitor does."""
    from moco_tpu.data.augment import default_eval_crop_frac

    cfg = eval_aug_config(
        config.image_size, crop_frac=default_eval_crop_frac(config.image_size)
    )
    key = jax.random.key(0)

    if feature_fn is None:
        feature_fn = build_feature_fn(model)

    sharding = None
    if mesh is not None and mesh.size > 1:
        # multi-chip eval: shard each batch over the data axis so the eval
        # forward parallelizes under the automatic partitioner (the eval
        # transform has no blur, so no pallas-partitioning caveats apply);
        # round the batch up to a mesh multiple so the shards are even
        from moco_tpu.parallel.mesh import batch_sharded

        sharding = batch_sharded(mesh)
        batch = ((batch + mesh.size - 1) // mesh.size) * mesh.size

    if indices is None:
        indices = np.arange(len(dataset))
    feats, labels = [], []
    from moco_tpu.data.loader import stage_eval_batch

    for start in range(0, len(indices), batch):
        idx = indices[start : start + batch]
        imgs, lbls, extents = stage_eval_batch(
            dataset.get_batch(idx), batch, sharding
        )
        valid = len(idx)
        images = augment_batch(imgs, key, cfg, extents)
        feats.append(np.asarray(feature_fn(params, stats, images))[:valid])
        labels.append(lbls)
    return np.concatenate(feats), np.concatenate(labels)


def run_knn(config: EvalConfig, mesh=None) -> float:
    from moco_tpu.parallel.mesh import create_mesh

    if mesh is None:
        mesh = create_mesh()
    model, params, stats = load_frozen_backbone(config)
    train_set = build_dataset(
        config.dataset, config.data_dir, image_size=config.image_size,
        stage_size=config.stage_size, num_workers=config.num_workers,
    )
    val_set = _val_split(config, train_set)
    bank, bank_labels = encode_dataset(model, params, stats, train_set, config, mesh=mesh)
    queries, qlabels = encode_dataset(model, params, stats, val_set, config, mesh=mesh)
    acc = knn_accuracy(
        jnp.asarray(queries),
        jnp.asarray(qlabels),
        jnp.asarray(bank),
        jnp.asarray(bank_labels),
        num_classes=config.num_classes,
        k=config.knn_k,
        temperature=config.knn_temperature,
        bank_chunk=config.knn_bank_chunk or None,
    )
    from moco_tpu.utils.logging import info

    info(f"kNN top-1: {100 * acc:.2f}% (k={config.knn_k}, T={config.knn_temperature})")
    return acc


def main(argv=None):
    from moco_tpu.config import PRESETS, add_config_flags, collect_overrides, get_preset

    parser = argparse.ArgumentParser(description="moco_tpu kNN evaluation")
    eval_presets = sorted(
        n for n, c in PRESETS.items() if isinstance(c, EvalConfig)
    )
    parser.add_argument("--preset", default="imagenet-lincls", choices=eval_presets)
    add_config_flags(parser, EvalConfig)
    parser.add_argument("--fake-devices", type=int, default=0)
    args = parser.parse_args(argv)
    if args.fake_devices:
        from moco_tpu.parallel.mesh import force_cpu_devices

        force_cpu_devices(args.fake_devices)
    from moco_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    run_knn(get_preset(args.preset).replace(**collect_overrides(args, EvalConfig)))


if __name__ == "__main__":
    main()
