"""ZeRO-1 optimizer-state sharding (parallel/zero.py): identical numerics,
a real footprint cut, and placement that survives the step (no silent
re-replication by the partitioner)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from moco_tpu.config import PretrainConfig, get_preset
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.parallel.zero import opt_state_shardings, shard_opt_state
from moco_tpu.train_state import create_train_state
from moco_tpu.train_step import build_encoder, build_optimizer, build_train_step

B, IMG, DIM, K = 16, 16, 16, 64


def _setup(mesh):
    config = PretrainConfig(
        variant="v2", arch="resnet_tiny", cifar_stem=True, mlp_head=True,
        num_negatives=K, embed_dim=DIM, batch_size=B, epochs=2, lr=0.1,
    )
    model = build_encoder(config)
    tx, sched = build_optimizer(config, 8)
    state = create_train_state(
        jax.random.key(0), model, tx, (B // mesh.size, IMG, IMG, 3), K, DIM
    )
    step = build_train_step(config, model, tx, mesh, 8, sched)
    return state, step


def test_sharding_specs_pick_divisible_axes(mesh8):
    state, _ = _setup(mesh8)
    specs = opt_state_shardings(state.opt_state, mesh8)
    sharded = [
        (jax.tree_util.keystr(p), s.spec)
        for (p, s) in jax.tree_util.tree_leaves_with_path(specs)
        if s.spec != P()
    ]
    assert sharded, "no optimizer leaf got sharded"
    for path, spec in sharded:
        assert DATA_AXIS in tuple(spec), (path, spec)
    # a [3,3,16,16] conv momentum shards its channel axis (16 % 8 == 0),
    # never the kernel axes (3 % 8 != 0)
    leaves = dict(
        (jax.tree_util.keystr(p), (l.shape, s.spec))
        for (p, l), (_, s) in zip(
            jax.tree_util.tree_leaves_with_path(state.opt_state),
            jax.tree_util.tree_leaves_with_path(specs),
            strict=True,
        )
    )
    conv_rows = [(shape, spec) for shape, spec in leaves.values()
                 if len(shape) == 4 and shape[:2] == (3, 3)]
    assert conv_rows
    for shape, spec in conv_rows:
        assert spec[0] is None and spec[1] is None, (shape, spec)


def test_zero_step_identical_numerics_and_smaller_footprint(mesh8):
    """One step from identical inits, ZeRO placement vs replicated: params
    and queue equal to float-reduction tolerance (the partition boundary
    changes XLA fusion order by ~1e-7 relative); per-device optimizer bytes
    cut ~mesh-fold; the output opt_state KEEPS the ZeRO placement."""
    state_a, step = _setup(mesh8)
    state_b, _ = _setup(mesh8)
    state_b = state_b.replace(opt_state=shard_opt_state(state_b.opt_state, mesh8))

    im_q = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
    im_k = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
    # two steps so momentum (built in step 1) feeds the step-2 update
    sa, _ = step(state_a, im_q, im_k)
    sa, ma = step(sa, im_q, im_k)
    sb, _ = step(state_b, im_q, im_k)
    sb, mb = step(sb, im_q, im_k)

    np.testing.assert_allclose(np.asarray(ma["loss"]), np.asarray(mb["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(sa.params_q), jax.tree.leaves(sb.params_q),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sa.queue), np.asarray(sb.queue),
                               rtol=1e-5, atol=1e-6)

    def device0_bytes(opt_state):
        total = 0
        for leaf in jax.tree.leaves(opt_state):
            if hasattr(leaf, "addressable_shards"):
                shard = leaf.addressable_shards[0]
                total += np.prod(shard.data.shape) * leaf.dtype.itemsize
        return total

    assert device0_bytes(sb.opt_state) < 0.4 * device0_bytes(sa.opt_state)
    # placement survives the jitted step: no silent re-replication
    specs = opt_state_shardings(state_b.opt_state, mesh8)
    for (path, leaf), (_, want) in zip(
        jax.tree_util.tree_leaves_with_path(sb.opt_state),
        jax.tree_util.tree_leaves_with_path(specs),
        strict=True,
    ):
        if want.spec != P() and hasattr(leaf, "sharding"):
            def _norm(spec):  # XLA may drop trailing Nones
                t = tuple(spec)
                while t and t[-1] is None:
                    t = t[:-1]
                return t

            assert _norm(leaf.sharding.spec) == _norm(want.spec), (
                jax.tree_util.keystr(path), leaf.sharding.spec, want.spec)


def test_zero_through_driver(mesh8):
    from moco_tpu.train import train

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=32,
        num_negatives=64, embed_dim=16, epochs=1, steps_per_epoch=4,
        zero_sharding=True, knn_monitor=False, ckpt_dir="", print_freq=2,
    )
    state, metrics = train(config, mesh8)
    assert int(state.step) == 4
    assert np.isfinite(metrics["loss"])


@pytest.mark.slow
def test_zero_checkpoint_roundtrip(mesh8, tmp_path):
    """A ZeRO run checkpoints its sharded opt_state and resumes bit-faithful:
    Orbax saves the sharded arrays, maybe_resume restores replicated, and the
    driver re-shards after resume (train() ordering) — end to end through the
    real driver."""
    from moco_tpu.train import train

    base = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=32,
        num_negatives=64, embed_dim=16, epochs=2, steps_per_epoch=4,
        zero_sharding=True, knn_monitor=False, print_freq=100,
        ckpt_dir=str(tmp_path / "ckpt"),
    )
    state_a, _ = train(base.replace(ckpt_dir=""), mesh8)           # 8 steps straight
    state_mid, _ = train(base, mesh8, max_steps=4)                  # epoch 1 + save
    assert int(state_mid.step) == 4
    import os

    # the save really happened — otherwise run 3 retrains from scratch and
    # the roundtrip assertions pass vacuously
    assert sorted(int(d) for d in os.listdir(tmp_path / "ckpt")) == [4]
    state_b, _ = train(base.replace(resume="auto"), mesh8)          # resume to 8

    assert int(state_a.step) == int(state_b.step) == 8
    for a, b in zip(jax.tree.leaves(state_a.params_q),
                    jax.tree.leaves(state_b.params_q), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # the resumed run's opt state is back in the ZeRO placement
    sharded = [l for l in jax.tree.leaves(state_b.opt_state)
               if hasattr(l, "sharding") and l.sharding.spec != P()]
    assert sharded, "resume dropped the ZeRO placement"
