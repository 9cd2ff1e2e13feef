"""End-to-end SPMD train-step tests on the 8-fake-device mesh (SURVEY §4
items 1-2): collectives + EMA + queue + optimizer composed exactly as the
real driver composes them, on a tiny ResNet so CPU compile stays fast."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import PretrainConfig
from moco_tpu.models.resnet import BasicBlock, ResNet
from moco_tpu.ops.ema import ema_update
from moco_tpu.train_state import create_train_state
from moco_tpu.train_step import build_optimizer, build_train_step

GLOBAL_B, IMG, DIM, K = 16, 8, 16, 64


def tiny_model():
    return ResNet(
        stage_sizes=(1, 1), block_cls=BasicBlock, width=8,
        cifar_stem=True, num_classes=DIM,
    )


@pytest.fixture(scope="module")
def setup(mesh8):
    config = PretrainConfig(
        variant="v1", num_negatives=K, embed_dim=DIM, temperature=0.07,
        lr=0.05, batch_size=GLOBAL_B, epochs=4, schedule=(2, 3),
    )
    model = tiny_model()
    tx, _ = build_optimizer(config, steps_per_epoch=4)
    state = create_train_state(
        jax.random.key(0), model, tx,
        (GLOBAL_B // 8, IMG, IMG, 3), K, DIM,
    )
    raw_step_fn = build_train_step(config, model, tx, mesh8, steps_per_epoch=4)

    def step_fn(s, im_q, im_k):
        # the step donates its input state (by design); tests reuse states, so
        # feed a copy and keep the original alive
        return raw_step_fn(jax.tree.map(jnp.copy, s), im_q, im_k)

    batches = [
        (
            jax.random.normal(jax.random.key(10 + i), (GLOBAL_B, IMG, IMG, 3)),
            jax.random.normal(jax.random.key(20 + i), (GLOBAL_B, IMG, IMG, 3)),
        )
        for i in range(3)
    ]
    return config, model, tx, state, step_fn, batches


def test_step_advances_and_metrics_finite(setup):
    config, model, tx, state, step_fn, batches = setup
    s = state
    for i, (im_q, im_k) in enumerate(batches):
        s, metrics = step_fn(s, im_q, im_k)
        assert int(s.step) == i + 1
        assert int(s.queue_ptr) == ((i + 1) * GLOBAL_B) % K
        assert np.isfinite(float(metrics["loss"]))
        assert 0.0 <= float(metrics["acc1"]) <= 100.0
    # Bounded sanity: CE over K+1 classes lies in [0, log(K+1)+slack]. (The
    # exact loss≈log(K+1) property needs INDEPENDENT random embeddings and is
    # pinned in test_losses; a fresh encoder's q/k are highly correlated, so
    # the positive dominates and the loss starts near zero.)
    _, m0 = step_fn(state, *batches[0])
    assert 0.0 <= float(m0["loss"]) <= np.log(K + 1) + 1.0


def test_key_params_move_only_by_ema(setup):
    """After one step, params_k must equal EMA(old_k, old_q) EXACTLY — no
    gradient may leak into the key encoder (`moco/builder.py` no_grad path)."""
    config, model, tx, state, step_fn, batches = setup
    new_state, _ = step_fn(state, *batches[0])
    expected = ema_update(state.params_k, state.params_q, config.momentum_ema)
    for a, b in zip(jax.tree.leaves(new_state.params_k), jax.tree.leaves(expected)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_query_params_change_and_queue_filled(setup):
    config, model, tx, state, step_fn, batches = setup
    new_state, _ = step_fn(state, *batches[0])
    changed = [
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(new_state.params_q), jax.tree.leaves(state.params_q)
        )
    ]
    assert all(changed)  # every tensor received gradient signal
    q = np.asarray(new_state.queue)
    # first GLOBAL_B rows replaced by fresh unit-norm keys, rest untouched
    np.testing.assert_allclose(np.linalg.norm(q[:GLOBAL_B], axis=1), 1.0, rtol=1e-4)
    np.testing.assert_array_equal(q[GLOBAL_B:], np.asarray(state.queue)[GLOBAL_B:])
    assert not np.allclose(q[:GLOBAL_B], np.asarray(state.queue)[:GLOBAL_B])


def test_determinism(setup):
    config, model, tx, state, step_fn, batches = setup
    s1, m1 = step_fn(state, *batches[0])
    s2, m2 = step_fn(state, *batches[0])
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(s1.params_q), jax.tree.leaves(s2.params_q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bn_stats_update_and_replicated(setup):
    config, model, tx, state, step_fn, batches = setup
    new_state, _ = step_fn(state, *batches[0])
    before = jax.tree.leaves(state.batch_stats_q)
    after = jax.tree.leaves(new_state.batch_stats_q)
    assert any(not np.allclose(a, b) for a, b in zip(before, after))
    after_k = jax.tree.leaves(new_state.batch_stats_k)
    before_k = jax.tree.leaves(state.batch_stats_k)
    assert any(not np.allclose(a, b) for a, b in zip(before_k, after_k))


def test_single_device_mesh_same_program(setup):
    """BASELINE config 1 is single-process: the SAME step program must run on
    a 1-device mesh (collectives degenerate to identity)."""
    from moco_tpu.parallel.mesh import create_mesh

    config, model, tx, state, step_fn, batches = setup
    mesh1 = create_mesh(1)
    fn1 = build_train_step(config, model, tx, mesh1, steps_per_epoch=4)
    s = jax.tree.map(jnp.copy, state)
    s, metrics = fn1(s, *batches[0])
    assert int(s.step) == 1
    assert int(s.queue_ptr) == GLOBAL_B % K
    assert np.isfinite(float(metrics["loss"]))


def test_update_is_the_ddp_mean_not_the_device_sum():
    """With SyncBN the 1-device and N-device programs compute the same
    function of the global batch, so one SGD step (no momentum, no wd) must
    move the query params identically. It did not before PR 21: the region
    differentiated w.r.t. REPLICATED params, autodiff psum'd the grads
    itself, GradSync's pmean over the already-reduced value was the
    identity, and every update was N× the DDP mean
    (collectives.device_local)."""
    from moco_tpu.parallel.mesh import create_mesh

    config = PretrainConfig(
        variant="v1", num_negatives=K, embed_dim=DIM, lr=0.1, sync_bn=True,
        batch_size=GLOBAL_B, weight_decay=0.0, sgd_momentum=0.0,
    )
    im_q = jax.random.normal(jax.random.key(1), (GLOBAL_B, IMG, IMG, 3))
    im_k = jax.random.normal(jax.random.key(2), (GLOBAL_B, IMG, IMG, 3))
    moved = {}
    for n in (1, 4):
        from moco_tpu.parallel.mesh import DATA_AXIS

        model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, width=8,
                       cifar_stem=True, num_classes=DIM,
                       bn_cross_replica_axis=DATA_AXIS)
        tx, sched = build_optimizer(config, 4)
        state = create_train_state(
            jax.random.key(0), model, tx, (GLOBAL_B // n, IMG, IMG, 3), K, DIM)
        before = jax.tree.map(np.asarray, state.params_q)
        step = build_train_step(config, model, tx, create_mesh(n), 4, sched)
        state, _ = step(state, im_q, im_k)
        moved[n] = jax.tree.map(lambda a, b: np.asarray(a) - b,
                                state.params_q, before)
    for d1, d4 in zip(jax.tree.leaves(moved[1]), jax.tree.leaves(moved[4]),
                      strict=True):
        np.testing.assert_allclose(d4, d1, rtol=2e-3, atol=1e-6)


def test_ring_shuffle_mode(setup, mesh8):
    """shuffle_mode='ring' (SURVEY §2.11 ppermute variant) must run the full
    step with finite loss and keep the queue semantics identical."""
    config, model, tx, state, step_fn, batches = setup
    ring_cfg = config.replace(shuffle_mode="ring")
    fn = build_train_step(ring_cfg, model, tx, mesh8, steps_per_epoch=4)
    s, metrics = fn(jax.tree.map(jnp.copy, state), *batches[0])
    assert np.isfinite(float(metrics["loss"]))
    assert int(s.queue_ptr) == GLOBAL_B % K
    import pytest

    with pytest.raises(ValueError, match="unknown shuffle_mode"):
        build_train_step(config.replace(shuffle_mode="nope"), model, tx, mesh8, 4)


def test_lr_follows_step_schedule(setup):
    """Milestone schedule (2,3) with 4 steps/epoch: lr drops x0.1 at epoch 2."""
    config, model, tx, state, step_fn, batches = setup
    s = state
    lrs = []
    for i in range(12):
        s, metrics = step_fn(s, *batches[i % 3])
        lrs.append(float(metrics["lr"]))
    assert np.allclose(lrs[0], 0.05)
    assert np.allclose(lrs[8], 0.005)  # step 8 = epoch 2 → first milestone


@pytest.mark.parametrize("name, devices, kernels", [
    ("cifar10-moco-v1", 1, {}),                       # BasicBlock, the v1 recipe: no blur
    ("imagenet-moco-v1", 1, {}),
    ("imagenet-moco-v2", 1, {"_blur_kernel": 2}),     # one kernel, both crops
    ("imagenet-moco-v2-8chip", 1, {"_blur_kernel": 2}),
    ("cell:r50-v2-f32", 1, {"_blur_kernel": 2}),      # the benchmark's float32 cell
    ("imagenet-moco-v2", 8, {"_blur_kernel": 2}),     # ShuffleBN gathers, key gather, grad sync
])
def test_step_program_lowers_for_tpu(name, devices, kernels, mesh8):
    """Every v1 / v2 image program the repo says it supports exports for the
    TPU platform from the CPU, uint8 staging canvas to donated queue update,
    and holds the blur's Mosaic kernel and no other."""
    from step_lowering import census_for_tpu

    assert census_for_tpu(name, devices, mesh8, batch_size=16) == kernels
