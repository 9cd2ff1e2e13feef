"""Golden-value regression pinning (SURVEY §4 item 3): fixed seed, fixed
data, fixed arch → the first steps' losses are pinned so any silent change
to the algorithm (EMA order, queue semantics, shuffle stream, LR, optimizer
chain, augmentation RNG) shows up as a diff here.

CPU XLA is deterministic, so tolerances are tight. If a DELIBERATE semantic
change moves these values, update the constants in the same commit and say
why in its message.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import PretrainConfig
from moco_tpu.train_state import create_train_state
from moco_tpu.train_step import build_encoder, build_optimizer, build_train_step

GLOBAL_B, IMG, DIM, K = 16, 8, 16, 64


def _run_steps(config, mesh, n=3):
    model = build_encoder(config)
    tx, sched = build_optimizer(config, 8)
    state = create_train_state(
        jax.random.key(0), model, tx,
        (GLOBAL_B // mesh.size, IMG, IMG, 3), K, DIM,
    )
    step_fn = build_train_step(config, model, tx, mesh, 8, sched)
    losses = []
    for i in range(n):
        im_q = jax.random.normal(jax.random.key(100 + i), (GLOBAL_B, IMG, IMG, 3))
        im_k = jax.random.normal(jax.random.key(200 + i), (GLOBAL_B, IMG, IMG, 3))
        state, metrics = step_fn(state, im_q, im_k)
        losses.append(float(metrics["loss"]))
    return losses, state


@pytest.fixture(scope="module")
def config():
    return PretrainConfig(
        variant="v1", arch="resnet_tiny", cifar_stem=True, num_negatives=K,
        embed_dim=DIM, batch_size=GLOBAL_B, epochs=2, lr=0.1, seed=0,
    )


def test_golden_losses_8dev(config, mesh8):
    losses, state = _run_steps(config, mesh8)
    # pinned 2026-07-29 (jax 0.9.0, CPU): update deliberately, never casually
    # re-pinned same day: stride-2 3x3 convs moved from SAME (0,1) padding to
    # torchvision's symmetric (1,1) — the torch-consumer parity fix
    # re-pinned PR 21 (jax 0.9.0, CPU): the region differentiated w.r.t.
    # REPLICATED params, so autodiff psum'd the grads itself and gradsync's
    # pmean was the identity — every update was 8x the DDP mean (old pin
    # [0.016187, 2.8706696, 3.7958486]; step 0 is untouched, it precedes
    # any update). With collectives.device_local the trajectory now tracks
    # the 1-device one below, as per-device BN alone predicts
    golden = [0.016187, 2.8786652, 3.4944372]
    np.testing.assert_allclose(losses, golden, rtol=2e-4, err_msg=str(losses))
    assert int(state.queue_ptr) == (3 * GLOBAL_B) % K


def test_golden_losses_1dev(config):
    """Separate pin for the 1-device mesh: per-DEVICE BatchNorm makes the
    numbers legitimately mesh-size-dependent (16-sample BN groups here vs
    8x2 on the 8-device mesh — exactly as per-GPU BN behaves in the
    reference), so each mesh size gets its own golden values."""
    from moco_tpu.parallel.mesh import create_mesh

    losses, _ = _run_steps(config, create_mesh(1))
    # re-pinned with the symmetric-padding parity fix (see 8dev note)
    golden = [0.0279795, 2.8311126, 3.4929943]
    np.testing.assert_allclose(losses, golden, rtol=2e-4, err_msg=str(losses))
