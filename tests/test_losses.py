"""InfoNCE / v3 loss property tests (SURVEY §4 item 2)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from moco_tpu.ops.losses import (
    contrastive_accuracy,
    infonce_logits,
    l2_normalize,
    softmax_cross_entropy,
    v3_contrastive_loss,
)
from moco_tpu.parallel import DATA_AXIS
from jax import shard_map


def _rand_unit(key, shape):
    return l2_normalize(jax.random.normal(key, shape))


def test_l2_normalize_unit_rows():
    x = jax.random.normal(jax.random.key(0), (5, 7)) * 10
    n = np.linalg.norm(np.asarray(l2_normalize(x)), axis=-1)
    np.testing.assert_allclose(n, 1.0, rtol=1e-5)


def test_logits_column0_is_positive_similarity():
    kq, kk, kqueue = jax.random.split(jax.random.key(1), 3)
    q = _rand_unit(kq, (4, 8))
    k = _rand_unit(kk, (4, 8))
    queue = _rand_unit(kqueue, (32, 8))
    logits, labels = infonce_logits(q, k, queue, temperature=0.2)
    assert logits.shape == (4, 33)
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), np.sum(np.asarray(q * k), -1) / 0.2, rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(labels), 0)


def test_loss_at_init_is_log_Kplus1():
    """With random unit q, k, queue and T=1 the expected loss ≈ log(K+1)."""
    K, dim, B = 4096, 128, 64
    kq, kk, kqueue = jax.random.split(jax.random.key(2), 3)
    q = _rand_unit(kq, (B, dim))
    k = _rand_unit(kk, (B, dim))
    queue = _rand_unit(kqueue, (K, dim))
    logits, labels = infonce_logits(q, k, queue, temperature=1.0)
    loss = float(softmax_cross_entropy(logits, labels))
    assert abs(loss - np.log(K + 1)) < 0.1


def test_no_gradient_reaches_queue_or_keys():
    kq, kk, kqueue = jax.random.split(jax.random.key(3), 3)
    q = _rand_unit(kq, (4, 8))
    k = _rand_unit(kk, (4, 8))
    queue = _rand_unit(kqueue, (16, 8))

    def loss_wrt_k_and_queue(k, queue):
        logits, labels = infonce_logits(q, jax.lax.stop_gradient(k), queue, 0.2)
        return softmax_cross_entropy(logits, labels)

    gk, gqueue = jax.grad(loss_wrt_k_and_queue, argnums=(0, 1))(k, queue)
    np.testing.assert_array_equal(np.asarray(gk), 0.0)
    np.testing.assert_array_equal(np.asarray(gqueue), 0.0)


def test_contrastive_accuracy_perfect_and_zero():
    logits = jnp.array([[10.0, 0.0, 0.0], [9.0, 1.0, 0.0]])
    labels = jnp.zeros(2, jnp.int32)
    acc1, acc5 = contrastive_accuracy(logits, labels)
    assert float(acc1) == 100.0
    logits_bad = jnp.array([[0.0, 10.0, 5.0, 4.0, 3.0, 2.0, 1.0]])
    acc1b, acc5b = contrastive_accuracy(logits_bad, jnp.zeros(1, jnp.int32))
    assert float(acc1b) == 0.0
    assert float(acc5b) == 0.0  # positive ranked 7th of 7


def test_v3_loss_single_device_matches_manual():
    kq, kk = jax.random.split(jax.random.key(4))
    q = _rand_unit(kq, (8, 16))
    k = _rand_unit(kk, (8, 16))
    loss = v3_contrastive_loss(q, k, temperature=0.5, axis_name=None)
    logits = np.asarray(q) @ np.asarray(k).T / 0.5
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    manual = -np.mean(np.diag(logp)) * 2 * 0.5
    np.testing.assert_allclose(float(loss), manual, rtol=1e-5)


def test_v3_loss_sharded_matches_single_device(mesh8):
    """The sharded v3 loss (all-gathered negatives + rank-offset labels) must
    equal the single-device computation on the same global batch."""
    kq, kk = jax.random.split(jax.random.key(5))
    q = _rand_unit(kq, (32, 16))
    k = _rand_unit(kk, (32, 16))
    ref = float(v3_contrastive_loss(q, k, 0.2, axis_name=None))

    def f(q, k):
        loss = v3_contrastive_loss(q, k, 0.2, axis_name=DATA_AXIS)
        return jax.lax.pmean(loss, DATA_AXIS)

    sharded = jax.jit(
        shard_map(f, mesh=mesh8, in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P())
    )(q, k)
    np.testing.assert_allclose(float(sharded), ref, rtol=1e-5)
