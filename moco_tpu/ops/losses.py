"""Contrastive losses: queue-based InfoNCE (MoCo v1/v2) and the queue-free
symmetric in-batch loss (MoCo v3).

Rebuilds the logits construction of `MoCo.forward` (`moco/builder.py:≈L117-165`)
and the v3 `ctr` loss (sibling repo `moco-v3/moco/builder.py`; SURVEY §2.9,
§3.5). Shapes are row-major and the negative block is one `[B, dim] x
[K, dim]^T` matmul so XLA tiles it straight onto the MXU; accumulation happens
in float32 regardless of input dtype (`preferred_element_type`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.parallel.collectives import all_gather_batch, batch_axis_index
from moco_tpu.telemetry.scopes import KEY_GATHER


def l2_normalize(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Row-wise L2 normalization (the reference's `nn.functional.normalize`)."""
    return x / jnp.sqrt(
        jnp.maximum(jnp.sum(jnp.square(x), axis=-1, keepdims=True), eps)
    )


def infonce_logits(
    q: jax.Array, k: jax.Array, queue: jax.Array, temperature: float
) -> tuple[jax.Array, jax.Array]:
    """(K+1)-way contrastive logits with the positive at column 0.

    Rebuild of `moco/builder.py:≈L140-160`:
      l_pos = einsum('nc,nc->n', q, k);  l_neg = q @ queue^T  (queue detached)
      logits = concat([l_pos, l_neg]) / T;  labels = zeros (positive first).

    `q`/`k` must be L2-normalized; `k` and `queue` must be stop-gradiented by
    the caller (no gradient ever reaches the key encoder or the queue —
    pinned by tests/test_train_step.py).
    """
    l_pos = jnp.einsum(
        "nc,nc->n", q, k, preferred_element_type=jnp.float32
    )[:, None]
    l_neg = jnp.einsum(
        "nc,kc->nk", q, lax.stop_gradient(queue), preferred_element_type=jnp.float32
    )
    logits = jnp.concatenate([l_pos, l_neg], axis=1) / temperature
    labels = jnp.zeros(q.shape[0], dtype=jnp.int32)
    return logits, labels


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over the batch (the reference's `nn.CrossEntropyLoss`)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1))


def contrastive_accuracy(
    logits: jax.Array, labels: jax.Array, topk: tuple[int, ...] = (1, 5)
) -> tuple[jax.Array, ...]:
    """Top-k accuracy over the (K+1)-way logits (rebuild of `accuracy`,
    `main_moco.py:≈L390-405`): the fraction of samples whose positive
    outranks all queue negatives (within top-k).

    Rank-count formulation instead of `lax.top_k`: the label column is in
    the top-k iff fewer than k columns score strictly higher. One compare +
    row-sum over [B, K+1] — O(BK) elementwise, no sort. This matters twice:
    `lax.top_k` over K+1 columns ran EVERY train step (it dominated the CPU
    horizon step at K=4096, ~22 of 25 s), and on TPU at K=65536 the per-step
    sort network is pure overhead for a 2-number metric. Tie semantics:
    strictly-greater counting credits the positive on exact float ties,
    matching torch `topk`'s first-occurrence behavior for equal values up
    to column order. A NaN label logit compares False against everything
    (n_better = 0), which would silently score as a top-k hit — the
    finiteness AND below keeps a diverged row a miss, like the old top_k
    formulation."""
    valid = labels >= 0  # eval paths pad ragged tails with label -1
    label_logit = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1
    )
    valid &= jnp.isfinite(label_logit[:, 0])
    n_better = jnp.sum((logits > label_logit), axis=-1)  # [B]
    return tuple(100.0 * jnp.mean((n_better < k) & valid) for k in topk)


def v3_contrastive_loss(
    q: jax.Array, k: jax.Array, temperature: float, axis_name,
    chunks: int = 1
) -> jax.Array:
    """One direction of the MoCo-v3 queue-free loss (SURVEY §3.5).

    `k` is all-gathered over the data axis so negatives are the OTHER
    in-batch samples across the whole global batch; the positive for local
    row i is global row `rank*B_local + i` (the reference's
    `labels = arange(N) + N*rank`). Loss is scaled by 2*T as in the paper's
    implementation. `q`/`k` must be L2-normalized, `k` stop-gradiented.

    `axis_name` may be a tuple (the 2-D data×fsdp mesh, ISSUE 15); `chunks`
    applies the FAST-style chunked gather schedule — the reassembled
    negatives are bit-identical either way (collectives.all_gather_batch).
    """
    k = lax.stop_gradient(k)
    if axis_name is not None:
        with jax.named_scope(KEY_GATHER):
            k_all = all_gather_batch(k, axis_name, chunks)
        offset = batch_axis_index(axis_name) * q.shape[0]
    else:
        k_all, offset = k, 0
    logits = (
        jnp.einsum("nc,mc->nm", q, k_all, preferred_element_type=jnp.float32)
        / temperature
    )
    labels = jnp.arange(q.shape[0], dtype=jnp.int32) + offset
    return softmax_cross_entropy(logits, labels) * (2.0 * temperature)
