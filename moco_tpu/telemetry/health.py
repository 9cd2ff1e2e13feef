"""In-graph learning-health diagnostics (ISSUE 13 tentpole part a).

Systems observability (tracing, obsd) says whether the machine is
healthy; nothing so far says whether MoCo is *learning*. The failure
modes the paper's mechanism admits — representation collapse (every
input maps to one feature), a frozen/diverged key encoder, a queue full
of stale or degenerate negatives — are SILENT: the loss keeps moving
against a degenerate contrast set while the features rot. This module
computes the cheap in-graph signals that make those modes visible:

  per-dim embedding std      mean over dims of the per-dim std across
                             the (local) batch; a collapsed encoder
                             drives it to ~0 while loss still "trains"
  participation ratio        tr(C)^2 / tr(C^2) of the embedding
                             covariance — the effective number of
                             dimensions the batch actually occupies
                             (1 = rank-one collapse, D = isotropic);
                             computed without an eigendecomposition
  logit margin               pos_sim − mean neg_sim (both ×T): the
                             contrast the loss is actually working
                             with. A margin pinned at ~0 means the
                             positives are indistinguishable from the
                             negatives — collapse, or a degenerate
                             queue
  queue feature-norm stats   rows are L2-normalized at enqueue, so a
                             norm drifting from 1 (or ~0: a crushed
                             encoder's eps-floored zero vector) marks
                             degenerate entries
  ptr-derived queue age      how many steps ago the OLDEST live queue
                             row was enqueued (each step advances the
                             ptr by the global batch, so a full queue
                             is K/B steps deep): the staleness of the
                             negative set relative to the encoder
  query↔key parameter drift  ‖θ_q − θ_k‖ / ‖θ_q‖ over the EMA-covered
                             subtree: ~0 means the EMA collapsed onto
                             the query encoder (or nothing is moving)
  grad norm by layer group   global grad L2 + first/last top-level
                             parameter group — a vanishing head (or
                             stem) gradient is the earliest signal of
                             a dead loss
  expert load                a routed encoder only: assignments to the
                             experts held here a token, and the fullest
                             held expert's load over the mean — a
                             router that starves or floods this chip

Contract (the step builders enforce it; tests pin it):

  - `health_stride == 0` (the default): none of the gated diagnostics
    trace — only the two always-on standard metrics (below) exist, as
    extra scalars in the metrics reduce the step already runs.
  - `health_stride = N`: the diagnostics are traced into the step under
    ONE `lax.cond` on `step % N == 0`; off-stride steps select the
    cheap zero branch, and the scalars ride the EXISTING per-step
    metrics reduction — no new collectives, no host callbacks
    (progcheck audits the instrumented variants).
  - diagnostics are observational: they read state/activations and
    contribute nothing to the loss/update path, so the parameter
    trajectory with health on is BITWISE the trajectory with it off.

`neg_sim`/`logit_margin` are standard step metrics (always on, like
`pos_sim` — they reuse the already-computed logits), popped by the
driver like the gradsync probe scalars and consumed by the
CollapseSentinel (resilience/sentinel.py) and the telemetry `health`
record block.

`crush_key_params` is the chaos `collapse_at_step` payload: it rewrites
the key-encoder params so its features degenerate to one constant
vector — the injected collapse every layer above (sentinel, obsd SLO,
serve reload guard) is drilled against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# step-metric keys the driver pops before meters/scalar-writer see them
# (the gradsync gs_comm_* convention); the h_-prefixed ones exist only
# when health_stride > 0 and carry zeros on off-stride steps
HEALTH_PREFIX = "h_"
STANDARD_KEYS = ("neg_sim", "logit_margin")

# the canonical "on" stride (config default is 0 = off): what bench.py's
# health_overhead row measures against and the README documents — chosen
# so the amortized diagnostics cost stays well under 1% of step time
# while the sentinel still sees a fresh emb-std sample every few seconds
DEFAULT_STRIDE = 10


def neg_sim_mean(logits: jax.Array, labels: jax.Array,
                 temperature: float) -> jax.Array:
    """Mean negative-pair similarity ×T over the logit matrix, excluding
    each row's positive (the `labels` column). Works for both layouts:
    v1/v2 puts the positive at column 0 (labels are zeros), v3 at the
    global-batch diagonal offset."""
    total = jnp.sum(logits, dtype=jnp.float32)
    pos = jnp.sum(
        jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                            axis=-1),
        dtype=jnp.float32,
    )
    n, m = logits.shape
    return (total - pos) / (n * (m - 1)) * temperature


def embedding_stats(z: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(mean per-dim std, participation ratio) of a `[B, D]` embedding
    batch. The participation ratio tr(C)^2 / tr(C^2) needs only the
    covariance traces — one `[D, B] x [B, D]` matmul, no eig."""
    z = z.astype(jnp.float32)
    centered = z - jnp.mean(z, axis=0, keepdims=True)
    var = jnp.mean(jnp.square(centered), axis=0)            # [D]
    mean_std = jnp.mean(jnp.sqrt(var))
    cov = centered.T @ centered / z.shape[0]                # [D, D]
    tr = jnp.sum(var)
    tr_sq = jnp.sum(jnp.square(cov))
    pr = jnp.square(tr) / jnp.maximum(tr_sq, 1e-20)
    return mean_std, pr


def grad_group_norms(grads) -> dict[str, jax.Array]:
    """Global grad L2 norm + the first/last top-level parameter group's
    (sorted key order — deterministic for a given arch). Local per-device
    grads: the metrics pmean averages the per-device norms."""

    def _norm(tree) -> jax.Array:
        leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(tree)]
        total = sum(leaves) if leaves else jnp.zeros((), jnp.float32)
        return jnp.sqrt(total)

    out = {"h_gnorm": _norm(grads)}
    if isinstance(grads, dict) and grads:
        keys = sorted(grads)
        out["h_gnorm_first"] = _norm(grads[keys[0]])
        out["h_gnorm_last"] = _norm(grads[keys[-1]])
    return out


def _gated(step: jax.Array, stride: int, compute) -> dict[str, jax.Array]:
    """Trace `compute()` under ONE lax.cond on the health stride:
    off-stride steps select a same-structure zero branch, so the
    expensive diagnostics execute only every `stride` steps. The cond is
    a plain control-flow primitive — no collective, no callback — and
    its outputs join the step's EXISTING metrics reduction. The real
    branch is traced INSIDE the cond (only `eval_shape`d here for the
    zero branch's structure), so XLA never hoists the diagnostics onto
    the every-step path."""
    shapes = jax.eval_shape(compute)

    def zeros():
        # typed like the real branch: inside the shard_map region its
        # outputs vary over the mesh axes, and cond demands equal types
        return {
            k: lax.pcast(jnp.zeros(v.shape, v.dtype), tuple(v.vma),
                         to="varying") if v.vma
            else jnp.zeros(v.shape, v.dtype)
            for k, v in shapes.items()
        }

    return lax.cond(step % stride == 0, compute, zeros)


def region_health(q: jax.Array, k: jax.Array, grads, step: jax.Array,
                  stride: int) -> dict[str, jax.Array]:
    """The shard_map-region diagnostics (per-device batch slice, averaged
    by the caller's metrics pmean): embedding std/participation ratio on
    the query AND key embeddings, grad norms by layer group."""

    def compute():
        std_q, pr_q = embedding_stats(q)
        std_k, _ = embedding_stats(k)
        out = {"h_emb_std_q": std_q, "h_emb_pr_q": pr_q,
               "h_emb_std_k": std_k}
        out.update(grad_group_norms(grads))
        return out

    return _gated(step, stride, compute)


def encoder_counters(reduce, counted, tokens: int, step: jax.Array,
                     stride: int) -> dict[str, jax.Array]:
    """A token encoder's own counters: what its forward pass sowed (`counted`,
    by collection), reduced by its family's `health(counted, tokens)`
    (`models/sdar.py`: expert load; `models/ouro.py`: the loop's progress).
    Per-device views of the query forward; the caller's metrics pmean
    averages them."""
    return _gated(step, stride, lambda: reduce(counted, tokens))


def queue_health(queue: jax.Array, step: jax.Array, global_batch: int,
                 stride: int) -> dict[str, jax.Array]:
    """Queue-side diagnostics, computed at the OUTER jit level where the
    queue is replicated (no collective): row-norm mean/min + the
    ptr-derived age in steps of the oldest live entry (the enqueue
    advances the ptr by the global batch each step, so a warm queue is
    exactly K/B steps deep; before that the age is the step count)."""
    k_slots = queue.shape[0]
    depth = max(k_slots // max(global_batch, 1), 1)

    def compute():
        norms = jnp.sqrt(jnp.sum(
            jnp.square(queue.astype(jnp.float32)), axis=-1))
        return {
            "h_qnorm_mean": jnp.mean(norms),
            "h_qnorm_min": jnp.min(norms),
            "h_qage_steps": jnp.minimum(
                step, depth).astype(jnp.float32),
        }

    return _gated(step, stride, compute)


def param_drift(params_q, params_k, step: jax.Array,
                stride: int) -> dict[str, jax.Array]:
    """Relative query↔key parameter drift ‖θ_q − θ_k‖ / ‖θ_q‖ over the
    EMA-covered subtree (the caller passes the matching trees — v3 drops
    the predictor). Outer-level, replicated: no collective."""

    def compute():
        diff_sq = q_sq = jnp.zeros((), jnp.float32)
        for gq, gk in zip(jax.tree.leaves(params_q),
                          jax.tree.leaves(params_k)):
            gq = gq.astype(jnp.float32)
            diff_sq = diff_sq + jnp.sum(jnp.square(gq - gk.astype(jnp.float32)))
            q_sq = q_sq + jnp.sum(jnp.square(gq))
        return {"h_pdrift": jnp.sqrt(diff_sq)
                / jnp.maximum(jnp.sqrt(q_sq), 1e-12)}

    return _gated(step, stride, compute)


def crush_key_params(params_k):
    """The chaos `collapse_at_step` payload: a key-encoder param tree
    whose forward maps EVERY input to one constant feature vector —
    kernels (≥2-D leaves) AND normalization `scale` leaves zeroed,
    remaining 1-D leaves (biases/shifts) set to one, so every block
    emits a constant and the final layer's bias alone decides the
    output. Zeroing the BN/LN scales matters: the step's own EMA leaks
    (1−m)·θ_q back in BEFORE the key forward, and batch norm rescales
    any nonzero kernel back to O(1) input-dependent activations — with
    the scales at ~(1−m) that leak is attenuated to noise instead. The
    driver re-applies the crush after every step at/after the fault: the
    fault models a persistently-wedged momentum update, not a one-off
    corruption."""

    def crush(path, x):
        name = getattr(path[-1], "key", "") if path else ""
        if name == "scale" or x.ndim != 1:
            return jnp.zeros_like(x)
        return jnp.ones_like(x)

    return jax.tree_util.tree_map_with_path(crush, params_k)
