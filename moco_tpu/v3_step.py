"""MoCo v3 — queue-free, symmetric, large-batch contrastive step
(BASELINE config 5; SURVEY §2.9 / §3.5, sibling repo `moco-v3`).

Differences from the v1/v2 step (train_step.py), per the reference:
- No queue, no ShuffleBN. Negatives are the OTHER in-batch samples,
  all-gathered across the data mesh.
- Both crops go through BOTH encoders; the loss is symmetric:
  `ctr(q1, k2) + ctr(q2, k1)`, each scaled by 2·T.
- The query model adds a 2-layer PREDICTOR on top of the projector; the
  momentum encoder is backbone+projector only. EMA therefore covers the
  params_q subtree MINUS the predictor.
- Momentum ramps 0.99 → 1.0 on a cosine over training.
- ViT: the patch-projection is frozen at random init — `stop_gradient` in
  the model (models/vit.py) plus an optimizer mask here so weight decay
  cannot move the frozen params either (== `requires_grad=False`).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from moco_tpu.config import PretrainConfig
from moco_tpu.models.heads import V3Predictor, V3Projector
from moco_tpu.ops.ema import ema_update, momentum_schedule
from moco_tpu.ops.losses import l2_normalize, v3_contrastive_loss
from moco_tpu.parallel.collectives import all_gather_batch, device_local
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.telemetry import health, scopes
from moco_tpu.train_state import TrainState, compiled_init, no_span

PREDICTOR_KEY = "predictor"


class V3Model(nn.Module):
    """backbone → projector (→ predictor when `predict=True`).

    One module serves both roles: the key encoder applies it with
    `predict=False` and a params tree lacking the predictor subtree.
    """

    backbone: nn.Module
    embed_dim: int = 256
    hidden_dim: int = 4096

    @nn.compact
    def __call__(self, x, train: bool = True, predict: bool = False):
        f = self.backbone(x, train=train)
        z = V3Projector(self.hidden_dim, self.embed_dim, name="projector")(f, train=train)
        if predict:
            z = V3Predictor(self.hidden_dim, self.embed_dim, name=PREDICTOR_KEY)(
                z, train=train
            )
        return z


def encoder_subtree(tree):
    """Drop the predictor subtree — the part of params_q the EMA covers."""
    return {k: v for k, v in tree.items() if k != PREDICTOR_KEY}


def patch_embed_trainable_mask(params) -> Any:
    """Optimizer mask: False for every leaf under a `patch_embed` module."""

    def is_trainable(path, _leaf):
        return not any(
            getattr(entry, "key", None) == "patch_embed" for entry in path
        )

    return jax.tree_util.tree_map_with_path(is_trainable, params)


@functools.partial(jax.jit, static_argnames=("model", "input_shape"))
def _init_v3_fields(rng, *, model: V3Model, input_shape):
    init_key, state_key = jax.random.split(rng)
    variables = model.init(
        init_key, jnp.zeros(input_shape, jnp.float32), train=False, predict=True
    )
    params_q = variables["params"]
    batch_stats_q = variables.get("batch_stats", {})
    return dict(
        step=jnp.zeros((), jnp.int32),
        params_q=params_q,
        params_k=jax.tree.map(jnp.copy, encoder_subtree(params_q)),
        batch_stats_q=batch_stats_q,
        batch_stats_k=jax.tree.map(jnp.copy, encoder_subtree(batch_stats_q)),
        queue=None,
        queue_ptr=None,
        rng=state_key,
    )


def create_v3_train_state(
    rng: jax.Array, model: V3Model, tx: optax.GradientTransformation, input_shape,
    span=no_span,
) -> TrainState:
    """Init query model (with predictor); key tree = encoder subtree copy.
    `span(name)` opens the driver's set-up span of that name, as in
    `create_train_state`: each times a compiled call that is waited for."""
    init_fields = functools.partial(
        _init_v3_fields, rng, model=model, input_shape=tuple(input_shape))
    return compiled_init(init_fields, tx, span)


def _build_apply(model: V3Model):
    def apply(params, stats, x, predict):
        out, mut = model.apply(
            {"params": params, "batch_stats": stats},
            x,
            train=True,
            predict=predict,
            mutable=["batch_stats"],
        )
        return l2_normalize(out), mut["batch_stats"]

    return apply


def _build_momentum_keys(model: V3Model):
    """The momentum-encoder branch, shared by the spmd_region and
    `build_v3_grad_probe` (ISSUE 9): keys for both crops (running stats
    chained through the two forwards, as two sequential reference forward
    calls would), stop-gradded — the v3 contract that no gradient reaches
    the momentum encoder."""
    apply = _build_apply(model)

    def momentum_keys(params_k, stats_k, x1, x2):
        k1, stats_k = apply(params_k, stats_k, x1, predict=False)
        k2, stats_k = apply(params_k, stats_k, x2, predict=False)
        k1 = lax.stop_gradient(k1)
        k2 = lax.stop_gradient(k2)
        return k1, k2, stats_k

    return momentum_keys


def _build_query_loss(model: V3Model, temperature: float,
                      batch_axis=DATA_AXIS, chunks: int = 1):
    """The symmetric v3 contrastive core, shared by the spmd_region's
    value_and_grad and the grad-flow probe. `batch_axis` is the data axis
    (or the 2-D mesh's axis tuple — ISSUE 15); `chunks` routes the key
    gathers through the FAST-style chunked schedule."""
    apply = _build_apply(model)

    def query_loss(pq, stats_q, x1, x2, k1, k2):
        q1, s = apply(pq, stats_q, x1, predict=True)
        q2, s = apply(pq, s, x2, predict=True)
        # innermost recognised scope wins in the trace's reduction: the
        # symmetric loss (with the key gathers inside it, and through the
        # enclosing value_and_grad its backward) is `loss_queue`'s, not the
        # encoder's
        with jax.named_scope(scopes.LOSS_QUEUE):
            loss = v3_contrastive_loss(q1, k2, temperature, batch_axis, chunks) + \
                   v3_contrastive_loss(q2, k1, temperature, batch_axis, chunks)
        return loss, (s, q1)

    return query_loss


def build_v3_grad_probe(config: PretrainConfig, model: V3Model, mesh):
    """The v3 differentiable audit surface (ISSUE 9, tools/progcheck P1):
    shard_map'd `(params_q, params_k, stats_q, stats_k, x1, x2) ->
    (g_q, g_k)` differentiating the SAME momentum-key + symmetric-loss code
    the v3 step traces, w.r.t. the query AND momentum params. The momentum
    branch ends in stop_gradient, so `g_k` must be structurally zero —
    progcheck proves it from the jaxpr. Grads route through the fused
    GradSync reduce (lint R7)."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.gradsync import GradSync

    momentum_keys = _build_momentum_keys(model)
    query_loss = _build_query_loss(model, config.temperature)
    gradsync = GradSync(config.replace(grad_sync="fused"), mesh.size)

    def probe(params_q, params_k, stats_q, stats_k, x1, x2):
        def loss_of(pq, pk):
            k1, k2, _ = momentum_keys(pk, stats_k, x1, x2)
            loss, _aux = query_loss(pq, stats_q, x1, x2, k1, k2)
            return loss

        grads = jax.grad(loss_of, argnums=(0, 1))(
            *device_local((params_q, params_k), DATA_AXIS))
        reduced, _, _probe = gradsync.region_reduce(grads, {}, jnp.int32(0))
        return reduced

    return jax.shard_map(
        probe,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
    )


def build_v3_train_step(
    config: PretrainConfig, model: V3Model, tx, mesh, steps_per_epoch: int,
    sched=None, state=None,
):
    """Jitted `(state, x1, x2) -> (state', metrics)`, state donated.

    With `config.sharding != "dp"` (ISSUE 15) the step is FSDP-sharded:
    `state` (an example TrainState — abstract shapes suffice) is required
    so the per-leaf shard axes are fixed at build time; params enter the
    region as fsdp shards, are all-gathered on use, and the GradSync-
    reduced gradient is sliced back to the shard before it leaves the
    region. The dp path is byte-for-byte the pre-ISSUE-15 program.
    """
    from moco_tpu.parallel.collectives import batch_axis_index
    from moco_tpu.parallel.fsdp import plan_for
    from moco_tpu.parallel.gradsync import GradSync
    from moco_tpu.train_step import lr_schedule

    temperature = config.temperature
    total_steps = config.epochs * steps_per_epoch
    if sched is None:
        sched = lr_schedule(config, steps_per_epoch)
    plan = plan_for(config, mesh)
    if plan is None:
        batch_axis = DATA_AXIS
        gradsync = GradSync(config, mesh.size)
    else:
        if state is None:
            raise ValueError(
                f"sharding={config.sharding!r} needs the example `state` at "
                "step-build time (the per-leaf shard axes come from its "
                "shapes) — the driver passes the freshly-created TrainState"
            )
        batch_axis = plan.batch_axes
        gradsync = GradSync.for_mesh(config, mesh)
        q_axes = plan.axis_tree(state.params_q)
        k_axes = plan.axis_tree(state.params_k)
        q_specs = plan.specs(state.params_q)
        k_specs = plan.specs(state.params_k)
    chunks = int(getattr(config, "collective_chunks", 1))
    momentum_keys = _build_momentum_keys(model)
    query_loss = _build_query_loss(model, temperature, batch_axis, chunks)

    def spmd_region(params_q, params_k, stats_q, stats_k, gs_state, x1, x2,
                    step):
        if plan is not None:
            # all-gather-on-use: the full weights exist only inside the
            # region's forward/backward window
            with jax.named_scope(scopes.Q_FWD_BWD):
                params_q = plan.gather(params_q, q_axes)
            with jax.named_scope(scopes.K_FWD):
                params_k = plan.gather(params_k, k_axes)
        with jax.named_scope(scopes.K_FWD):
            k1, k2, stats_k = momentum_keys(params_k, stats_k, x1, x2)

        def loss_fn(pq):
            return query_loss(pq, stats_q, x1, x2, k1, k2)

        # w.r.t. the device-local view: the grads come out per-device and
        # gradsync's reduce below is the only one (collectives.device_local)
        with jax.named_scope(scopes.Q_FWD_BWD):
            (loss, (new_stats_q, q1)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(device_local(params_q, batch_axis))
        with jax.named_scope(scopes.OPT_EMA), jax.named_scope(scopes.GRAD_SYNC):
            payload, gs_new, gs_probe = gradsync.region_reduce(
                grads, gs_state, step)
            if plan is not None and gradsync.mode != "demo":
                # reduce-scatter: the reduced full grads leave the region as
                # this device's shard (demo's sparse payload merges outside)
                payload = plan.scatter(payload, q_axes)
        with jax.named_scope(scopes.Q_FWD_BWD):
            new_stats_q = lax.pmean(new_stats_q, batch_axis)
        with jax.named_scope(scopes.K_FWD):
            new_stats_k = lax.pmean(stats_k, batch_axis)
        with jax.named_scope(scopes.LOSS_QUEUE):
            # monitoring: in-batch top-1 for the q1·k2 direction
            with jax.named_scope(scopes.KEY_GATHER):
                k2_all = all_gather_batch(k2, batch_axis, chunks)
            logits = jnp.einsum("nc,mc->nm", q1, k2_all, preferred_element_type=jnp.float32)
            labels = jnp.arange(q1.shape[0]) + batch_axis_index(batch_axis) * q1.shape[0]
            acc1 = 100.0 * jnp.mean(jnp.argmax(logits, axis=-1) == labels)
            # positive-pair alignment, same frozen-encoder detector as the
            # v1/v2 step's pos_sim (q1/k2 are L2-normalized, so the row-dot
            # is the cosine of the local positive pair)
            pos_sim = jnp.mean(jnp.sum(q1 * k2, axis=-1))
            # ISSUE 13 standard metrics: the monitoring logits are raw
            # cosines (no /T), so neg_sim_mean's ×T runs at T=1 here
            neg_sim = health.neg_sim_mean(logits, labels, 1.0)
            metrics = {"loss": loss, "acc1": acc1, "pos_sim": pos_sim,
                       "neg_sim": neg_sim, "logit_margin": pos_sim - neg_sim}
            if config.health_stride:
                # stride-gated collapse diagnostics (queue-free v3: no
                # queue stats) riding the SAME metrics pmean — no new
                # collectives
                metrics.update(health.region_health(
                    q1, k2, grads, step, config.health_stride))
            metrics = lax.pmean(metrics, batch_axis)
        return payload, gs_new, gs_probe, new_stats_q, new_stats_k, metrics

    if plan is None:
        in_specs = (P(), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS),
                    P(DATA_AXIS), P())
        out_specs = (gradsync.payload_specs(P), P(DATA_AXIS), P(), P(), P(),
                     P())
    else:
        batch_spec = P(plan.batch_axes)
        payload_spec = (gradsync.payload_specs(P)
                        if gradsync.mode == "demo" else q_specs)
        in_specs = (q_specs, k_specs, P(), P(), batch_spec, batch_spec,
                    batch_spec, P())
        out_specs = (payload_spec, batch_spec, P(), P(), P(), P())
    region = jax.shard_map(
        spmd_region,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )

    def train_step(state: TrainState, x1, x2):
        with jax.named_scope(scopes.OPT_EMA):
            if config.momentum_ramp:
                m = momentum_schedule(config.momentum_ema, state.step,
                                      total_steps)
            else:
                m = config.momentum_ema
            params_k = ema_update(state.params_k,
                                  encoder_subtree(state.params_q), m)
        payload, gs_new, gs_probe, stats_q, stats_k, metrics = region(
            state.params_q, params_k, state.batch_stats_q, state.batch_stats_k,
            state.gradsync, x1, x2, state.step,
        )
        with jax.named_scope(scopes.OPT_EMA):
            with jax.named_scope(scopes.GRAD_SYNC):
                grads = gradsync.finalize(payload, state.step)
            updates, opt_state = tx.update(grads, state.opt_state, state.params_q)
            params_q = optax.apply_updates(state.params_q, updates)
            lr = sched(state.step)
            with jax.named_scope(scopes.GRAD_SYNC):
                gs_post = gradsync.probe_post(grads)
            next_step = state.step + 1
        metrics = dict(
            metrics, lr=lr, momentum=m,
            gs_comm_pre=gs_probe, gs_comm_post=gs_post,
        )
        if config.health_stride:
            # q↔k drift over the EMA-covered subtree (the predictor is
            # query-only); outer level, replicated: no collective
            with jax.named_scope(scopes.LOSS_QUEUE):
                metrics.update(health.param_drift(
                    encoder_subtree(state.params_q), params_k, state.step,
                    config.health_stride))
        return (
            state.replace(
                step=next_step,
                params_q=params_q,
                params_k=params_k,
                batch_stats_q=stats_q,
                batch_stats_k=stats_k,
                opt_state=opt_state,
                gradsync=gs_new,
            ),
            metrics,
        )

    return jax.jit(train_step, donate_argnums=(0,))
