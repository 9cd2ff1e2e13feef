"""The MoCo pretrain step as ONE jitted SPMD program (SURVEY §7 design stance).

Rebuilds the whole per-step pipeline of `main_moco.py:≈L280-320` +
`MoCo.forward` (`moco/builder.py:≈L117-165`) as a single donated-state jit:

    outer jit level (replicated state, automatic partitioner):
        EMA key-encoder update  (BEFORE the key forward — ordering invariant)
        optimizer update from psum'd grads
        queue enqueue            (AFTER logits — keys never their own negatives)
    inner shard_map region (per-device semantics over the 1-D data mesh):
        ShuffleBN shuffle → key forward (per-device BN stats) → unshuffle
        query forward + InfoNCE + local grads → pmean (the DDP all-reduce)

The hybrid split exists because replicated-state updates derived from
`all_gather`ed values cannot be typed replicated inside shard_map (see
moco_tpu/parallel/collectives.py); outside, XLA's partitioner keeps them
replicated for free — and the whole thing still compiles to one program.

Per-step collectives (cf. SURVEY §3.1): 2 all-gathers of the local key batch
(shuffle-in, unshuffle) + 1 of the 128-d keys (enqueue) + the gradient sync
(ISSUE 6: `parallel/gradsync.py` — one fused pmean, per-bucket chained
psums, quantized reduce with error feedback, or DeMo-style sparse sync,
selected by `config.grad_sync`) + 1 tiny scalar psum (the comm-phase
grads-ready probe the telemetry fence drains). The reference's rank-0
permutation broadcast and DDP buffer re-broadcast are GONE — replaced by
deterministic shared-RNG permutation and replicated arithmetic (zero
communication).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from moco_tpu.config import PretrainConfig
from moco_tpu.models import (build_resnet, build_token_encoder, constant_modules,
                             is_token_encoder, token_counters)
from moco_tpu.models.sdar import trainable_mask
from moco_tpu.telemetry import health, scopes
from moco_tpu.ops.ema import ema_update, momentum_schedule
from moco_tpu.ops.losses import (
    contrastive_accuracy,
    infonce_logits,
    l2_normalize,
    softmax_cross_entropy,
)
from moco_tpu.ops.queue import dequeue_and_enqueue
from moco_tpu.parallel.collectives import (
    batch_shuffle,
    batch_unshuffle,
    device_local,
)
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.train_state import TrainState


def build_encoder(config: PretrainConfig):
    """Encoder factory — the reference's `models.__dict__[arch](num_classes=dim)`
    plus the v2 MLP-head splice (`moco/builder.py:≈L25-35`). For v3 the
    encoder is backbone→projector (+predictor on the query side), so this
    returns the composite `V3Model`."""
    dtype = jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32
    if is_token_encoder(config.arch):
        if config.variant == "v3":
            raise ValueError("a token encoder trains under the queue-based v2 step")
        return build_token_encoder(
            config.arch, num_classes=config.embed_dim, mlp_head=config.mlp_head,
            layers=config.num_hidden_layers, held=config.num_experts,
            vocab=config.vocab_size, dtype=dtype, remat=config.remat)
    if config.variant == "v3":
        from moco_tpu.v3_step import V3Model

        if config.arch.startswith("vit"):
            from moco_tpu.models.vit import build_vit

            backbone = build_vit(
                config.arch, num_classes=None, dtype=dtype, remat=config.remat
            )
        else:
            backbone = build_resnet(
                config.arch,
                num_classes=None,
                cifar_stem=config.cifar_stem,
                dtype=dtype,
                bn_cross_replica_axis=DATA_AXIS if config.sync_bn else None,
                remat=config.remat,
            )
        return V3Model(backbone, embed_dim=config.embed_dim)
    if config.arch.startswith("vit"):
        from moco_tpu.models.vit import build_vit

        return build_vit(
            config.arch, num_classes=config.embed_dim, dtype=dtype, remat=config.remat
        )
    return build_resnet(
        config.arch,
        num_classes=config.embed_dim,
        mlp_head=config.mlp_head,
        cifar_stem=config.cifar_stem,
        dtype=dtype,
        bn_cross_replica_axis=DATA_AXIS if config.sync_bn else None,
        remat=config.remat,
    )


def lr_schedule(config: PretrainConfig, steps_per_epoch: int) -> Callable:
    """Step→lr. v1/v2: evaluated at integer epochs (`floor(step/spe)`) to
    match the reference's per-epoch `adjust_learning_rate`
    (`main_moco.py:≈L377-388`). v3: FRACTIONAL epoch — the moco-v3 driver
    adjusts per-iteration (`epoch + i/len(loader)`), and with per-epoch
    stepping the whole first warmup epoch would run at lr=0."""
    from moco_tpu.ops.schedules import cosine_lr, step_lr, warmup_cosine_lr

    lr = config.effective_lr  # resolves base_lr × batch/256 presets

    def sched(step):
        epoch = jnp.asarray(step, jnp.float32) / steps_per_epoch
        if config.variant != "v3":
            epoch = jnp.floor(epoch)
        if config.warmup_epochs > 0:
            return warmup_cosine_lr(lr, epoch, config.epochs, config.warmup_epochs)
        if config.cos:
            return cosine_lr(lr, epoch, config.epochs)
        return step_lr(lr, epoch, config.schedule)

    return sched


def build_optimizer(
    config: PretrainConfig, steps_per_epoch: int
) -> tuple[optax.GradientTransformation, Callable]:
    """The reference's SGD(momentum=0.9, wd=1e-4) with wd folded into the
    momentum buffer (torch semantics: wd enters the gradient BEFORE the
    momentum trace), plus v3's AdamW/LARS options (SURVEY §2.9)."""
    sched = lr_schedule(config, steps_per_epoch)
    if config.optimizer == "sgd":
        tx = optax.chain(
            optax.add_decayed_weights(config.weight_decay),
            optax.sgd(sched, momentum=config.sgd_momentum),
        )
    elif config.optimizer == "adamw":
        tx = optax.adamw(sched, weight_decay=config.weight_decay)
    elif config.optimizer == "lars":
        # moco-v3's LARS (R50 recipe) excludes bias/BN (1-D) params from BOTH
        # weight decay and the trust-ratio adaptation — they get plain
        # momentum SGD at the base lr
        def dim_mask(params):
            return jax.tree.map(lambda p: jnp.ndim(p) > 1, params)

        tx = optax.lars(
            sched,
            weight_decay=config.weight_decay,
            weight_decay_mask=dim_mask,
            trust_ratio_mask=dim_mask,
            momentum=config.sgd_momentum,
        )
    else:
        raise ValueError(f"unknown optimizer {config.optimizer!r}")
    if config.variant == "v3" and config.arch.startswith("vit"):
        # frozen random patch projection: stop_gradient in the model zeroes
        # the grads; the mask stops weight decay from moving the params too
        from moco_tpu.v3_step import patch_embed_trainable_mask

        tx = optax.masked(tx, patch_embed_trainable_mask)
    constant = (constant_modules(config.arch, config.num_experts)
                if is_token_encoder(config.arch) else ())
    if constant:
        # a share of an expert layer does not train its router (models/sdar.py),
        # nor a selecting attention its indexer (models/keye.py): the same
        # pattern, stop_gradient in the model and the mask for the decay
        tx = optax.masked(tx, lambda params: trainable_mask(params, constant))
    return tx, sched


def build_fused_step(step_fn, two_crops_fn, data_key):
    """ONE program per step: augmentation + train step in a single donated
    jit. Separate aug / fold_in / step programs would each pay a dispatch;
    in-program, XLA can also overlap the aug's VPU work with weight
    prefetches. Shared by the train driver and bench.py so the benchmark
    measures exactly the program training runs."""
    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fused_step(state, imgs_u8, extents, step):
        with jax.named_scope(scopes.AUG):
            key = jax.random.fold_in(data_key, step)
            im_q, im_k = two_crops_fn(imgs_u8, key, extents)
        return step_fn(state, im_q, im_k)

    return fused_step


def _build_key_path(config: PretrainConfig, model):
    """The region's key-encoder branch as ONE shared function: ShuffleBN
    shuffle → key forward (per-device BN stats) → unshuffle → L2-norm →
    `stop_gradient` (the reference's no_grad key path, `moco/builder.py`).

    Shared by the spmd_region AND `build_grad_probe` so the audited program
    (progcheck P1: no differentiable path from the loss into the key
    encoder) is the SAME code the train step traces — deleting the
    stop_gradient here changes both, and the auditor fires."""

    chunks = int(getattr(config, "collective_chunks", 1))

    def key_path(params_k, stats_k, im_k, key):
        if not jax.tree.leaves(stats_k):
            # an encoder without BatchNorm (models/sdar.py) has no batch
            # statistics to leak: rows are independent, ShuffleBN is a no-op
            # and is left out, and so are its two gathers
            k = model.apply({"params": params_k}, im_k, train=True)
            return lax.stop_gradient(l2_normalize(k)), stats_k
        with jax.named_scope(scopes.SHUFFLE_BN):
            if config.shuffle_mode == "ring":
                from moco_tpu.parallel.collectives import ring_shuffle

                im_k_shuf = ring_shuffle(im_k, DATA_AXIS)
            else:
                im_k_shuf, perm = batch_shuffle(im_k, key, DATA_AXIS, chunks)
        k, mut_k = model.apply(
            {"params": params_k, "batch_stats": stats_k},
            im_k_shuf,
            train=True,
            mutable=["batch_stats"],
        )
        k = l2_normalize(k)
        with jax.named_scope(scopes.KEY_GATHER):
            if config.shuffle_mode == "ring":
                k = ring_shuffle(k, DATA_AXIS, inverse=True)
            else:
                k = batch_unshuffle(k, perm, DATA_AXIS, chunks)
        k = lax.stop_gradient(k)  # the reference's no_grad key path
        return k, mut_k["batch_stats"]

    return key_path


def _build_query_loss(config: PretrainConfig, model, temperature: float):
    """The region's differentiable core: query forward → InfoNCE against
    (keys, queue). Shared by the spmd_region's value_and_grad and the
    grad-flow probe (which also differentiates w.r.t. the queue)."""

    # what the forward pass hands out beside the embedding: BatchNorm's batch
    # statistics, and a token encoder's own counts where the counters are on
    mutable = ["batch_stats"]
    if config.health_stride and is_token_encoder(config.arch):
        mutable += token_counters(config.arch)[0]

    def query_loss(pq, stats_q, im_q, k, queue):
        q, mut_q = model.apply(
            {"params": pq, "batch_stats": stats_q},
            im_q,
            train=True,
            mutable=mutable,
        )
        q = l2_normalize(q)
        # innermost recognised scope wins in the trace's reduction: the
        # logits against the queue and the loss (forward and, through the
        # enclosing value_and_grad, backward) are `loss_queue`'s, not the
        # encoder's
        with jax.named_scope(scopes.LOSS_QUEUE):
            logits, labels = infonce_logits(q, k, queue, temperature)
            loss = softmax_cross_entropy(logits, labels)
        # q rides the aux for the health diagnostics (ISSUE 13) — already
        # computed, and DCE'd by XLA wherever nothing consumes it
        return loss, (
            mut_q.get("batch_stats", stats_q),
            logits,
            labels,
            q,
            {name: mut_q[name] for name in mutable[1:] if name in mut_q},
        )

    return query_loss


def build_grad_probe(config: PretrainConfig, model, mesh):
    """The differentiable audit surface (ISSUE 9, tools/progcheck P1).

    Returns a shard_map'd `(params_q, params_k, stats_q, stats_k, queue,
    im_q, im_k, key) -> (g_q, g_k, g_queue)` that differentiates the SAME
    key-path + InfoNCE code the train step traces — w.r.t. the query params
    AND the key params AND the queue. The MoCo contract (He et al.) is that
    the key branch ends in stop_gradient, so `g_k`/`g_queue` must be
    STRUCTURALLY zero: progcheck proves from the jaxpr that those outputs
    depend on no program input, instead of sampling finite differences.
    Grads route through the fused GradSync reduce (lint R7: grads meet
    collectives only via the gradsync API)."""
    from moco_tpu.parallel.gradsync import GradSync

    temperature = config.temperature
    key_path = _build_key_path(config, model)
    query_loss = _build_query_loss(config, model, temperature)
    gradsync = GradSync(config.replace(grad_sync="fused"), mesh.size)

    def probe(params_q, params_k, stats_q, stats_k, queue, im_q, im_k, key):
        def loss_of(pq, pk, qu):
            k, _ = key_path(pk, stats_k, im_k, key)
            loss, _aux = query_loss(pq, stats_q, im_q, k, qu)
            return loss

        grads = jax.grad(loss_of, argnums=(0, 1, 2))(
            *device_local((params_q, params_k, queue), DATA_AXIS))
        reduced, _, _probe = gradsync.region_reduce(grads, {}, jnp.int32(0))
        return reduced

    return jax.shard_map(
        probe,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=P(),
    )


def build_train_step(config: PretrainConfig, model, tx, mesh,
                     steps_per_epoch: int, sched=None, state=None):
    """Return jitted `(state, im_q, im_k) -> (state', metrics)`, state donated.

    `im_q`/`im_k` are GLOBAL `[B, H, W, C]` batches (sharded over the data
    axis by the input pipeline); metrics are replicated scalars.

    `sched` must be the schedule returned by `build_optimizer` for the SAME
    `steps_per_epoch` — pass it through so the logged `metrics['lr']` is by
    construction the lr optax applies. If omitted it is re-derived here with
    this call's `steps_per_epoch`.

    `state` (an example TrainState; abstract shapes suffice) is required
    only for the FSDP-sharded v3 step (ISSUE 15) — the per-leaf shard axes
    are fixed from its shapes at build time.
    """
    if config.shuffle_mode not in ("permute", "ring"):
        raise ValueError(f"unknown shuffle_mode {config.shuffle_mode!r}")
    if config.variant == "v3":
        from moco_tpu.v3_step import build_v3_train_step

        return build_v3_train_step(config, model, tx, mesh, steps_per_epoch,
                                   sched, state)

    temperature = config.temperature
    total_steps = config.epochs * steps_per_epoch
    if sched is None:
        sched = lr_schedule(config, steps_per_epoch)
    # gradient sync strategy (ISSUE 6): the ONLY place grads meet a
    # collective — lint R7 forbids pmean/psum on grads outside parallel/
    from moco_tpu.parallel.gradsync import GradSync

    gradsync = GradSync(config, mesh.size)

    # --- ShuffleBN key path + InfoNCE core, factored so build_grad_probe
    # audits exactly this code (ISSUE 9): "permute" = the reference-faithful
    # all-gather + shared-RNG global permutation; "ring" = half-shard roll
    # (2 ppermutes, partial decorrelation — see collectives.ring_shuffle for
    # why whole-shard rotation would be a no-op)
    key_path = _build_key_path(config, model)
    query_loss = _build_query_loss(config, model, temperature)

    def spmd_region(params_q, params_k, stats_q, stats_k, queue, gs_state,
                    im_q, im_k, key, step):
        with jax.named_scope(scopes.K_FWD):
            k, new_stats_k_local = key_path(params_k, stats_k, im_k, key)

        def loss_fn(pq):
            return query_loss(pq, stats_q, im_q, k, queue)

        # w.r.t. the device-local view: the grads come out per-device and
        # gradsync's reduce below is the only one (collectives.device_local)
        with jax.named_scope(scopes.Q_FWD_BWD):
            (loss, (new_stats_q, logits, labels, q, counted)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(device_local(params_q, DATA_AXIS))
        # DDP-equivalent gradient sync (mean over the data axis) through the
        # configured strategy; demo's replicated merge happens outside
        with jax.named_scope(scopes.OPT_EMA), jax.named_scope(scopes.GRAD_SYNC):
            payload, gs_new, gs_probe = gradsync.region_reduce(
                grads, gs_state, step)
        # Running BN stats: averaged across devices so replicas stay
        # bit-identical (replaces DDP broadcast_buffers, SURVEY §2.2 note).
        with jax.named_scope(scopes.Q_FWD_BWD):
            new_stats_q = lax.pmean(new_stats_q, DATA_AXIS)
        with jax.named_scope(scopes.K_FWD):
            new_stats_k = lax.pmean(new_stats_k_local, DATA_AXIS)
        with jax.named_scope(scopes.LOSS_QUEUE):
            acc1, acc5 = contrastive_accuracy(logits, labels)
            # positive-pair cosine alignment (column 0 is q·k⁺/T): the
            # cheapest honest learning signal — only aug-invariance
            # optimization moves it, so a silently frozen encoder leaves it
            # at its init value while loss/acc metrics can still look
            # plausible against a frozen-feature queue (measured r5)
            pos_sim = jnp.mean(logits[:, 0]) * temperature
            # the contrast the loss works with (ISSUE 13 standard metrics,
            # popped by the driver like the gs_comm_* probes): a margin
            # pinned at ~0 is collapse or a degenerate queue
            neg_sim = health.neg_sim_mean(logits, labels, temperature)
            metrics = {"loss": loss, "acc1": acc1, "acc5": acc5,
                       "pos_sim": pos_sim, "neg_sim": neg_sim,
                       "logit_margin": pos_sim - neg_sim}
            if config.health_stride:
                # stride-gated collapse diagnostics (ISSUE 13): they join
                # the SAME metrics pmean below — no new collectives
                metrics.update(health.region_health(
                    q, k, grads, step, config.health_stride))
                if counted:     # a token encoder's own counters, reduced by its family
                    metrics.update(health.encoder_counters(
                        token_counters(config.arch)[1], counted,
                        im_q.shape[0] * im_q.shape[1], step, config.health_stride))
            metrics = lax.pmean(metrics, DATA_AXIS)
        return payload, gs_new, gs_probe, k, new_stats_q, new_stats_k, metrics

    region = jax.shard_map(
        spmd_region,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(), P()),
        out_specs=(gradsync.payload_specs(P), P(DATA_AXIS), P(), P(DATA_AXIS),
                   P(), P(), P()),
    )

    def train_step(state: TrainState, im_q, im_k):
        with jax.named_scope(scopes.K_FWD):
            shuffle_key = jax.random.fold_in(state.rng, state.step)
        with jax.named_scope(scopes.OPT_EMA):
            if config.momentum_ramp:
                m = momentum_schedule(config.momentum_ema, state.step,
                                      total_steps)
            else:
                m = config.momentum_ema
            # EMA BEFORE the key forward, every step
            # (`moco/builder.py:≈L120-124`)
            params_k = ema_update(state.params_k, state.params_q, m)
            # barrier: without it XLA interleaves the ~163 per-leaf EMA
            # fusions with the optimizer's per-leaf fusions and the VMEM
            # prefetcher, costing ~20 ms/step of copy stalls on the v5e
            # (measured r2: the update phase alone is 24.8 ms interleaved
            # vs 5.0 ms fenced)
            params_k = lax.optimization_barrier(params_k)
        payload, gs_new, gs_probe, k_global, stats_q, stats_k, metrics = region(
            state.params_q,
            params_k,
            state.batch_stats_q,
            state.batch_stats_k,
            state.queue,
            state.gradsync,
            im_q,
            im_k,
            shuffle_key,
            state.step,
        )
        # demo's sparse merge (a no-op for the dense modes) lives at the
        # outer jit level: replicated values derived from gathered ones
        # cannot be typed replicated inside the region (collectives.py note)
        with jax.named_scope(scopes.OPT_EMA):
            with jax.named_scope(scopes.GRAD_SYNC):
                grads = gradsync.finalize(payload, state.step)
            grads = lax.optimization_barrier(grads)  # fence bwd from the update phase
            updates, opt_state = tx.update(grads, state.opt_state, state.params_q)
            params_q = optax.apply_updates(state.params_q, updates)
            lr = sched(state.step)
            with jax.named_scope(scopes.GRAD_SYNC):
                gs_post = gradsync.probe_post(grads)
            next_step = state.step + 1
        with jax.named_scope(scopes.LOSS_QUEUE):
            # enqueue AFTER the logits (`moco/builder.py:≈L160-163`)
            queue, queue_ptr = dequeue_and_enqueue(
                state.queue, state.queue_ptr, k_global
            )
        metrics = dict(
            metrics, lr=lr, queue_ptr=queue_ptr,
            # comm-phase probes (telemetry/timing.py): drained in order by
            # the stride-gated fence, popped by the driver before display
            gs_comm_pre=gs_probe, gs_comm_post=gs_post,
        )
        if config.health_stride:
            # replicated-state diagnostics (ISSUE 13) live at the outer
            # jit level where queue/params are replicated: no collective
            with jax.named_scope(scopes.LOSS_QUEUE):
                metrics.update(health.queue_health(
                    state.queue, state.step, config.batch_size,
                    config.health_stride))
                metrics.update(health.param_drift(
                    state.params_q, params_k, state.step,
                    config.health_stride))
        new_state = state.replace(
            step=next_step,
            params_q=params_q,
            params_k=params_k,
            batch_stats_q=stats_q,
            batch_stats_k=stats_k,
            opt_state=opt_state,
            queue=queue,
            queue_ptr=queue_ptr,
            gradsync=gs_new,
        )
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,))
