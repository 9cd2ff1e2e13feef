"""Pretrain driver (layer L4; rebuild of `main_moco.py`).

Control flow parity with `main_moco.py:≈L114-320` — argparse → build model/
optimizer/data → epoch loop → per-step train → meters → rank-0 checkpoint —
minus the process fan-out: there is no `mp.spawn`, no per-GPU worker; ONE
controller process per host drives all local chips through the jitted SPMD
step (SURVEY §2.10 process-topology row).

Usage:
    python -m moco_tpu.train --preset cifar10-moco-v1 --data-dir /data/cifar
    python -m moco_tpu.train --preset imagenet-moco-v2 --data-dir /data/imagenet
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from moco_tpu.checkpoint import (
    checkpoint_manager,
    finalize_checkpoints,
    maybe_resume,
    read_position,
    save_checkpoint,
)
from moco_tpu.config import PRESETS, PretrainConfig, get_preset
from moco_tpu.data import (
    aug_config_for,
    build_dataset,
    build_token_views_sharded,
    build_two_crops_sharded,
    epoch_loader,
    token_view_config_for,
)
from moco_tpu.models import attention_path, dispatch_path, held_vocab, is_token_encoder
from moco_tpu.ops.knn import knn_accuracy
from moco_tpu.parallel.mesh import create_mesh, local_batch_size
from moco_tpu.resilience import (
    CollapseError,
    CollapseSentinel,
    DataQualityError,
    NaNSentinel,
    NonFiniteLossError,
    PreemptionHandler,
    ResizeListener,
    RollbackExhaustedError,
    StepWatchdog,
    active_chaos,
    clear_chaos,
    install_chaos,
    parse_chaos_spec,
    write_resize_request,
)
from moco_tpu.telemetry import scopes
from moco_tpu.telemetry.trace import null_tracer
from moco_tpu.train_state import create_train_state, no_span
from moco_tpu.train_step import build_encoder, build_optimizer, build_train_step
from moco_tpu.utils.logging import ProfilerWindow, ScalarWriter, info, log_event
from moco_tpu.utils.meters import AverageMeter, ProgressMeter, RateMeter, Throughput


def make_feature_fn(model, variant: str):
    """Jitted frozen-encoder embedding fn for the kNN monitor (eval-mode BN).

    v3 embeds with the BACKBONE only — the probe/kNN protocol (and the
    sibling repo's eval) scores backbone features, not the 256-d projector
    space; the projector would make the monitor track a different geometry
    than the metric it is a proxy for (VERDICT r2 weak #5)."""

    if variant == "v3":
        backbone = model.backbone

        @jax.jit
        def feature_fn(params, batch_stats, images_f32):
            out = backbone.apply(
                {
                    "params": params["backbone"],
                    "batch_stats": batch_stats.get("backbone", {}),
                },
                images_f32,
                train=False,
            )
            return out / jnp.linalg.norm(out, axis=-1, keepdims=True)

        return feature_fn

    @jax.jit
    def feature_fn(params, batch_stats, images_f32):
        out = model.apply(
            {"params": params, "batch_stats": batch_stats},
            images_f32,
            train=False,
        )
        return out / jnp.linalg.norm(out, axis=-1, keepdims=True)

    return feature_fn


def knn_monitor(
    config, feature_fn, state, dataset, mesh=None, val_dataset=None
) -> tuple[float, bool]:
    """Periodic kNN top-1 (SURVEY §2.5 protocol at monitoring scale). The
    bank is a train subset; queries come from `val_dataset` when one exists
    (imagefolder `val/`, CIFAR test split) — a REAL val metric — else from a
    held-out train slice (logged as `knn_train_top1`). Returns
    (accuracy, is_real_val). `feature_fn` comes from `make_feature_fn` ONCE
    per run (recompiling the eval forward every epoch costs minutes on the
    sandbox)."""
    from moco_tpu.evals.knn import encode_dataset

    n = min(len(dataset), config.knn_bank_size)
    rng = np.random.RandomState(config.seed)
    idx = rng.permutation(len(dataset))[:n]
    if val_dataset is not None:
        bank_idx = idx
        q_set = val_dataset
        q_idx = rng.permutation(len(val_dataset))[: max(n // 4, 1)]
    else:
        split = int(n * 0.8)
        bank_idx, q_idx = idx[:split], idx[split:]
        q_set = dataset
    bank, bank_labels = encode_dataset(
        None, state.params_q, state.batch_stats_q, dataset, config,
        indices=bank_idx, feature_fn=feature_fn, mesh=mesh,
    )
    val, val_labels = encode_dataset(
        None, state.params_q, state.batch_stats_q, q_set, config,
        indices=q_idx, feature_fn=feature_fn, mesh=mesh,
    )
    acc = knn_accuracy(
        jnp.asarray(val), jnp.asarray(val_labels), jnp.asarray(bank),
        jnp.asarray(bank_labels), num_classes=dataset.num_classes,
        k=min(200, len(bank_idx)), temperature=0.07,
    )
    return acc, val_dataset is not None


def _monitor_val_split(config, train_dataset):
    """A real validation split for the kNN monitor, when the dataset has
    one: imagefolder `val/` dir or the CIFAR-10 test batch. None otherwise
    (synthetic / no val dir) — the monitor then holds out train data.

    The val split must share the train split's label space: ImageFolder
    derives class ids from its own directory listing, so a partial or
    differently-listed `val/` would silently shift every label. Mismatched
    class maps fall back to the train hold-out with a visible notice."""
    if config.dataset == "imagefolder":
        val_dir = os.path.join(config.data_dir, "val")
        if os.path.isdir(val_dir):
            try:
                val = build_dataset(
                    "imagefolder", val_dir, image_size=config.image_size,
                    stage_size=config.stage_size, num_workers=config.num_workers,
                )
            except FileNotFoundError:
                return None  # empty val/ placeholder: no class subdirs
            if val.class_to_idx != getattr(train_dataset, "class_to_idx", None):
                info(
                    "kNN monitor: val/ class directories differ from train/ "
                    "— labels would misalign; falling back to a train "
                    "hold-out split"
                )
                return None
            return val
    if config.dataset == "cifar10":
        try:
            return build_dataset("cifar10", config.data_dir, train=False)
        except FileNotFoundError:
            return None
    if config.dataset == "synthetic_texture":
        # a held-out draw from the same distribution (class tiles come from
        # a FIXED seed, so labels align across seeds by construction): the
        # monitor reports real generalization, not train-set recall
        from moco_tpu.data.datasets import SyntheticTextureDataset

        return SyntheticTextureDataset(
            num_samples=2048, image_size=config.image_size,
            num_classes=config.num_classes,
            seed=getattr(train_dataset, "seed", 0) + 10007
            if hasattr(train_dataset, "seed") else 10007,
            # mirror the train distribution's knobs: a val split drawn with
            # different texture_amp/cast_strength would skew the monitor
            texture_amp=getattr(train_dataset, "texture_amp", 0.4),
            cast_strength=getattr(train_dataset, "cast_strength", 0.5),
        )
    return None


def train(config: PretrainConfig, mesh=None, max_steps: int | None = None,
          dataset=None):
    """Run pretraining; returns (final_state, last_metrics_dict).

    `dataset` overrides the config-built one (callers that need a custom
    size/source, e.g. the horizon runs, without widening the flag surface).

    Fault tolerance (resilience/): SIGTERM/SIGINT finishes the in-flight
    step, writes an emergency checkpoint, and returns cleanly; a non-finite
    loss triggers a bounded rollback — restore the last good checkpoint,
    advance the data stream past the poisoned window, and retry, aborting
    with `RollbackExhaustedError` only after `config.max_rollbacks`
    consecutive rollbacks that make no net progress. Note a rollback
    intentionally alters the data stream, so the post-rollback trajectory is
    no longer bit-identical to an uninterrupted run (preemption resume IS).
    """
    if mesh is None:
        mesh = create_mesh()
    # ISSUE 15: fsdp runs need the 2-D (data, fsdp) mesh; callers (tests,
    # main) hand in the plain 1-D mesh and this folds it — same devices,
    # same order — into the layout config.sharding asks for. dp passes
    # through untouched.
    from moco_tpu.parallel.mesh import mesh_for_config

    mesh = mesh_for_config(config, mesh)
    installed_chaos = False
    if config.chaos:
        if active_chaos() is None:
            plan = parse_chaos_spec(config.chaos)
            if plan is not None:
                # same cross-restart fire-once persistence as env-installed
                # plans: a supervised drill restarts the process, and a
                # --chaos kill/freeze re-firing on every re-traversal would
                # crash-loop the drill
                plan.state_dir = os.environ.get("MOCO_TPU_CHAOS_STATE") or None
            install_chaos(plan)
            installed_chaos = True
        else:
            # an already-active plan (chaos_context in tests, or a
            # MOCO_TPU_CHAOS env plan) wins — its fire-once state must not
            # be clobbered mid-scenario — but say so LOUDLY: an operator's
            # --chaos drill silently exercising someone else's faults would
            # be vacuous
            log_event(
                "chaos",
                f"--chaos {config.chaos!r} IGNORED: a plan is already "
                f"active for this process ({active_chaos()!r}) — unset "
                "MOCO_TPU_CHAOS to use the CLI spec",
            )
    rollbacks = 0
    last_nan_step = -1
    data_advance = 0
    poison_pos = None
    run_config = config
    try:
        while True:
            try:
                return _train_once(run_config, mesh, max_steps, dataset,
                                   data_advance=data_advance,
                                   poison_pos=poison_pos)
            except NonFiniteLossError as e:
                if not config.ckpt_dir or config.max_rollbacks <= 0:
                    raise
                # "consecutive" = no net progress: a NaN at or before the
                # last poisoned step means the run never got past it
                rollbacks = rollbacks + 1 if e.step <= last_nan_step else 1
                last_nan_step = max(last_nan_step, e.step)
                if rollbacks > config.max_rollbacks:
                    raise RollbackExhaustedError(
                        f"{rollbacks} consecutive rollbacks without progress "
                        f"past step {last_nan_step} (max_rollbacks="
                        f"{config.max_rollbacks}): the divergence is "
                        "structural, not a poisoned data window — aborting "
                        "for a human"
                    ) from e
                reason = ("representation collapse"
                          if isinstance(e, CollapseError)
                          else "non-finite loss")
                log_event(
                    "rollback",
                    f"{reason} at step {e.step}: restoring the last "
                    f"good checkpoint and advancing the data stream past the "
                    f"poisoned window (rollback {rollbacks}/"
                    f"{config.max_rollbacks})",
                )
                run_config = config.replace(resume="auto")
                data_advance = e.step
                poison_pos = e.pos
    finally:
        if installed_chaos:
            # a plan left installed would hijack the NEXT train() call in
            # this process: its own --chaos spec would be silently ignored
            # (a vacuous drill), or this run's unspent faults would fire
            # into it
            clear_chaos()


def _train_once(config: PretrainConfig, mesh, max_steps: int | None = None,
                dataset=None, data_advance: int = 0,
                poison_pos: tuple[int, int] | None = None):
    """Safety shell around `_train_once_impl`: telemetry is created early in
    the pass (so rollback/resume incidents are captured) but the step loop's
    own finally is far below — an exception in between (corrupt restore,
    baseline-eval failure) must still unregister the log_event sink and
    close the events file. `close()` is idempotent, so the impl's rich
    summary close wins when both run."""
    open_telemetry: list = []
    try:
        return _train_once_impl(config, mesh, max_steps, dataset,
                                data_advance, poison_pos, open_telemetry)
    finally:
        for tel in open_telemetry:
            tel.close()


def _service_dataset_len(endpoints_spec) -> int:
    """Dataset length from the first staging server that answers a meta
    probe. Every endpoint is tried once; total unreachability is a
    configuration error (the servers are expected up before the train
    host starts — same contract as ServiceClient's handshake). A
    same-length-different-data server is still caught per-connection by
    the client's meta check."""
    from moco_tpu.data.service import protocol
    from moco_tpu.data.service.client import ServiceConfigError

    endpoints = (protocol.parse_endpoints(endpoints_spec)
                 if isinstance(endpoints_spec, str) else endpoints_spec)
    tried = []
    for host, port in endpoints:
        meta = protocol.fetch_meta(host, port)
        if meta is not None and int(meta.get("n", 0)) > 0:
            return int(meta["n"])
        tried.append(f"{host}:{port}")
    raise ServiceConfigError(
        "no staging server answered a meta probe (tried "
        + ", ".join(tried)
        + ") — start the servers first, or unset input_service"
    )


def _train_once_impl(config: PretrainConfig, mesh, max_steps: int | None = None,
                     dataset=None, data_advance: int = 0,
                     poison_pos: tuple[int, int] | None = None,
                     _telemetry_out: list | None = None):
    """One driver pass (the body `train` retries around on rollback).
    `data_advance`: skip the data stream forward past the poisoned window —
    weights restart from the restored checkpoint but the window is never
    re-consumed. `poison_pos` is the `(epoch, batch_index)` the poisoned
    batch was consumed at; when absent it is derived from `data_advance`
    (only correct while steps and batches are still aligned)."""
    if config.knn_monitor and config.knn_every_epochs < 1:
        raise ValueError(
            f"knn_every_epochs must be >= 1 (got {config.knn_every_epochs}); "
            "disable the monitor with knn_monitor=False instead")
    if config.debug_nans:
        # numeric sanitizer (SURVEY §5.2): raise at the op that produced the
        # first NaN instead of training through garbage
        jax.config.update("jax_debug_nans", True)
    n_chips = mesh.size
    local_b = local_batch_size(config.batch_size, mesh)  # validates divisibility
    # a token encoder (models/sdar.py, models/ouro.py) is fed int32 rows and
    # lengths where an image encoder is fed uint8 canvases and extents: same
    # feed, same step
    tokens = is_token_encoder(config.arch)
    if tokens and config.knn_monitor:
        raise ValueError("knn_monitor reads labelled images; a token encoder has none")

    dataset_len = None
    if dataset is None:
        if config.input_prestage:
            # pre-staged epoch cache (ISSUE 14): the dataset IS the mmap —
            # epochs are row gathers, decode happened once offline
            from moco_tpu.data.service.prestage import PrestagedDataset

            dataset = PrestagedDataset(config.input_prestage)
        elif config.input_service and not config.knn_monitor:
            # input_service is the remote-decode topology: the train host
            # may not even mount the data tree, and the only local use of
            # the dataset would be len(). The handshake meta already
            # carries the length every ServiceClient connection validates
            # against — probe it instead of paying an ImageFolder scan.
            # (The kNN monitor genuinely decodes locally, so it keeps the
            # local build.)
            dataset_len = _service_dataset_len(config.input_service)
        else:
            dataset = build_dataset(
                config.dataset, config.data_dir, image_size=config.image_size,
                stage_size=config.stage_size, num_workers=config.num_workers,
                **(dict(vocab=held_vocab(config.arch, config.vocab_size),
                        length=2 * config.seq_len) if tokens else {}),
            )
    if dataset_len is None:
        dataset_len = len(dataset)
    # clamp to the batches the loader can actually yield: a steps_per_epoch
    # above that silently truncated epochs (and stretched the lr schedule) —
    # the r2 "3200-step" horizon run actually ran 768 steps this way
    available = max(dataset_len // config.batch_size, 1)
    steps_per_epoch = min(config.steps_per_epoch or available, available)
    if config.steps_per_epoch and steps_per_epoch < config.steps_per_epoch:
        info(
            f"steps_per_epoch clamped {config.steps_per_epoch} -> "
            f"{steps_per_epoch}: the {dataset_len}-sample dataset yields only "
            f"{available} batches of {config.batch_size}"
        )

    # observability on process 0 only: every host writing the same tags into
    # one tb_dir duplicates curves, and concurrent profiler traces race
    is_main = jax.process_index() == 0
    n_procs = jax.process_count()
    # structured telemetry (ISSUE 2): EVERY process builds one (the pod
    # allgather needs all hosts' vectors) but only process 0 writes
    # events.jsonl + heartbeat. None when off — the step loop then runs
    # zero telemetry code (no fences, no sampling: the overhead contract).
    # Created BEFORE the rollback/data-advance events below so every
    # incident of this driver pass lands in the stream.
    telemetry = None
    if config.telemetry_dir:
        from moco_tpu.telemetry import RunTelemetry

        telemetry = RunTelemetry(
            config, n_chips=n_chips, n_procs=n_procs,
            process_index=jax.process_index(), steps_per_epoch=steps_per_epoch,
        )
        if _telemetry_out is not None:
            _telemetry_out.append(telemetry)
        if dataset is not None:
            # which decode path feeds the run (ImageFolder: native | pil)
            telemetry.event("dataset", source=type(dataset).__name__,
                            n=dataset_len,
                            backend=getattr(dataset, "backend", ""))
    input_stats = telemetry.input_stats if telemetry is not None else None
    # the program's own spans (ISSUE 25; names in telemetry/scopes.py): with
    # telemetry on each also enters the profiler's trace, at every trace_mode
    tracer = telemetry.tracer if telemetry is not None else null_tracer()
    setup_span = telemetry.setup_span if telemetry is not None else no_span
    # a phase of the main thread's step: entered next to the tracer's span of
    # that phase (`scopes.STEP_PHASES`), it books the same interval into the
    # step record's field
    phase = telemetry.timer.phase if telemetry is not None else no_span

    if (config.input_cache_mb and not config.input_prestage
            and dataset is not None):
        # decode-once canvas cache (ISSUE 3): wrapped per driver pass, so a
        # NaN rollback restarts it cold (safe — it is index-keyed, carries
        # no positional state, and the skipped window is simply never asked
        # for). Lives OUTSIDE the epoch loop: epochs >= 2 are the payoff.
        # (A prestage is already the cache-everything case — wrapping it
        # would spend RAM duplicating an mmap the page cache shares. The
        # guard is "a local decoding dataset exists": a service-fed run
        # without the kNN monitor built none, while service + kNN keeps
        # one whose repeated bank encodes are exactly this cache's
        # workload.)
        from moco_tpu.data.canvas_cache import CachedDataset

        dataset = CachedDataset(dataset, config.input_cache_mb,
                                stats=input_stats)

    model = build_encoder(config)
    tx, sched = build_optimizer(config, steps_per_epoch)
    init_key = jax.random.key(config.seed)
    from moco_tpu.parallel.gradsync import GradSync

    with setup_span("create_train_state"):
        if config.variant == "v3":
            from moco_tpu.v3_step import create_v3_train_state

            state = create_v3_train_state(
                init_key, model, tx,
                (local_b, config.image_size, config.image_size, 3),
                span=setup_span,
            )
        else:
            state = create_train_state(
                init_key,
                model,
                tx,
                (local_b, config.seq_len) if tokens
                else (local_b, config.image_size, config.image_size, 3),
                config.num_negatives,
                config.embed_dim,
                span=setup_span,
                input_dtype=jnp.int32 if tokens else jnp.float32,
            )
        with setup_span("place_state"):
            # gradient-sync accumulators (ISSUE 6): attached BEFORE any
            # resume so the restore target carries the dialect-2 leaves
            # (quantized/demo); fused/bucketed attach an empty tree.
            # Bound to the mesh's own axes (for_mesh): on the 2-D fsdp_tp
            # mesh the quantized reduce is the multihop one, and the
            # telemetry describe() below must account the same per-hop
            # bytes the program moves
            gradsync = GradSync.for_mesh(config, mesh)
            state = gradsync.attach(state, mesh)
            if config.sharding != "dp":
                # FSDP placement (ISSUE 15): params/opt leaves land sharded
                # over the fsdp axis BEFORE the step builds, so jit
                # compiles against the committed input shardings (the
                # zero_sharding pattern)
                from moco_tpu.parallel import fsdp

                state = fsdp.place_state(state, mesh, config)
    with setup_span("build_step"):
        step_fn = build_train_step(config, model, tx, mesh, steps_per_epoch,
                                   sched, state=state)
    if telemetry is not None and tokens:
        telemetry.set_attn(attention_path(config.arch, config.seq_len, local_b, config.remat,
                                          config.compute_dtype))
        moe = dispatch_path(config.arch, local_b, config.seq_len, config.num_experts)
        if moe is not None:
            telemetry.set_moe(moe)
    if telemetry is not None:
        # static comm facts for the record stream: mode, knobs, analytic
        # per-device sync payload (bytes/step) — rendered by
        # telemetry_report. `sharding` stamps the mode the numbers were
        # measured under (ISSUE 15 satellite).
        telemetry.set_grad_sync(
            dict(gradsync.describe(state.params_q),
                 sharding=config.sharding))
        # per-device state inventory: under fsdp the params/opt bytes
        # measure ~1/N of dp — the acceptance gate and bench read this
        from moco_tpu.parallel.fsdp import state_bytes_per_device

        telemetry.set_sharding(dict(
            mode=config.sharding,
            mesh_shape={str(a): int(s) for a, s in mesh.shape.items()},
            **state_bytes_per_device(state),
        ))

    mgr = checkpoint_manager(config.ckpt_dir) if config.ckpt_dir else None
    if mgr is not None and config.resume:
        # restore straight into the run's own placement: Orbax places
        # every host's shards locally (a restore-then-`device_put` would
        # need cross-host transfers, unsupported on multi-process CPU and a
        # DCN round-trip on real pods). dp restores replicated; fsdp passes
        # the per-leaf NamedSharding TREE (dialect 3) so dp→fsdp and N→M
        # checkpoints land sharded without a resharding pass.
        from moco_tpu.parallel.mesh import replicated

        if config.sharding != "dp":
            from moco_tpu.parallel.fsdp import state_shardings

            restore_sharding = state_shardings(state, mesh, config)
        else:
            restore_sharding = replicated(mesh)
        with setup_span("restore"):
            state = maybe_resume(mgr, state, config.resume,
                                 sharding=restore_sharding)
        if gradsync.needs_state:
            # re-place the per-device accumulators (the replicated-restore
            # path lands them replicated) — mirrors the ZeRO re-shard below
            state = state.replace(
                gradsync=gradsync.place_state(state.gradsync, mesh))
            # sharding-MODE change (ISSUE 15): at equal mesh size the
            # accumulator shapes match, so the dialect shim cannot see it —
            # but the EF residuals were accumulated under a different
            # reduce topology. The sidecar stamp is the tiebreaker.
            resumed_step = int(state.step)
            if resumed_step:
                from moco_tpu.checkpoint import read_recorded_sharding

                recorded = read_recorded_sharding(
                    config.ckpt_dir, resumed_step) or "dp"
                if recorded != config.sharding:
                    log_event(
                        "ckpt-dialect",
                        f"step {resumed_step} was saved under sharding="
                        f"{recorded!r}, this run uses {config.sharding!r} — "
                        "discarding its gradsync accumulators: error-"
                        "feedback/momentum state restarts from zeros",
                    )
                    state = state.replace(gradsync=jax.tree.map(
                        jnp.zeros_like, state.gradsync))
    if config.zero_sharding:
        # ZeRO-1 (after any resume, so the placement survives it): optimizer
        # state sharded over the data axis; jit propagates the committed
        # input shardings through every subsequent step
        from moco_tpu.parallel.zero import shard_opt_state

        state = state.replace(opt_state=shard_opt_state(state.opt_state, mesh))

    from moco_tpu.train_step import build_fused_step

    data_key = jax.random.key(config.seed + 1)
    with setup_span("build_step"):
        if tokens:
            two_crops_fn = build_token_views_sharded(
                token_view_config_for(config), mesh)
        else:
            # image pipeline in the model's compute dtype: bf16 halves the
            # aug's HBM traffic on TPU (the encoder casts to bf16 immediately
            # anyway)
            from moco_tpu.data.augment import with_dtype

            two_crops_fn = build_two_crops_sharded(
                with_dtype(aug_config_for(config), config.compute_dtype), mesh)
        fused_step = build_fused_step(step_fn, two_crops_fn, data_key)

    # host-side step counter mirroring state.step: int(state.step) would be a
    # device→host sync serializing every iteration
    global_step = int(state.step)
    # data-stream position: prefer the checkpoint's position sidecar — step
    # arithmetic replays consumed batches once a NaN rollback's data-window
    # skip has drifted the step↔batch mapping. Arithmetic remains the
    # fallback for sidecar-less checkpoints (pre-feature, or lost to a
    # mid-save kill): skip the resumed epoch's already-consumed batches so
    # no data is replayed (the epoch_loader permutation is deterministic per
    # epoch, so batch i here is bit-identical to batch i of the interrupted
    # run)
    pos = (read_position(config.ckpt_dir, global_step)
           if config.ckpt_dir and global_step else None)
    if pos is not None:
        start_epoch, resume_skip = pos
    else:
        start_epoch = global_step // steps_per_epoch
        resume_skip = global_step % steps_per_epoch
    poison_epoch = poison_batch = None
    if data_advance > global_step:
        # NaN rollback: weights restart from the restored step, but the data
        # stream must not replay the poisoned window — every batch from the
        # restore point THROUGH the poisoned batch is skipped, across epoch
        # boundaries when the restored checkpoint is older than the poison's
        # epoch (ckpt_every_epochs > 1, or an integrity walk-back past a
        # corrupt save). Skipped epochs yield fewer steps than
        # steps_per_epoch, so the run's step count drifts from epoch
        # alignment — accepted: the trajectory already diverged the moment
        # data was skipped.
        if poison_pos is not None:
            poison_epoch, poison_batch = poison_pos
        else:
            poison_epoch = (data_advance - 1) // steps_per_epoch
            poison_batch = (data_advance - 1) % steps_per_epoch
        log_event(
            "rollback",
            f"advancing the data stream past the poisoned window: restored "
            f"step {global_step}, skipping through batch {poison_batch} of "
            f"epoch {poison_epoch}",
        )
    total_steps = max_steps or config.epochs * steps_per_epoch
    last_metrics: dict = {}
    baseline_metrics: dict = {}
    feature_fn = make_feature_fn(model, config.variant) if config.knn_monitor else None
    monitor_val = _monitor_val_split(config, dataset) if config.knn_monitor else None
    writer = ScalarWriter(config.tb_dir if is_main else "")
    profiler = ProfilerWindow(
        config.profile_dir if is_main else "", config.profile_start, config.profile_stop
    )
    done = False

    # untrained-baseline row (VERDICT r3 weak #3): a kNN curve is only
    # evidence of learning relative to what RANDOM features score on the
    # same data — print it before any step so every horizon log carries it.
    # The monitor itself is a mesh-sharded (collective) computation, so
    # EVERY process must enter it; only the print/writer are main-gated
    baseline_sidecar = (
        os.path.join(config.ckpt_dir, "untrained_baseline.json")
        if config.ckpt_dir else None
    )
    if config.knn_monitor and start_epoch == 0 and global_step == 0:
        acc0, is_val0 = knn_monitor(
            config, feature_fn, state, dataset, mesh, val_dataset=monitor_val
        )
        tag0 = "knn_val_top1_untrained" if is_val0 else "knn_train_top1_untrained"
        # separate dict: the step loop REBINDS last_metrics each logging
        # interval, which would silently drop the baseline row
        baseline_metrics[tag0] = acc0
        if is_main:
            info(
                f"Epoch [-1] kNN({'val' if is_val0 else 'train'}) top-1 "
                f"{100 * acc0:.2f}% (UNTRAINED baseline; chance "
                f"{100.0 / dataset.num_classes:.2f}%)"
            )
            writer.write(0, {tag0: acc0})
        if telemetry is not None:
            telemetry.event("knn_eval", step=0, tag=tag0, acc=float(acc0))
        if is_main and baseline_sidecar:
            # persist next to the checkpoints: a resumed run can no
            # longer MEASURE the untrained baseline (the restored
            # encoder is trained), so it must inherit the recorded
            # one — otherwise resume silently weakens any gate that
            # compares against it
            # atomic: a preemption mid-write must not leave truncated
            # JSON that bricks every later resume (the whole point of
            # the sidecar is surviving preemption)
            tmp = baseline_sidecar + ".tmp"
            with open(tmp, "w") as f:
                json.dump({tag0: float(acc0)}, f)
            os.replace(tmp, baseline_sidecar)
    elif config.knn_monitor and global_step > 0 and baseline_sidecar and \
            os.path.exists(baseline_sidecar):
        try:
            with open(baseline_sidecar) as f:
                restored = json.load(f)
        except (json.JSONDecodeError, OSError):
            restored = {}
        if not isinstance(restored, dict):  # e.g. a file containing `null`
            restored = {}
        # empty/corrupt sidecar: leave baseline_metrics alone — the caller
        # (tools/_horizon_run.py) refuses to gate without a baseline,
        # which is the honest outcome
        baseline_metrics.update(restored)
        if is_main and restored:
            tag0, acc0 = next(iter(restored.items()))
            info(
                f"Epoch [-1] kNN top-1 {100 * acc0:.2f}% (UNTRAINED "
                f"baseline, restored from {baseline_sidecar})"
            )

    # resilience hooks (ISSUE 1): signal-flag preemption, every-step NaN
    # sentinel (one-step lag), hang watchdog, decode-failure meter, chaos
    plan = active_chaos()
    sentinel = NaNSentinel() if config.loss_sentinel else None
    # learning-health sentinel (ISSUE 13): armed when any predicate has a
    # nonzero threshold; consumes the popped health scalars below with
    # the same one-step-lag device-read discipline as the NaN sentinel
    collapse = None
    if config.collapse_acc1 or config.collapse_emb_std or config.collapse_margin:
        collapse = CollapseSentinel(
            config.collapse_window,
            acc1_floor=config.collapse_acc1,
            emb_std_eps=config.collapse_emb_std,
            margin_eps=config.collapse_margin,
            min_step=config.collapse_min_step,
            rollback=config.collapse_rollback,
        )
    preempted = False
    resized = False
    first_batch_pending = True  # the run's first loader wait is set-up's
    prev_loss = None  # the loss of the step before, for the `starved` query
    _resilience = contextlib.ExitStack()
    preempt = _resilience.enter_context(PreemptionHandler())
    # elastic resize (ISSUE 11): SIGUSR2 or a <telemetry_dir>/resize.request
    # trigger file asks for a clean checkpoint + EXIT_RESIZE so the
    # supervisor can relaunch onto a different mesh
    resize = _resilience.enter_context(ResizeListener(config.telemetry_dir))
    watchdog = _resilience.enter_context(StepWatchdog(config.watchdog_secs))
    try:
        for epoch in range(start_epoch, config.epochs):
            if done:
                break
            batch_time = AverageMeter("Time", ":6.3f")
            data_time = AverageMeter("Data", ":6.3f")
            losses = AverageMeter("Loss", ":.4e")
            top1 = AverageMeter("Acc@1", ":6.2f")
            top5 = AverageMeter("Acc@5", ":6.2f")
            decode_fail = RateMeter("DecFail")
            progress = ProgressMeter(
                steps_per_epoch,
                [batch_time, data_time, losses, top1, top5, decode_fail],
                prefix=f"Epoch: [{epoch}]",
            )
            # rolling window for the per-step line: the cumulative view is
            # polluted by the first-step compile stall for the whole epoch
            # (ISSUE 2 satellite); epoch summary still reports cumulative
            throughput = Throughput(n_chips, window=32)
            skip = resume_skip if epoch == start_epoch else 0
            if poison_epoch is not None and epoch <= poison_epoch:
                # inside the poisoned window: epochs before the poison's are
                # skipped wholesale, the poison's own epoch through the
                # poisoned batch itself
                skip = steps_per_epoch if epoch < poison_epoch else max(
                    skip, poison_batch + 1)
            epoch_start_step = global_step
            if config.input_service:
                # disaggregated input service (ISSUE 14): the SAME epoch
                # permutation/shard/fast-forward, but canvas rows stream
                # from standalone staging servers — bit-identical to the
                # in-process branch below on the same seed/epoch
                from moco_tpu.data.service.client import service_epoch_loader

                loader = service_epoch_loader(
                    config.input_service, dataset_len, epoch, config.seed,
                    config.batch_size, mesh, skip_batches=skip,
                    retries=config.loader_retries,
                    backoff_secs=config.loader_backoff_secs,
                    depth=config.prefetch_depth,
                    streams=config.staging_workers, stats=input_stats,
                    tracer=telemetry.tracer if telemetry is not None
                    else None,
                    request_timeout_s=config.input_request_timeout_s,
                )
            else:
                loader = epoch_loader(
                    dataset, epoch, config.seed, config.batch_size, mesh,
                    skip_batches=skip, retries=config.loader_retries,
                    backoff_secs=config.loader_backoff_secs,
                    depth=config.prefetch_depth,
                    workers=config.staging_workers,
                    stats=input_stats, trim_h2d=config.h2d_trim,
                    tracer=telemetry.tracer if telemetry is not None
                    else None,
                )
            end = time.perf_counter()
            if telemetry is not None:
                telemetry.timer.epoch_start()
            try:
                # steps_per_epoch may cap the epoch; the loader's length is
                # known, so the `step` span opens only around real steps
                batches = iter(loader)
                for i in range(skip, min(steps_per_epoch, skip + len(loader))):
                    # one `step` span per iteration (the profiler's
                    # StepTraceAnnotation); its children are where the main
                    # thread's time goes, what is under none of them is the
                    # loop's own (the record's `loop_s`)
                    with tracer.span(scopes.STEP_SPAN, cat="step",
                                     step=global_step + 1):
                        with tracer.span("data_wait", detail=True), \
                                phase("data_s"):
                            if first_batch_pending:
                                first_batch_pending = False
                                with setup_span("first_batch"):
                                    batch = next(batches, None)
                            else:
                                batch = next(batches, None)
                        if batch is None:  # the loader ended early
                            break
                        imgs, _labels, extents = batch
                        data_time.update(time.perf_counter() - end)
                        profiler.maybe_toggle(global_step)
                        if telemetry is not None:
                            # is the step before done already? Then nothing
                            # is queued and this one goes to an idle device
                            # (the record's `starved`; a query, no wait)
                            telemetry.timer.probe_idle(prev_loss)
                        with tracer.span("dispatch", detail=True), \
                                phase("host_s"):
                            state, metrics = fused_step(
                                state, imgs, extents, global_step)
                        prev_loss = metrics["loss"]
                        global_step += 1
                        # comm-phase probes (ISSUE 6): device scalars marking
                        # grads-ready / grads-reduced, popped so meters and the
                        # scalar writer never see them
                        gs_pre = metrics.pop("gs_comm_pre", None)
                        gs_post = metrics.pop("gs_comm_post", None)
                        # learning-health scalars (ISSUE 13): popped like the
                        # gs probes so meters/scalar-writer never see them.
                        # The h_* block carries cond-selected ZEROS on
                        # off-stride steps — only on-stride values are real.
                        neg_sim = metrics.pop("neg_sim", None)
                        logit_margin = metrics.pop("logit_margin", None)
                        health_dev = {
                            k: metrics.pop(k)
                            for k in [k for k in metrics if k.startswith("h_")]
                        }
                        on_health_stride = bool(
                            config.health_stride
                            and (global_step - 1) % config.health_stride == 0
                        )
                        if (telemetry is not None
                                and telemetry.timer.fence_due(global_step)):
                            # stride-gated device fence: off-stride steps stay
                            # fully async (the overhead contract)
                            with tracer.span("fence", detail=True), \
                                    phase("fence_s"):
                                telemetry.timer.maybe_fence(
                                    global_step, metrics["loss"],
                                    comm_pre=gs_pre, comm_post=gs_post,
                                )
                        if plan is not None and plan.maybe_nan(global_step):
                            # emulate a real divergence end-to-end: the NaN flows
                            # through the same metrics dict the sentinel/meters see
                            metrics = dict(metrics, loss=float("nan"))
                        if sentinel is not None or collapse is not None:
                            # both read a device value of the step BEFORE
                            # (one-step lag): the wait for that step is here
                            with tracer.span("sentinel", detail=True), \
                                    phase("wait_s"):
                                if sentinel is not None:
                                    sentinel.observe(global_step,
                                                     metrics["loss"],
                                                     pos=(epoch, i))
                                if collapse is not None:
                                    obs = {"logit_margin": logit_margin,
                                           "acc1": metrics.get("acc1")}
                                    if on_health_stride:
                                        # stride-gated diagnostics are real
                                        # only on stride steps; feeding the
                                        # off-stride zeros would read as
                                        # instant collapse
                                        obs.update(health_dev)
                                    collapse.observe(global_step, obs,
                                                     pos=(epoch, i))
                        if plan is not None:
                            # slow-step drill (ISSUE 8): the sleep lands inside
                            # THIS step's timer window, so the anomaly detector
                            # sees a real step_s blowout end-to-end
                            plan.maybe_slow(global_step)
                        watchdog.beat(global_step)
                        d_fail = getattr(dataset, "decode_failures", 0)
                        d_total = getattr(dataset, "decode_total", 0)
                        # per-host fault signals (SIGTERM flag, decode counters)
                        # must be ACTED on identically everywhere: one host
                        # raising or breaking alone leaves the rest hung in the
                        # next collective. Multi-host runs agree on them at a
                        # fixed step cadence; single-host acts immediately.
                        # refresh the resize flag from the trigger file (time-
                        # gated; SIGUSR2 needs no poll) before the pod sync so
                        # every host folds the same observation
                        resize.poll()
                        preempt_agreed = False
                        resize_agreed = False
                        abort_fail, abort_total = d_fail, d_total
                        if n_procs > 1:
                            abort_fail = abort_total = 0
                            if (config.resilience_sync_steps > 0 and
                                    global_step % config.resilience_sync_steps == 0):
                                from jax.experimental import multihost_utils

                                agg = multihost_utils.process_allgather(
                                    np.asarray(
                                        [int(preempt.triggered), d_fail, d_total,
                                         int(resize.triggered)],
                                        np.int64,
                                    )
                                )
                                preempt_agreed = bool(agg[:, 0].max())
                                resize_agreed = bool(agg[:, 3].max())
                                abort_fail = int(agg[:, 1].sum())
                                abort_total = int(agg[:, 2].sum())
                                if telemetry is not None:
                                    # pod telemetry piggybacks on this already-
                                    # synchronizing cadence: one extra small
                                    # allgather, no new sync points; process 0
                                    # folds the matrix into a `pod` record
                                    telemetry.pod_record(
                                        global_step,
                                        multihost_utils.process_allgather(
                                            telemetry.pod_vector()
                                        ),
                                    )
                        if (
                            config.decode_abort_rate
                            and abort_total >= config.batch_size
                            and abort_fail / abort_total > config.decode_abort_rate
                        ):
                            raise DataQualityError(
                                f"decode-failure rate {abort_fail}/{abort_total} = "
                                f"{abort_fail / abort_total:.1%} exceeds "
                                f"decode_abort_rate={config.decode_abort_rate:.1%}: "
                                "training on zero canvases would silently waste "
                                "the run"
                            )
                        step_loss = None  # host-synced loss, when printing pulls it
                        if i % config.print_freq == 0:
                            # pull metrics (host sync) only when printing
                            with tracer.span("loss_readback", detail=True), \
                                    phase("readback_s"):
                                last_metrics = {k: float(v)
                                                for k, v in metrics.items()}
                            step_loss = last_metrics["loss"]
                            if config.debug_nans and not np.isfinite(last_metrics["loss"]):
                                raise FloatingPointError(
                                    f"non-finite loss {last_metrics['loss']} at step {global_step}"
                                )
                            losses.update(last_metrics["loss"], config.batch_size)
                            top1.update(last_metrics.get("acc1", 0.0), config.batch_size)
                            top5.update(last_metrics.get("acc5", 0.0), config.batch_size)
                            decode_fail.update(d_fail, d_total)
                            progress.display(i)
                            writer.write(
                                global_step,
                                dict(
                                    last_metrics,
                                    # per-step line reports the ROLLING rate (the
                                    # cumulative one drags the compile stall
                                    # through the whole epoch); the epoch summary
                                    # below stays cumulative
                                    imgs_per_sec=throughput.rolling_imgs_per_sec,
                                    imgs_per_sec_per_chip=(
                                        throughput.rolling_imgs_per_sec
                                        / max(n_chips, 1)
                                    ),
                                    decode_failures=d_fail,
                                    decode_failure_rate=decode_fail.rate,
                                ),
                            )
                        throughput.update(config.batch_size)
                        batch_time.update(time.perf_counter() - end)
                        end = time.perf_counter()
                        health_rec = None
                        if telemetry is not None and on_health_stride:
                            # health block for the step record (ISSUE 13):
                            # pulled to host only on health-stride steps, as
                            # ONE batched transfer — per-scalar float() would
                            # pay a device→host round trip each × a dozen
                            # scalars. Keys drop the h_ prefix — obsd rules
                            # address them as health:<key>.
                            pull = dict(health_dev)
                            if logit_margin is not None:
                                pull["_logit_margin"] = logit_margin
                                pull["_neg_sim"] = neg_sim
                                pull["_pos_sim"] = metrics["pos_sim"]
                                pull["_acc1"] = metrics["acc1"]
                            with tracer.span("loss_readback", detail=True), \
                                    phase("readback_s"):
                                host = jax.device_get(pull)
                            health_rec = {
                                k[2:]: round(float(v), 6)
                                for k, v in host.items()
                                if k.startswith("h_")
                            }
                            if logit_margin is not None:
                                health_rec["logit_margin"] = round(
                                    float(host["_logit_margin"]), 6)
                                health_rec["neg_sim"] = round(
                                    float(host["_neg_sim"]), 6)
                                health_rec["pos_sim"] = round(
                                    float(host["_pos_sim"]), 6)
                                health_rec["acc1"] = round(
                                    float(host["_acc1"]), 4)
                        if telemetry is not None:
                            phases = telemetry.timer.finish_step()
                            with tracer.span("telemetry", detail=True), \
                                    phase("telemetry_s"):
                                flushed = telemetry.on_step(
                                    global_step, phases, throughput,
                                    loss=step_loss, health=health_rec)
                            if flushed:
                                # flushed: land the TensorBoard curves at the
                                # same cadence (ISSUE 2 satellite)
                                writer.flush()
                        if plan is not None:
                            plan.maybe_sigterm(global_step)
                            # elastic-resize drill (ISSUE 11): record the target
                            # device count where the supervisor will look for
                            # it, then exit through the same path an operator
                            # request takes
                            chaos_devices = plan.maybe_resize(global_step)
                            if chaos_devices is not None:
                                if config.telemetry_dir:
                                    write_resize_request(
                                        config.telemetry_dir,
                                        devices=chaos_devices or None,
                                    )
                                resize.trigger()
                            if plan.maybe_collapse(global_step):
                                # collapse drill (ISSUE 13): crush the key
                                # encoder to a constant-feature tree, EVERY
                                # step from here on — the in-step EMA would
                                # heal a one-shot crush within one step
                                from moco_tpu.telemetry.health import (
                                    crush_key_params,
                                )

                                state = state.replace(
                                    params_k=crush_key_params(state.params_k))
                            # process-level faults (ISSUE 4): SIGKILL-grade death
                            # and wedged-collective freeze — both invisible to
                            # the in-process handlers, recoverable only by the
                            # out-of-process supervisor. After on_step, so the
                            # heartbeat's last beat records this step.
                            plan.maybe_kill(global_step)
                            plan.maybe_freeze(global_step)
                        if preempt_agreed or (n_procs == 1 and preempt.triggered):
                            # finish-the-step-then-exit: the emergency checkpoint
                            # (a COLLECTIVE save) lands after the loop, at a step
                            # every host agrees on — a signaled host breaking by
                            # itself would leave the others in a hung collective
                            preempted = True
                            done = True
                            break
                        if resize_agreed or (n_procs == 1 and resize.triggered):
                            # same finish-the-step-then-exit shape as preemption,
                            # but the exit code says "relaunch me onto a NEW
                            # mesh" (EXIT_RESIZE) instead of "same argv"
                            resized = True
                            done = True
                            break
                        if global_step >= total_steps:
                            done = True
                            break
            finally:
                # unblock the prefetch thread on early break; quietly — a
                # pending staged-read error raised here would replace an
                # in-flight exception (disarming the NaN rollback) or void a
                # completed/preempted run whose every consumed step succeeded
                loader.close_quietly()
            if sentinel is not None:
                # check the epoch's LAST loss now (its one-step-lag check
                # would otherwise land after the epoch-end save below, and a
                # NaN state would be checkpointed — then restored by the very
                # rollback trying to escape it)
                sentinel.flush()
            if collapse is not None:
                # same reasoning for the collapse predicates: a collapsed
                # state must not be checkpointed past its own detection
                collapse.flush()
            if preempted or resized:
                break  # no epoch eval/save: the emergency checkpoint follows
            # epoch summary stays CUMULATIVE (honest average incl. the
            # compile stall); the per-step line above reports rolling
            info(
                f"Epoch [{epoch}] imgs/sec {throughput.imgs_per_sec:.1f} "
                f"({throughput.imgs_per_sec_per_chip:.1f}/chip)"
            )
            if telemetry is not None:
                telemetry.event(
                    "epoch_summary", epoch=epoch, step=global_step,
                    imgs_per_sec=round(throughput.imgs_per_sec, 2),
                    imgs_per_sec_rolling=round(
                        throughput.rolling_imgs_per_sec, 2),
                )
            # cadence: every knn_every_epochs, plus the run's final epoch
            # (early `done` break included) so end-of-run gates always see a
            # current number. Zero-step epochs (a rollback skipped them
            # wholesale) have nothing new to report: the weights are
            # unchanged, so the eval would burn minutes re-measuring the
            # previous point and write a duplicate at the same global_step
            if config.knn_monitor and global_step > epoch_start_step and (
                (epoch + 1) % config.knn_every_epochs == 0
                or epoch == config.epochs - 1
                or done
            ):
                if telemetry is not None:
                    # the supervisor's analogue of watchdog.suspended():
                    # an "eval" beat widens its staleness window so the
                    # beat-less minutes below aren't killed as a hang
                    telemetry.phase_beat("eval", global_step)
                with watchdog.suspended():
                    # a multi-minute eval with no step beats is a guaranteed
                    # false 'possible hang' flag otherwise
                    acc, is_val = knn_monitor(
                        config, feature_fn, state, dataset, mesh,
                        val_dataset=monitor_val,
                    )
                # with a real val split the tag is a true val metric;
                # otherwise the held-out slice comes from the TRAIN set and
                # the tag says so, to avoid misreading it
                tag = "knn_val_top1" if is_val else "knn_train_top1"
                label = "val" if is_val else "train"
                last_metrics[tag] = acc
                info(f"Epoch [{epoch}] kNN({label}) top-1 {100 * acc:.2f}%")
                writer.write(global_step, {tag: acc})
                if telemetry is not None:
                    telemetry.event("knn_eval", step=global_step, epoch=epoch,
                                    tag=tag, acc=float(acc))
            if (
                mgr is not None
                and global_step > epoch_start_step  # an epoch the rollback
                # skipped wholesale made no progress — re-saving the restored
                # step would collide with the existing checkpoint
                and (epoch + 1) % config.ckpt_every_epochs == 0
            ):
                # unlike the reference's rank-0-only torch.save, Orbax saving
                # of multi-process arrays is COLLECTIVE — every process must
                # call it. Async (wait=False): serialization overlaps the
                # next epoch's compute; the integrity manifest is deferred to
                # the next save / finalize_checkpoints
                with tracer.span("checkpoint", cat="checkpoint",
                                 step=global_step):
                    save_checkpoint(mgr, state, global_step, wait=False,
                                    position=(epoch + 1, 0), devices=n_chips,
                                    sharding=config.sharding)
        if sentinel is not None:
            # the final step's loss is still pending (one-step lag)
            sentinel.flush()
        if collapse is not None:
            collapse.flush()
    finally:
        # always land the profiler trace and flush buffered scalars,
        # even when the loop raises (debug_nans, data errors, ^C);
        # restore signal dispositions and stop the watchdog thread
        _resilience.close()
        profiler.close()
        if telemetry is not None:
            # run_end summary + final flush; also surfaces the writer's
            # dropped-scalar count (ISSUE 2 satellite) so silent drops are
            # visible in the machine record. `preempted` routes the final
            # heartbeat's phase (preempt_exit vs run_end) so the supervisor
            # knows a relaunch is expected without scraping logs.
            telemetry.close(scalar_drops=writer.dropped, last_step=global_step,
                            preempted=preempted, resized=resized)
        writer.close()
        if mgr is not None:
            # commit any in-flight async epoch save (and its deferred
            # manifest) BEFORE a rollback's restore walks the directory —
            # otherwise "latest" may be a step Orbax is still writing
            finalize_checkpoints(mgr)
    if (preempted or resized) and mgr is not None:
        # step-tagged emergency checkpoint: the position sidecar (plus the
        # mid-epoch `resume_skip` path) makes the resumed run bit-identical
        # to the uninterrupted one. `epoch`/`i` survive the loop: the
        # preempted/resized break only fires inside an iteration
        emergency_pos = ((epoch + 1, 0) if i + 1 >= steps_per_epoch
                         else (epoch, i + 1))
        log_event(
            "resize" if resized else "preempt",
            f"writing {'elastic' if resized else 'emergency'} checkpoint at "
            f"step {global_step}, then exiting cleanly",
            step=global_step, pid=os.getpid(),
        )
        save_checkpoint(mgr, state, global_step, position=emergency_pos,
                        devices=n_chips, sharding=config.sharding)
    if preempted:
        # surfaced to callers (absent otherwise): main() turns it into
        # EXIT_PREEMPTED so the supervisor can tell a preemption's clean
        # exit (“relaunch me”) from a natural end without log forensics
        last_metrics = dict(last_metrics, preempted=True)
    if resized:
        # main() turns it into EXIT_RESIZE: "relaunch me onto the new mesh"
        last_metrics = dict(last_metrics, resized=True)
    if mgr is not None:
        finalize_checkpoints(mgr)
    if config.export_path and is_main and not preempted and not resized:
        # close the pretrain→probe loop: v1/v2 write the query encoder in the
        # reference checkpoint dialect (torchvision names) for evals.lincls /
        # evals.knn / export_detectron2; v3 writes its backbone tree dialect
        if config.variant == "v3":
            from moco_tpu.checkpoint import export_v3_backbone

            export_v3_backbone(state, config.export_path, config.image_size)
        elif config.arch.startswith("vit"):
            from moco_tpu.checkpoint import export_vit_encoder

            export_vit_encoder(state, config.export_path, config.image_size)
        else:
            from moco_tpu.checkpoint import export_encoder_q

            export_encoder_q(state, config.export_path)
        info(f"exported encoder -> {config.export_path}")
    return state, {**baseline_metrics, **last_metrics}


def main(argv=None):
    """CLI entry. Exits through the named codes in resilience/exitcodes.py
    (the supervisor's classification protocol — lint rule R5 forbids bare
    `sys.exit(<int>)` here): 0 clean, EXIT_PREEMPTED after an honored
    SIGTERM + emergency checkpoint, EXIT_RESIZE after an honored elastic
    resize (clean checkpoint, relaunch onto a new mesh expected),
    EXIT_ROLLBACK_EXHAUSTED / EXIT_DATA_QUALITY for the deliberate
    run-enders a restart cannot fix, EXIT_CONFIG_ERROR for a bad
    preset/flag. Anything else propagates as a traceback (python's exit 1
    → classified as a generic crash)."""
    from moco_tpu.config import add_config_flags, collect_overrides
    from moco_tpu.resilience.exitcodes import (
        EXIT_CONFIG_ERROR,
        EXIT_DATA_QUALITY,
        EXIT_PREEMPTED,
        EXIT_RESIZE,
        EXIT_ROLLBACK_EXHAUSTED,
    )

    parser = argparse.ArgumentParser(description="moco_tpu pretraining")
    pretrain_presets = sorted(
        name for name, cfg in PRESETS.items() if isinstance(cfg, PretrainConfig)
    )
    parser.add_argument("--preset", default="cifar10-moco-v1", choices=pretrain_presets)
    parser.add_argument("--max-steps", type=int, default=None)
    parser.add_argument("--num-devices", type=int, default=None)
    parser.add_argument("--fake-devices", type=int, default=0,
                        help="force N fake CPU devices (testing)")
    parser.add_argument("--multihost", action="store_true",
                        help="call jax.distributed.initialize() (multi-host pods; "
                             "args auto-detected on Cloud TPU)")
    parser.add_argument("--coordinator-address", default=None)
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    add_config_flags(parser, PretrainConfig)
    args = parser.parse_args(argv)
    if args.fake_devices:
        from moco_tpu.parallel.mesh import force_cpu_devices

        force_cpu_devices(args.fake_devices)
    if args.multihost:
        from moco_tpu.parallel.mesh import distributed_init

        distributed_init(args.coordinator_address, args.num_processes, args.process_id)
    try:
        config = get_preset(args.preset).replace(
            **collect_overrides(args, PretrainConfig)
        )
    except (TypeError, ValueError) as e:
        # bad flag value / preset / __post_init__ validation: the same argv
        # can never succeed, so the exit code must say "don't restart me"
        log_event("exit", f"config error: {e}", code=EXIT_CONFIG_ERROR)
        sys.exit(EXIT_CONFIG_ERROR)
    # persistent XLA compile cache: a restarted/resumed run skips the
    # multi-minute cold compile
    from moco_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    try:
        # fold in the config's sharding layout HERE so an unsatisfiable
        # combination (--sharding-axis-size not dividing the device count,
        # a resize-appended --sharding onto the wrong mesh) exits
        # config_error like any other bad argv — train()'s own re-fold is
        # then a no-op for the CLI path
        from moco_tpu.parallel.mesh import mesh_for_config

        mesh = mesh_for_config(config, create_mesh(args.num_devices))
    except ValueError as e:
        # more devices requested than exist (e.g. a typo'd resize request's
        # --num-devices append), or a sharding layout the device count
        # cannot satisfy: the same argv can never succeed — the supervisor
        # must classify this config_error and revert/stop, not relaunch a
        # generic "crash" into a loop
        log_event("exit", f"mesh config error: {e}", code=EXIT_CONFIG_ERROR)
        sys.exit(EXIT_CONFIG_ERROR)
    info(f"config: {config}")
    dev = mesh.devices.flat[0]
    info(f"mesh: {mesh} on {mesh.size} x {dev.platform} ({dev.device_kind})")
    try:
        _state, metrics = train(config, mesh, max_steps=args.max_steps)
    except RollbackExhaustedError as e:
        log_event("exit", f"rollback budget exhausted: {e}",
                  code=EXIT_ROLLBACK_EXHAUSTED)
        sys.exit(EXIT_ROLLBACK_EXHAUSTED)
    except DataQualityError as e:
        log_event("exit", f"data quality abort: {e}", code=EXIT_DATA_QUALITY)
        sys.exit(EXIT_DATA_QUALITY)
    if metrics.get("preempted"):
        log_event("exit", "preemption honored: emergency checkpoint written, "
                          "exiting for relaunch", code=EXIT_PREEMPTED)
        sys.exit(EXIT_PREEMPTED)
    if metrics.get("resized"):
        log_event("exit", "resize honored: elastic checkpoint written, "
                          "exiting for relaunch onto the new mesh",
                  code=EXIT_RESIZE)
        sys.exit(EXIT_RESIZE)


if __name__ == "__main__":
    main()
