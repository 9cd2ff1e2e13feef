"""Shared assembly + timing for the step-mode benchmark program.

One definition of "the benchmark" — the fused aug+train-step program built
the way the train driver builds it — used by `bench.py`'s step mode and
`chip_smoke.py`. Every hyperparameter comes from the config; the callers
only choose WHICH config.

Timing semantics:
- rounds end in `float(loss)`: a device→host read of a step output, so the
  timed region contains the work (dispatch alone returns early).
- warm up generously (compile, then the first donated-state round), then
  chain steps with one final sync.
- best-of-rounds; a non-finite loss must never publish a number (asserted
  here, both at warmup and at the end).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def build_v2_fused_step(config, mesh, *, steps_per_epoch: int = 1000,
                        state_seed: int = 0, fused_seed: int = 1):
    """Assemble the fused aug+train-step program and its initial state for
    `config`, exactly as the train driver does (`config.variant` selects
    the v1/v2 queue step or the v3 queue-free step and the matching aug
    pair). Returns `(fused, state)`; `fused(state, imgs_u8, extents,
    step)` is the one jitted program."""
    from moco_tpu.data.augment import (
        aug_config_for,
        build_two_crops_sharded,
        with_dtype,
    )
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import (
        build_encoder,
        build_fused_step,
        build_optimizer,
        build_train_step,
    )

    n_chips = mesh.devices.size
    model = build_encoder(config)
    tx, sched = build_optimizer(config, steps_per_epoch=steps_per_epoch)
    local_shape = (config.batch_size // n_chips,
                   config.image_size, config.image_size, 3)
    if config.variant == "v3":
        from moco_tpu.v3_step import create_v3_train_state

        state = create_v3_train_state(
            jax.random.key(state_seed), model, tx, local_shape)
    else:
        state = create_train_state(
            jax.random.key(state_seed),
            model,
            tx,
            local_shape,
            config.num_negatives,
            config.embed_dim,
        )
    # gradient-sync accumulators (ISSUE 6), exactly as the driver attaches
    # them — a quantized/demo bench without the state would crash at trace
    from moco_tpu.parallel.gradsync import GradSync

    state = GradSync.for_mesh(config, mesh).attach(state, mesh)
    if getattr(config, "sharding", "dp") != "dp":
        # FSDP placement (ISSUE 15), exactly as the driver applies it —
        # the sharded bench must time the sharded program
        from moco_tpu.parallel import fsdp

        state = fsdp.place_state(state, mesh, config)
    step_fn = build_train_step(config, model, tx, mesh, steps_per_epoch,
                               sched, state=state)
    # the SAME variant->aug selection as the train driver (v1 presets get
    # the v1 recipe, not a silently-substituted v2 stack — review, r5)
    aug_cfg = with_dtype(aug_config_for(config), config.compute_dtype)
    two_crops = build_two_crops_sharded(aug_cfg, mesh)
    fused = build_fused_step(step_fn, two_crops, jax.random.key(fused_seed))
    return fused, state


def build_v2_fused_bench(config, mesh, *, steps_per_epoch: int = 1000,
                         state_seed: int = 0, fused_seed: int = 1,
                         data_seed: int = 0):
    """`build_v2_fused_step` plus one staged uint8 batch at the native
    staging shape (`image_size + image_size // 8`) — re-augmented on
    device every step, representing the steady-state input path with host
    decode amortized. Returns `(fused, state, imgs_u8, extents)`."""
    from moco_tpu.data.datasets import full_extents

    fused, state = build_v2_fused_step(
        config, mesh, steps_per_epoch=steps_per_epoch,
        state_seed=state_seed, fused_seed=fused_seed)
    stage = config.image_size + config.image_size // 8
    rng = np.random.RandomState(data_seed)
    imgs_u8 = jnp.asarray(
        rng.randint(0, 256, (config.batch_size, stage, stage, 3), dtype=np.uint8)
    )
    extents = full_extents(config.batch_size, stage, stage)
    return fused, state, imgs_u8, extents


def time_fused_step(fused, state, imgs_u8, extents, *, warmup: int,
                    steps: int, rounds: int = 2):
    """Warm up, then best-of-`rounds` timed runs of `steps` chained steps.

    Returns `(best_s_per_step, compile_warmup_s, final_loss, state)`.
    `compile_warmup_s` covers compile + the warmup loop, including its
    sync; with a warm persistent cache it collapses to the warmup.
    """
    t_c = time.perf_counter()
    metrics = None
    for i in range(warmup):
        state, metrics = fused(state, imgs_u8, extents, i)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"non-finite warmup loss {loss}"
    compile_warmup_s = time.perf_counter() - t_c

    best = float("inf")
    for r in range(rounds):
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = fused(state, imgs_u8, extents, (r + 1) * 1000 + i)
        loss = float(metrics["loss"])
        best = min(best, (time.perf_counter() - t0) / steps)
    # a fast-but-wrong kernel must not publish a number
    assert np.isfinite(loss), f"non-finite benchmark loss {loss}"
    return best, compile_warmup_s, loss, state


def time_step_percentiles(fused, state, imgs_u8, extents, *, steps: int,
                          step_base: int = 10_000):
    """Per-step wall-time distribution: `steps` steps, EACH synced to the
    host via `float(loss)` (ISSUE 2: the tail — p95/p99 — is what a perf
    PR must not regress, and chained timing can only see the mean).

    The per-step sync adds one device→host round-trip to every sample, so
    these percentiles are comparable to EACH OTHER and to other synced
    runs — not to the chained `time_fused_step` mean. Returns
    `({"p50": ms, "p95": ms, "p99": ms}, state)`.
    """
    from moco_tpu.telemetry import percentiles_ms

    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = fused(state, imgs_u8, extents, step_base + i)
        loss = float(metrics["loss"])  # d2h sync ends the sample
        times.append(time.perf_counter() - t0)
    assert np.isfinite(loss), f"non-finite percentile-pass loss {loss}"
    return percentiles_ms(times), state
