"""Chip smoke: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry point a user calls:
`moco_tpu.train.main` with `--preset imagenet-moco-v2` (ResNet-50, 224²,
K=65536, MLP head, bf16) at per-chip batch 128 over ALL local devices, fed
by a generated JPEG tree through the real feed (native decode → 512²
staging canvas → H2D → on-device two-crop augmentation → step), for 8
steps with a checkpoint save mid-run and one at the end. Then it checks
what came out (see `run_smoke`) and prints two JSON lines on stdout: the full
record (versions, compile cache, smoke readings, every check), then — LAST —
the verdict `{"ok": ..., "device": {"platform", "kind", "count"}}` with exactly
those keys, which is what a driver reads.

One process: nothing here spawns, because a chip belongs to one process.
No CPU mode: without a TPU (or outside the repo) it exits non-zero before
compiling anything and prints no result. `run_smoke` is a function of
sizes so tier-1 drives the same body tiny on fake CPU devices
(tests/test_chip_smoke.py). No `except` stands between a failing phase and
the exit code.

    python3 chip_smoke.py        # on the chip machine; exit 0 = pass
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

PRESET = "imagenet-moco-v2"
PER_CHIP_BATCH = 128
STEPS_PER_EPOCH = 4
EPOCHS = 2


def _cache_entries(cache_dir: str | None) -> set[str]:
    """File names in the compile cache (one `<program>-<key>-cache` each)."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return set()
    return set(os.listdir(cache_dir))


def _argv_for(overrides: dict) -> list[str]:
    argv = ["--preset", PRESET]
    for field, value in overrides.items():
        flag = "--" + field.replace("_", "-")
        text = str(value).lower() if isinstance(value, bool) else str(value)
        argv += [flag, text]
    return argv


def _read_events(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def run_smoke(workdir: str, *, platform: str, per_device_batch: int,
              min_mosaic_calls: int, num_devices: int | None = None,
              jpeg_size: tuple[int, int] = (500, 375),
              size_overrides: dict | None = None) -> dict:
    """The smoke body, over the first `num_devices` devices (None = all).
    `size_overrides` are PretrainConfig fields that cut the run to size (the
    script passes none: full width); everything else is the preset's.
    Returns the result record; `record["ok"]` is the verdict,
    `record["checks"]` says which check failed."""
    import jax
    import numpy as np

    devices = jax.devices()[:num_devices]
    if devices[0].platform != platform:
        raise SystemExit(
            f"chip_smoke: needs platform {platform!r}, JAX found "
            f"{devices[0].platform!r} ({devices[0].device_kind}) — no CPU mode")

    from moco_tpu import train
    from moco_tpu.checkpoint import checkpoint_manager, restore_checkpoint
    from moco_tpu.config import get_preset
    from moco_tpu.data.datasets import build_dataset, write_jpeg_tree
    from moco_tpu.data.loader import epoch_loader, epoch_permutation
    from moco_tpu.parallel.mesh import create_mesh, replicated
    from moco_tpu.utils.benchkit import build_v2_fused_step
    from moco_tpu.utils.cache import enable_persistent_cache

    n_dev = len(devices)
    batch = per_device_batch * n_dev
    total_steps = EPOCHS * STEPS_PER_EPOCH
    tree = os.path.join(workdir, "jpeg_tree")
    ckpt_dir = os.path.join(workdir, "ckpt")
    tel_dir = os.path.join(workdir, "telemetry")

    cache_dir = enable_persistent_cache()
    cache_before = _cache_entries(cache_dir)

    # -- phase 1: the trainer, through its CLI entry ------------------------
    t0 = time.time()
    write_jpeg_tree(tree, n_images=batch * STEPS_PER_EPOCH, size=jpeg_size)
    tree_s = time.time() - t0
    overrides = dict(
        data_dir=tree, batch_size=batch, epochs=EPOCHS,
        ckpt_dir=ckpt_dir, ckpt_every_epochs=1,
        telemetry_dir=tel_dir, telemetry_stride=1, telemetry_flush_steps=1,
        print_freq=1, **(size_overrides or {}),
    )
    t_train = time.time()
    train.main(_argv_for(overrides) + ["--num-devices", str(n_dev)])
    train_s = time.time() - t_train
    config = get_preset(PRESET).replace(**overrides)

    events = _read_events(os.path.join(tel_dir, "events.jsonl"))
    run_start = next(e for e in events if e["kind"] == "run_start")
    run_end = next(e for e in events if e["kind"] == "run_end")
    dataset_evt = next(e for e in events if e.get("event") == "dataset")
    steps = [e for e in events if e["kind"] == "step"]
    losses = [e.get("loss") for e in steps]
    loss_bound = math.log(config.num_negatives + 1) + 1.0
    hbm_peaks = [e["hbm_peak_bytes"] for e in steps if "hbm_peak_bytes" in e]

    checks: dict[str, bool] = {}
    checks["run_start_names_device"] = (
        run_start.get("platform") == platform
        and run_start.get("device_kind") == devices[0].device_kind
        and run_start.get("n_chips") == n_dev)
    checks["step_count"] = (
        [e["step"] for e in steps] == list(range(1, total_steps + 1))
        and run_end.get("last_step") == total_steps)
    checks["losses_finite_and_bounded"] = (
        len(losses) == total_steps
        and all(v is not None and math.isfinite(v) and 0.0 < v <= loss_bound
                for v in losses))
    checks["staging_backend_native"] = dataset_evt.get("backend") == "native"

    # -- phase 2: the step's program, as the trainer assembles it -----------
    mesh = create_mesh(n_dev)
    fused, state = build_v2_fused_step(config, mesh)
    stage_h, stage_w = config.stage_size or 512, 2 * (config.stage_size or 512)
    lowered = fused.lower(
        state,
        jax.ShapeDtypeStruct((batch, stage_h, stage_w, 3), np.uint8),
        jax.ShapeDtypeStruct((batch, 3), np.int32),
        0,
    ).as_text()
    mosaic_calls = lowered.count("tpu_custom_call")
    checks["mosaic_calls_in_step"] = mosaic_calls >= min_mosaic_calls and (
        min_mosaic_calls == 0 or "_blur_kernel" in lowered)

    # -- phase 3: the checkpoint restores, and the queue advanced -----------
    mgr = checkpoint_manager(ckpt_dir)
    saved_steps = sorted(mgr.all_steps())
    restored = restore_checkpoint(mgr, state, sharding=replicated(mesh))
    mgr.close()
    queue_ptr = int(restored.queue_ptr)
    checks["checkpoints_saved"] = saved_steps == [
        STEPS_PER_EPOCH * (e + 1) for e in range(EPOCHS)]
    checks["restored_step"] = int(restored.step) == total_steps
    checks["queue_ptr_advanced"] = (
        queue_ptr == (total_steps * batch) % config.num_negatives)
    del state, restored, fused

    # -- phase 4: staged batches on the device == their host source ---------
    # every batch of one epoch is HELD on the device before any is read
    # back, so a staging canvas recycled under a live transfer would show
    dataset = build_dataset("imagefolder", tree, stage_size=config.stage_size,
                            num_workers=config.num_workers)
    loader = epoch_loader(dataset, 0, config.seed, batch, mesh,
                          depth=config.prefetch_depth,
                          workers=config.staging_workers)
    try:
        staged = list(loader)
    finally:
        loader.close()
    perm = epoch_permutation(len(dataset), 0, config.seed, batch)
    staged_equal = len(staged) == STEPS_PER_EPOCH
    for b, (imgs, labels, extents) in enumerate(staged):
        h_imgs, h_labels, h_extents = dataset.get_batch(
            perm[b * batch:(b + 1) * batch])
        staged_equal = (staged_equal
                        and np.array_equal(np.asarray(imgs), h_imgs)
                        and np.array_equal(np.asarray(labels), h_labels)
                        and np.array_equal(np.asarray(extents), h_extents))
    checks["staged_batch_equals_host_source"] = staged_equal
    shard_rows = {s.device.id: int(s.data.shape[0])
                  for s in staged[-1][0].addressable_shards}
    checks["batch_split_evenly_over_devices"] = (
        len(shard_rows) == n_dev
        and set(shard_rows.values()) == {per_device_batch})
    del staged

    # -- phase 5: memory, per device ----------------------------------------
    per_device_peak = None
    if platform != "cpu":  # the CPU backend has no allocator statistics
        per_device_peak = {d.id: int(d.memory_stats()["peak_bytes_in_use"])
                           for d in devices}
        checks["peak_bytes_in_use_reported"] = (
            len(hbm_peaks) == total_steps and min(hbm_peaks) > 0)
        checks["every_device_held_memory"] = (
            len(per_device_peak) == n_dev and min(per_device_peak.values()) > 0)

    step_s = [e["step_s"] for e in steps]
    cache_after = _cache_entries(cache_dir)
    return {
        "ok": all(checks.values()),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n_dev},
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "preset": PRESET, "global_batch": batch, "steps": total_steps,
        # `compiled_here`: programs this run compiled and cached — on a warm
        # cache only what straddles JAX's 1 s caching threshold, never the
        # step
        "compile_cache": {
            "dir": cache_dir, "entries_before": len(cache_before),
            "entries_after": len(cache_after),
            "compiled_here": sorted(
                name.rsplit("-", 2)[0] for name in cache_after - cache_before),
        },
        # a smoke reading, NOT a benchmark: 8 synced steps, telemetry fencing
        # every step, one async checkpoint save inside the window
        "smoke_reading": {
            "note": "smoke reading, not a benchmark",
            "setup_s": round(steps[0]["t"] - t_train, 1),
            "steady_step_s_median": round(statistics.median(step_s[2:]), 4),
            "step_s": [round(v, 4) for v in step_s],
            "train_main_s": round(train_s, 1),
            "jpeg_tree_s": round(tree_s, 1),
        },
        "losses": [round(v, 4) for v in losses if v is not None],
        "mosaic_calls_in_step": mosaic_calls,
        "queue_ptr": queue_ptr,
        "hbm_peak_bytes": max(hbm_peaks) if hbm_peaks else None,
        "per_device_peak_bytes": per_device_peak,
        "per_device_batch_rows": shard_rows,
        "checks": checks,
    }


def verdict_line(record: dict) -> dict:
    """The last stdout line: exactly `ok` and `device` (platform, kind,
    count), the device as JAX reports it. Everything else is in the record
    line before it."""
    device = record["device"]
    return {"ok": bool(record["ok"]),
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    # checkpoints, telemetry and the JPEG tree stay out of the checkout (and
    # so out of whatever a chip tool copies back)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        record = run_smoke(workdir, platform="tpu",
                           per_device_batch=PER_CHIP_BATCH, min_mosaic_calls=1)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(record))
    print(json.dumps(verdict_line(record)), flush=True)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
