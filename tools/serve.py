#!/usr/bin/env python
"""Serve MoCo embeddings over HTTP (ISSUE 5).

    python tools/serve.py --pretrained runs/encoder.safetensors \
        --arch resnet50 --port 8080 --telemetry-dir runs/serve/telemetry

Loads the checkpoint's encoder through the shared surgery loader
(`checkpoint.load_for_inference` — both dialects), pre-compiles the
bucket ladder, and mounts the stdlib front end (moco_tpu/serve/http.py):
POST /v1/embed, POST /v1/knn (with --knn-bank), POST /admin/reload (hot
weight swap — the fleet supervisor's roll target, ISSUE 10),
GET /healthz, /stats.

SIGTERM/SIGINT drains gracefully — in-flight requests complete, new work
gets a structured 503 `draining` — via the resilience/preemption.py
handler (second signal: immediate exit, exactly like the train driver).

One process per chip: a replica claims the accelerator JAX shows it, so
on a TPU host give each replica its own device (README "Running").

Exit codes (README table): 0 clean drain · 45 bad config/checkpoint ·
47 could not bind host:port (see resilience/exitcodes.py).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moco_tpu.config import ServeConfig, add_config_flags, collect_overrides  # noqa: E402
from moco_tpu.resilience.exitcodes import (  # noqa: E402
    EXIT_CONFIG_ERROR,
    EXIT_OK,
    EXIT_SERVE_BIND,
)
from moco_tpu.utils.logging import info, log_event  # noqa: E402


def build_service(config: ServeConfig):
    """Engine + service from a ServeConfig (shared with bench/tests)."""
    from moco_tpu.serve import EmbeddingEngine, EmbedService

    def engine_factory(path: str) -> "EmbeddingEngine":
        # hot reload (ISSUE 10): POST /admin/reload builds the new engine
        # through the SAME loader + config as the boot-time one, so a
        # reloaded replica is indistinguishable from a cold start on that
        # checkpoint (bit-identity test-pinned)
        return EmbeddingEngine.from_checkpoint(
            path,
            config.arch,
            image_size=config.image_size,
            cifar_stem=config.cifar_stem,
            buckets=config.buckets,
        )

    engine = engine_factory(config.pretrained)
    registry = None
    tracer = None
    if config.telemetry_dir:
        from moco_tpu.telemetry.registry import EVENTS_FILENAME, MetricsRegistry
        from moco_tpu.telemetry.trace import Tracer

        # span layer (ISSUE 8): serve spans (request/flush/engine) +
        # SIGUSR1 / trigger-file / shed-spike capture windows land in the
        # same telemetry dir; the registry stamps the tracer's run_id so
        # serve snapshots join the merged timeline
        tracer = Tracer(
            config.telemetry_dir, config.trace_mode, proc="serve",
            capture_steps=config.trace_capture_steps,
            capture_budget=config.trace_capture_budget,
        )
        registry = MetricsRegistry(
            os.path.join(config.telemetry_dir, EVENTS_FILENAME),
            stamp={"run_id": tracer.run_id, "trace_id": tracer.trace_id},
        )
    knn_bank = knn_labels = knn_bank_meta = None
    if config.knn_bank:
        from moco_tpu.serve.bankbuild import load_bank

        # versioned banks (ISSUE 16) come back with their manifest
        # metadata (checkpoint binding + probe rows) so the service can
        # dual-swap (engine, bank) pairs; a plain npz gets meta=None and
        # behaves exactly as before
        knn_bank, knn_labels, knn_bank_meta = load_bank(config.knn_bank)
    ann_shard = None
    if config.ann_cells:
        # sharded ANN (ISSUE 20): a verified paired index must sit next
        # to the versioned bank; a missing/torn index is a config error
        # (exit 45), never a silent fall-back to exact
        from moco_tpu.serve import ann as annmod

        loaded = annmod.load_ann(config.knn_bank)  # AnnIndexError -> 45
        if loaded is None:
            raise ValueError(
                f"--ann-cells {config.ann_cells} but bank "
                f"{config.knn_bank!r} has no ANN index manifest — build "
                "it with tools/bank_build.py --ann-cells"
            )
        arrays, _manifest = loaded
        ann_shard = annmod.AnnShard(
            knn_bank, knn_labels, arrays,
            shard=config.ann_shard, shards=config.ann_shards,
            nprobe=config.ann_nprobe,
            rerank=config.ann_rerank or config.knn_k,
            temperature=config.knn_temperature,
            num_classes=config.num_classes,
        )
    service = EmbedService(
        engine,
        flush_ms=config.flush_ms,
        max_queue=config.max_queue,
        request_deadline_ms=config.request_deadline_ms,
        cache_mb=config.embed_cache_mb,
        registry=registry,
        snapshot_every=config.snapshot_every,
        tracer=tracer,
        shed_spike_min=config.trace_shed_spike,
        knn_bank=knn_bank,
        knn_labels=knn_labels,
        num_classes=config.num_classes,
        knn_k=config.knn_k,
        knn_temperature=config.knn_temperature,
        reload_probe=config.reload_probe,
        reload_min_spread=config.reload_min_spread,
        knn_bank_meta=knn_bank_meta,
        bank_agreement_min=config.bank_agreement_min,
        ann=ann_shard,
        admission_tiers=config.admission_tiers,
        batch_max_queue=config.batch_max_queue,
        batch_deadline_ms=config.batch_deadline_ms,
    )
    service.set_engine_factory(engine_factory)
    return service, registry


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[1],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    add_config_flags(parser, ServeConfig)
    args = parser.parse_args(argv)
    try:
        config = ServeConfig().replace(**collect_overrides(args, ServeConfig))
        if not config.pretrained:
            raise ValueError("--pretrained <exported encoder> is required")
    except ValueError as e:
        info(f"config error: {e}")
        return EXIT_CONFIG_ERROR

    from moco_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()

    try:
        service, registry = build_service(config)
    except (ValueError, OSError) as e:
        info(f"cannot build the service: {e}")
        return EXIT_CONFIG_ERROR

    from moco_tpu.serve import ServeFrontend

    try:
        frontend = ServeFrontend(service, config.host, config.port)
    except OSError as e:
        info(f"cannot bind {config.host}:{config.port}: {e}")
        return EXIT_SERVE_BIND

    from moco_tpu.resilience.preemption import PreemptionHandler

    if service.tracer is not None:
        service.tracer.install_signal()  # SIGUSR1 arms a capture window
    with PreemptionHandler() as pre:
        frontend.start()
        info(
            f"serving {config.arch} embeddings on {frontend.url} "
            f"(buckets {list(config.buckets)}, flush {config.flush_ms} ms, "
            f"queue {config.max_queue}, deadline "
            f"{config.request_deadline_ms:.0f} ms)"
        )
        while not pre.triggered:
            time.sleep(0.2)
    log_event(
        "serve",
        "signal received: draining — finishing in-flight batches, "
        "rejecting new work",
    )
    service.drain(config.drain_timeout_s)
    frontend.shutdown()
    if service.tracer is not None:
        service.tracer.close()
    if registry is not None:
        registry.close()
    info("drained cleanly")
    return EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
