"""moco_tpu.data — input pipelines (datasets, host staging, augmentation)
plus the disaggregated input service (ISSUE 14) under `data/service/`.

This __init__ is LAZY (PEP 562, the telemetry/serve __init__ pattern):
the input-service control plane (`data/service/server.py`,
`tools/staging_server.py`) is PURE stdlib by contract — the mocolint R11
`staging-server-stdlib-only` boundary walks ancestor __init__s, and an
eager `from moco_tpu.data.augment import ...` here would drag jax into
every staging-server supervisor process. Each public name resolves its
submodule on first attribute access, so `from moco_tpu.data import
epoch_loader` keeps working unchanged while `import
moco_tpu.data.service.protocol` touches nothing heavy."""

from __future__ import annotations

import importlib

# public name -> submodule that defines it
_EXPORTS = {
    "AugConfig": "augment",
    "augment_batch": "augment",
    "build_two_crops_sharded": "augment",
    "build_token_views_sharded": "augment",
    "token_view_config_for": "augment",
    "aug_config_for": "augment",
    "eval_aug_config": "augment",
    "two_crops": "augment",
    "v1_aug_config": "augment",
    "v2_aug_config": "augment",
    "v3_aug_configs": "augment",
    "CachedDataset": "canvas_cache",
    "CIFAR10": "datasets",
    "ImageFolder": "datasets",
    "SyntheticDataset": "datasets",
    "SyntheticTokenDataset": "datasets",
    "build_dataset": "datasets",
    "Prefetcher": "loader",
    "epoch_loader": "loader",
    "epoch_permutation": "loader",
    "host_shard": "loader",
    "InputPipelineStats": "stats",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        submodule = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    value = getattr(
        importlib.import_module(f"{__name__}.{submodule}"), name
    )
    globals()[name] = value  # cache: later accesses skip __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
