"""Bucketed-compile embedding engine (ISSUE 5 tentpole).

XLA compiles one program per input SHAPE: a naive server jitting whatever
batch size the batcher produced would recompile on nearly every distinct
coalesce (1, 3, 7, 12, ...) — each a multi-second stall under load. The
engine instead pads every batch to a small fixed ladder of bucket shapes
(default 1/8/32/128), pre-compiles ALL of them at `warmup()`, and then
never compiles again: steady-state load sees only warm program launches.

Soundness of padding (test-pinned): with `train=False` the encoder runs
BN on running stats, so every per-row computation is independent of batch
composition — the same image embeds BIT-IDENTICALLY whether it rides
solo in the 1-bucket or padded among strangers in the 128-bucket, and
identically to a direct `model.apply` on the same normalized input.

Preprocessing matches the eval path (data/augment.py): uint8 canvases at
the model resolution are scaled to [0,1] and normalized with the
ImageNet mean/std — the transform every frozen-feature consumer
(lincls, kNN) applies after its deterministic center crop. Cropping and
resizing stay client-side: the service's contract is "model-resolution
RGB in, feature vector out".
"""

from __future__ import annotations

import numpy as np

from moco_tpu.serve.batcher import bucket_for, validate_buckets

DEFAULT_BUCKETS = (1, 8, 32, 128)


class EmbeddingEngine:
    """Jitted feature extraction over a fixed bucket ladder.

    `embed(images_u8)` accepts `[n, S, S, 3]` uint8 with any
    `1 <= n <= buckets[-1]`, pads to the smallest fitting bucket, and
    returns the first `n` feature rows as float32 numpy. Call `warmup()`
    (the service does) before taking traffic so every bucket's program is
    already compiled."""

    def __init__(self, model, params, batch_stats, *, image_size: int,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS):
        import jax
        import jax.numpy as jnp

        from moco_tpu.data.augment import IMAGENET_INV_STD, IMAGENET_MEAN

        self.model = model
        self.image_size = int(image_size)
        self.buckets = validate_buckets(buckets)
        # pin the frozen weights to a device ONCE — uncommitted host
        # arrays would be re-placed on every call (the lincls lesson)
        self.params = jax.device_put(params)
        self.batch_stats = jax.device_put(batch_stats or {})
        self.feat_dim: int | None = None
        mean = jnp.asarray(IMAGENET_MEAN)
        inv_std = jnp.asarray(IMAGENET_INV_STD)

        def _apply(p, stats, images_u8):
            x = images_u8.astype(jnp.float32) / 255.0
            x = (x - mean) * inv_std
            return model.apply({"params": p, "batch_stats": stats}, x,
                               train=False)

        self._jitted = jax.jit(_apply)

    @classmethod
    def from_checkpoint(cls, path: str, arch: str, *, image_size: int = 224,
                        cifar_stem: bool = False,
                        buckets: tuple[int, ...] = DEFAULT_BUCKETS
                        ) -> "EmbeddingEngine":
        """Load a pretraining export through the shared checkpoint-surgery
        loader (`checkpoint.load_for_inference` — the same dialect table
        lincls and the Detectron2 converter consume). Imported lazily:
        the serve package stays import-light for callers that bring their
        own params (bench, tests)."""
        from moco_tpu.checkpoint import load_for_inference

        model, params, stats = load_for_inference(
            path, arch, image_size=image_size, cifar_stem=cifar_stem
        )
        return cls(model, params, stats, image_size=image_size,
                   buckets=buckets)

    # -- lifecycle -----------------------------------------------------------
    def warmup(self) -> int:
        """Compile every bucket's program up front (zeros batches) so no
        live request ever pays a compile. Returns the feature dim."""
        s = self.image_size
        for b in self.buckets:
            out = self._jitted(
                self.params, self.batch_stats,
                np.zeros((b, s, s, 3), np.uint8),
            )
        self.feat_dim = int(out.shape[-1])
        return self.feat_dim

    def compiled_programs(self) -> int | None:
        """How many distinct programs the jit cache holds. After
        `warmup()` this must STAY at `len(buckets)` under any load — the
        no-recompile guarantee the tests pin."""
        return int(self._jitted._cache_size())

    # -- the hot path --------------------------------------------------------
    def embed(self, images_u8: np.ndarray) -> np.ndarray:
        images_u8 = np.asarray(images_u8)
        s = self.image_size
        if (images_u8.ndim != 4 or images_u8.shape[1:] != (s, s, 3)
                or images_u8.dtype != np.uint8):
            raise ValueError(
                f"expected [n, {s}, {s}, 3] uint8, got "
                f"{images_u8.shape} {images_u8.dtype}"
            )
        n = images_u8.shape[0]
        bucket = bucket_for(n, self.buckets)  # raises when n > buckets[-1]
        if n < bucket:
            padded = np.zeros((bucket, s, s, 3), np.uint8)
            padded[:n] = images_u8
        else:
            padded = images_u8
        out = self._jitted(self.params, self.batch_stats, padded)
        return np.asarray(out[:n], np.float32)
