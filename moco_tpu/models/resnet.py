"""Flax ResNet backbone zoo (layer L3b of SURVEY.md §1).

The reference takes its encoders from `torchvision.models`
(`models.__dict__[arch](num_classes=dim)`, `main_moco.py:≈L40-46,165`). This
is a from-scratch flax implementation with matching structure so that (a) the
linear-probe checkpoint surgery has the same named-part semantics (backbone
vs final `fc`) and (b) the exporter (checkpoint.py) can emit
torchvision-style names for downstream consumers (SURVEY §2.6).

TPU-first choices:
- NHWC layout throughout (XLA:TPU's native convolution layout; torchvision's
  NCHW is a CUDA convention, not semantics).
- Weights/activations can run in bfloat16 via `dtype=`, with BN statistics
  and the parameter master copies kept in float32 (`param_dtype`).
- BatchNorm is PER-DEVICE by default (no cross-replica axis): MoCo's
  ShuffleBN depends on per-device statistics (SURVEY §7 hard part 1).
  `bn_cross_replica_axis` enables SyncBN only for transfer configs that
  want it (e.g. detection's `Base-RCNN-C4-BN`).

Structure parity notes (vs torchvision `resnet.py`):
- Bottleneck is v1.5: the stride sits on the 3x3 conv, not the 1x1.
- 3x3 convs use EXPLICIT symmetric padding 1 (torch semantics): flax's
  default SAME pads (0,1) at stride 2, a one-pixel tap shift that would
  make exported checkpoints run a slightly different network in torch
  consumers (pinned by tests/test_torch_consumer.py against real torch).
- Stem: 7x7/2 conv, BN, ReLU, 3x3/2 max-pool. `cifar_stem=True` swaps in the
  community CIFAR variant (3x3/1 conv, no max-pool) used by every CIFAR MoCo
  demo (BASELINE config 1).
- `torch` BN defaults: momentum 0.1, eps 1e-5 → flax momentum 0.9, eps 1e-5.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.models.batchnorm import BatchNorm

ModuleDef = Any


def _space_to_depth_stem(x, kernel, dtype):
    """The 7x7/2 ImageNet stem conv computed as a space-to-depth 4x4/1 conv.

    The MXU contracts over input channels in 128-lanes; a 3-channel conv
    leaves it ~2% utilized. Re-tiling the image into 2x2 blocks
    ([B,224,224,3] -> [B,112,112,12]) and zero-padding the kernel 7->8
    ([7,7,3,64] -> [4,4,12,64]) computes the IDENTICAL convolution (same
    products, regrouped) with 4x the contraction depth and no strided
    window. The parameter stays the torchvision-shaped [7,7,3,64] — only
    the trace-time compute is re-tiled, so checkpoints/exports are
    unchanged. (MLPerf-era TPU ResNet trick; derivation in the test.)

    Output position i reads x[2i+k-3], k=0..6. With the kernel left-padded
    to 8 taps (k'=k+1) this is x[2i+k'-4]; writing k'=2q+p with p the
    within-block offset gives blocks j=i+q-2, q=0..3 — a stride-1 4-tap
    block conv with padding (2,1).
    """
    b, h, w, c = x.shape
    kh, kw, cin, cout = kernel.shape  # [7,7,3,64]
    kpad = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))  # [8,8,3,64]
    k_s2d = (
        kpad.reshape(4, 2, 4, 2, cin, cout)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(4, 4, 4 * cin, cout)
    )
    x_s2d = (
        x.reshape(b, h // 2, 2, w // 2, 2, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b, h // 2, w // 2, 4 * c)
    )
    return jax.lax.conv_general_dilated(
        x_s2d.astype(dtype),
        k_s2d.astype(dtype),
        window_strides=(1, 1),
        padding=((2, 1), (2, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


class BasicBlock(nn.Module):
    """2x3x3 residual block (ResNet-18/34)."""

    filters: int
    strides: int = 1
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = BatchNorm

    @nn.compact
    def __call__(self, x):
        residual = x
        # explicit pad 1 on 3x3 convs: flax's default SAME pads (0,1) at
        # stride 2 — a one-pixel tap shift vs torchvision's symmetric
        # padding=1 at every stage transition, which would make exported
        # checkpoints run a (slightly) different network in torch consumers
        y = self.conv(
            self.filters, (3, 3), (self.strides, self.strides),
            padding=[(1, 1), (1, 1)], name="conv1",
        )(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        y = self.conv(
            self.filters, (3, 3), padding=[(1, 1), (1, 1)], name="conv2"
        )(y)
        y = self.norm(name="bn2")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), (self.strides, self.strides), name="downsample_conv"
            )(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(residual + y)


class Bottleneck(nn.Module):
    """1x1 → 3x3(stride) → 1x1(x4) residual block (ResNet-50/101/152, v1.5)."""

    filters: int
    strides: int = 1
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = BatchNorm
    expansion: int = 4

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = nn.relu(y)
        # explicit pad 1: torchvision-symmetric (see BasicBlock note)
        y = self.conv(
            self.filters, (3, 3), (self.strides, self.strides),
            padding=[(1, 1), (1, 1)], name="conv2",
        )(y)
        y = self.norm(name="bn2")(y)
        y = nn.relu(y)
        y = self.conv(self.filters * self.expansion, (1, 1), name="conv3")(y)
        y = self.norm(name="bn3")(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * self.expansion,
                (1, 1),
                (self.strides, self.strides),
                name="downsample_conv",
            )(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet encoder ending in a `num_classes`-dim `fc` head.

    For MoCo pretraining `num_classes` is the embedding dim (128) and
    `mlp_head=True` swaps `fc` for the v2 2-layer MLP head
    (`moco/builder.py:≈L25-35`: Linear(d,d) → ReLU → Linear(d,dim)).
    `num_classes=None` returns pooled backbone features (used by the linear
    probe and the kNN feature bank).
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int | None = 128
    mlp_head: bool = False
    cifar_stem: bool = False
    width: int = 64
    dtype: Any = jnp.float32
    bn_momentum: float = 0.9
    bn_cross_replica_axis: str | None = None
    s2d_stem: bool = True  # compute the 7x7/2 stem as a space-to-depth conv
                           # (identical math, ~4x MXU contraction depth);
                           # params/exports unchanged. Auto-skipped for odd
                           # input sizes.
    remat: bool = False    # per-residual-block rematerialization: save only
                           # block boundaries, recompute internals in the
                           # backward — trades (underutilized) MXU FLOPs for
                           # HBM traffic on the memory-bound step. Identical
                           # numerics (same ops, re-executed).

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(
            nn.Conv, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32
        )
        norm = partial(
            BatchNorm,
            use_running_average=not train,
            momentum=self.bn_momentum,
            epsilon=1e-5,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            axis_name=self.bn_cross_replica_axis,
        )

        x = x.astype(self.dtype)
        if self.cifar_stem:
            x = conv(self.width, (3, 3), name="conv1")(x)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
        elif self.s2d_stem and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            kernel = self.param(
                "conv1",
                # match nn.Conv's param tree: conv1/kernel with the default
                # initializer, so checkpoints are interchangeable with the
                # plain-conv stem
                lambda rng: {
                    "kernel": nn.initializers.lecun_normal()(
                        rng, (7, 7, x.shape[-1], self.width), jnp.float32
                    )
                },
            )["kernel"]
            x = _space_to_depth_stem(x, kernel, self.dtype)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        else:
            x = conv(self.width, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv1")(x)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])

        block_cls = nn.remat(self.block_cls) if self.remat else self.block_cls
        for i, num_blocks in enumerate(self.stage_sizes):
            for j in range(num_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = block_cls(
                    filters=self.width * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    name=f"layer{i + 1}_{j}",
                )(x)

        x = jnp.mean(x, axis=(1, 2))  # global average pool → [B, feat_dim]
        x = x.astype(jnp.float32)
        if self.num_classes is None:
            return x
        dense = partial(nn.Dense, dtype=jnp.float32, param_dtype=jnp.float32)
        if self.mlp_head:
            d = x.shape[-1]
            x = dense(d, name="fc_hidden")(x)
            x = nn.relu(x)
            x = dense(self.num_classes, name="fc")(x)
        else:
            x = dense(self.num_classes, name="fc")(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=(2, 2, 2, 2), block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=Bottleneck)
ResNet101 = partial(ResNet, stage_sizes=(3, 4, 23, 3), block_cls=Bottleneck)
ResNet152 = partial(ResNet, stage_sizes=(3, 8, 36, 3), block_cls=Bottleneck)

# 2-stage, width-16 micro-ResNet: smoke tests / CI on the single-core CPU
# sandbox, where a full ResNet-18 compile is minutes. Not a reference arch.
ResNetTiny = partial(ResNet, stage_sizes=(1, 1), block_cls=BasicBlock, width=16)

# `--arch` registry (the reference's `model_names`/`models.__dict__[arch]`).
ARCHS: dict[str, Callable[..., ResNet]] = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "resnet_tiny": ResNetTiny,
}

FEATURE_DIMS = {
    "resnet18": 512,
    "resnet34": 512,
    "resnet50": 2048,
    "resnet101": 2048,
    "resnet152": 2048,
    "resnet_tiny": 32,
}


def build_resnet(arch: str, **kwargs) -> ResNet:
    if arch not in ARCHS:
        raise ValueError(f"unknown arch {arch!r}; choose from {sorted(ARCHS)}")
    return ARCHS[arch](**kwargs)
