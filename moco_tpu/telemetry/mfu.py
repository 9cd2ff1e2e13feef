"""Analytic-FLOPs MFU estimation (ISSUE 2 tentpole part 2).

MFU = achieved FLOP/s ÷ peak FLOP/s. The numerator comes from an ANALYTIC
count of the model's matmul/conv FLOPs (the standard convention: 2 FLOPs
per multiply-add, convs + dense layers only — BN/activations/pooling are
bandwidth, not FLOPs, and would flatter the number), scaled by the MoCo
step's encoder-pass structure:

  v1/v2 — query encoder forward+backward (3 fwd-equivalents, the standard
          1+2 fwd/bwd accounting) + key encoder forward (1): 4× per image
  v3    — BOTH crops through both encoders: query fwd+bwd on 2 crops (6)
          + momentum forward on 2 crops (2): 8× per image

Projection heads ARE counted (they are dense layers); the v3
predictor/projector MLPs beyond the configured head are not — they are
<0.5% of a ResNet-50/ViT step and the estimate documents itself as
backbone-dominated via `flops_per_image` in the run_start record.

The denominator is a per-chip peak-FLOPs table keyed on
`device.device_kind` (bf16 peaks from the Cloud TPU docs), overridable via
`config.peak_flops_per_chip` — the only honest option on CPU or unlisted
hardware, where auto-detection yields None and MFU is omitted rather than
fabricated.
"""

from __future__ import annotations

# (substring of device_kind lowercased, peak bf16 FLOP/s per chip).
# Ordered: more specific entries first — "v5p" must win over "v5".
PEAK_FLOPS_BF16 = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),   # some jax versions report v5e as "TPU v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def detect_peak_flops(device_kind: str) -> float | None:
    """Peak bf16 FLOP/s for a `device.device_kind` string, None if unknown
    (CPU, GPU, future TPUs) — callers must then rely on the config
    override or skip MFU."""
    kind = (device_kind or "").lower()
    for key, peak in PEAK_FLOPS_BF16:
        if key in kind:
            return peak
    return None


def _conv_flops(h_out: int, w_out: int, k: int, c_in: int, c_out: int) -> float:
    return 2.0 * h_out * w_out * k * k * c_in * c_out


def _conv_out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


# mirrors models/resnet.py: (stage_sizes, bottleneck?, width)
_RESNET_SPECS = {
    "resnet18": ((2, 2, 2, 2), False, 64),
    "resnet34": ((3, 4, 6, 3), False, 64),
    "resnet50": ((3, 4, 6, 3), True, 64),
    "resnet101": ((3, 4, 23, 3), True, 64),
    "resnet152": ((3, 8, 36, 3), True, 64),
    "resnet_tiny": ((1, 1), False, 16),
}

# mirrors models/vit.py: (width, depth, patch_size)
_VIT_SPECS = {
    "vit_small": (384, 12, 16),
    "vit_base": (768, 12, 16),
    "vit_large": (1024, 24, 16),
    "vit_huge": (1280, 32, 14),
    "vit_tiny": (64, 2, 16),
}


def resnet_fwd_flops(arch: str, image_size: int, cifar_stem: bool = False) -> float:
    """Forward conv FLOPs per image for the flax ResNet in models/resnet.py
    (2·H·W·K²·Cin·Cout per conv, including downsample projections;
    excludes BN/ReLU/pool and any head — see head_fwd_flops)."""
    stage_sizes, bottleneck, width = _RESNET_SPECS[arch]
    flops = 0.0
    if cifar_stem:
        size = image_size  # 3x3/1 conv, no pool
        flops += _conv_flops(size, size, 3, 3, width)
    else:
        size = _conv_out(image_size, 7, 2, 3)
        flops += _conv_flops(size, size, 7, 3, width)
        size = _conv_out(size, 3, 2, 1)  # max-pool: no FLOPs, changes size
    expansion = 4 if bottleneck else 1
    c_in = width
    for i, num_blocks in enumerate(stage_sizes):
        filters = width * 2**i
        c_out = filters * expansion
        for j in range(num_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_size = _conv_out(size, 3, stride, 1)
            if bottleneck:
                flops += _conv_flops(size, size, 1, c_in, filters)          # conv1 1x1
                flops += _conv_flops(out_size, out_size, 3, filters, filters)  # conv2 3x3/s
                flops += _conv_flops(out_size, out_size, 1, filters, c_out)    # conv3 1x1
            else:
                flops += _conv_flops(out_size, out_size, 3, c_in, filters)  # conv1 3x3/s
                flops += _conv_flops(out_size, out_size, 3, filters, filters)  # conv2 3x3
            if stride != 1 or c_in != c_out:  # downsample projection
                flops += _conv_flops(out_size, out_size, 1, c_in, c_out)
            c_in, size = c_out, out_size
    return flops


def vit_fwd_flops(arch: str, image_size: int) -> float:
    """Forward matmul FLOPs per image for the flax ViT in models/vit.py:
    patch embed + per-block (qkv, scores, attn·V, proj, 4x MLP); excludes
    LayerNorm/GELU and any head."""
    width, depth, patch = _VIT_SPECS[arch]
    grid = image_size // patch
    n = grid * grid + 1  # patch tokens + class token
    d = width
    flops = 2.0 * (grid * grid) * (patch * patch * 3) * d  # patch embed conv
    per_block = (
        2.0 * n * d * (3 * d)      # qkv projection
        + 2.0 * n * n * d          # Q·Kᵀ scores
        + 2.0 * n * n * d          # scores·V
        + 2.0 * n * d * d          # output projection
        + 2.0 * 2 * n * d * (4 * d)  # MLP fc1 + fc2 (ratio 4)
    )
    return flops + depth * per_block


def _routed_token_flops(z: dict, held: int) -> float:
    """What a token of a routed block costs whatever its attention attends to:
    the q/k/v/o projections, the router over all experts, and the held experts'
    three products at the assignments uniform routing sends here."""
    d, hd = z["hidden"], z["head_dim"]
    return (
        2.0 * d * (z["heads"] + 2 * z["kv_heads"]) * hd    # q, k, v projections
        + 2.0 * z["heads"] * hd * d                        # output projection
        + 2.0 * d * z["experts"]                           # router
        + z["top_k"] * (held or z["experts"]) / z["experts"]
        * 3 * 2.0 * d * z["expert_width"]                  # gate, up, down
    )


def sdar_fwd_flops(arch: str, seq_len: int, layers: int = 0, held: int = 0) -> float:
    """Forward matmul FLOPs per VIEW (one document's `seq_len` tokens) for
    the routed token encoder in models/sdar.py: per block the q/k/v/o
    projections, scores and mix at the block-causal mask's density, the
    router over all experts, and the held experts' three products at the
    assignments uniform routing sends here (`top_k * held / experts` a
    token); excludes norms, rotary, softmax, the sort and the embedding
    lookup. `layers` / `held`: 0 is the arch's own number."""
    from moco_tpu.models.sdar import SDAR_SIZES

    z = SDAR_SIZES[arch]
    blocks = -(-seq_len // z["block_length"])
    density = (blocks + 1) / (2.0 * blocks)
    per_token = (_routed_token_flops(z, held)
                 + 2.0 * 2 * seq_len * density * z["heads"] * z["head_dim"])    # scores + mix
    return (layers or z["layers"]) * seq_len * per_token


def looped_fwd_flops(arch: str, seq_len: int, layers: int = 0) -> float:
    """Forward matmul FLOPs per VIEW for the looped dense token encoder in
    models/ouro.py: per layer application the q/k/v/o projections, scores and
    mix at the causal mask's density and the MLP's three products, times the
    layers, times the passes over them (a layer is counted once a PASS: the
    weights are shared, the work is not); excludes norms, rotary, softmax and
    the embedding lookup. `layers`: 0 is the arch's own number."""
    from moco_tpu.models.ouro import OURO_SIZES

    z = OURO_SIZES[arch]
    d, hd = z["hidden"], z["head_dim"]
    density = (seq_len + 1) / (2.0 * seq_len)
    per_token = (
        2.0 * d * (z["heads"] + 2 * z["kv_heads"]) * hd    # q, k, v projections
        + 2.0 * 2 * seq_len * density * z["heads"] * hd    # scores + mix
        + 2.0 * z["heads"] * hd * d                        # output projection
        + 3 * 2.0 * d * z["width"]                         # gate, up, down
    )
    return z["ut_steps"] * (layers or z["layers"]) * seq_len * per_token


def head_fwd_flops(arch: str, embed_dim: int, mlp_head: bool) -> float:
    """Projection-head dense FLOPs per image (fc, or the v2 2-layer MLP)."""
    from moco_tpu.models import is_token_encoder, token_sizes
    from moco_tpu.models.resnet import FEATURE_DIMS

    if arch in _VIT_SPECS:
        feat = _VIT_SPECS[arch][0]
    elif is_token_encoder(arch):
        feat = token_sizes(arch)["hidden"]
    else:
        feat = FEATURE_DIMS[arch]
    if mlp_head:
        return 2.0 * feat * feat + 2.0 * feat * embed_dim
    return 2.0 * feat * embed_dim


def selecting_fwd_flops(arch: str, seq_len: int, layers: int = 0, held: int = 0) -> float:
    """Forward matmul FLOPs per VIEW for the routed token encoder with learned
    sparse attention in models/keye.py: `sdar_fwd_flops`' count with the
    scores and mix at the SELECTED pairs (`sum_t min(t + 1, topk)` a view, not
    the causal half), plus the indexer's three projections and its scores over
    every causal pair."""
    from moco_tpu.models.keye import KEYE_SIZES

    z = KEYE_SIZES[arch]
    ih, idim, n = z["index_heads"], z["index_dim"], min(z["index_topk"], seq_len)
    selected = n * (n + 1) / 2 + (seq_len - n) * z["index_topk"]     # pairs a view
    causal = seq_len * (seq_len + 1) / 2
    per_token = _routed_token_flops(z, held) + 2.0 * z["hidden"] * (ih * idim + idim + ih)
    per_view = 2.0 * 2 * selected * z["heads"] * z["head_dim"] + 2.0 * causal * ih * idim
    return (layers or z["layers"]) * (seq_len * per_token + per_view)


def model_fwd_flops(arch: str, image_size: int, *, cifar_stem: bool = False,
                    embed_dim: int = 128, mlp_head: bool = False, seq_len: int = 512,
                    num_hidden_layers: int = 0, num_experts: int = 0) -> float:
    """Backbone + head forward FLOPs per image (a token encoder: per view of
    `seq_len` tokens) for any supported arch."""
    from moco_tpu.models.keye import KEYE_SIZES
    from moco_tpu.models.ouro import OURO_SIZES
    from moco_tpu.models.sdar import SDAR_SIZES

    if arch in _VIT_SPECS:
        body = vit_fwd_flops(arch, image_size)
    elif arch in _RESNET_SPECS:
        body = resnet_fwd_flops(arch, image_size, cifar_stem)
    elif arch in SDAR_SIZES:
        body = sdar_fwd_flops(arch, seq_len, num_hidden_layers, num_experts)
    elif arch in OURO_SIZES:
        body = looped_fwd_flops(arch, seq_len, num_hidden_layers)
    elif arch in KEYE_SIZES:
        body = selecting_fwd_flops(arch, seq_len, num_hidden_layers, num_experts)
    else:
        raise ValueError(f"no analytic FLOPs model for arch {arch!r}")
    return body + head_fwd_flops(arch, embed_dim, mlp_head)


# fwd-equivalent encoder passes per image: fwd+bwd = 3 fwd (standard 1+2
# accounting), momentum fwd = 1
_STEP_MULTIPLIER = {"v1": 3 + 1, "v2": 3 + 1, "v3": 2 * 3 + 2 * 1}


def train_step_flops(config) -> float:
    """Analytic FLOPs for ONE global-batch training step of `config`."""
    per_image = model_fwd_flops(
        config.arch, config.image_size, cifar_stem=config.cifar_stem,
        embed_dim=config.embed_dim, mlp_head=config.mlp_head,
        seq_len=config.seq_len, num_hidden_layers=config.num_hidden_layers,
        num_experts=config.num_experts,
    )
    return per_image * _STEP_MULTIPLIER[config.variant] * config.batch_size


class MFUEstimator:
    """step wall time → model-FLOPs utilization fraction.

    `peak_flops_per_chip` None/0 disables (mfu() returns None) — never
    fabricate a denominator."""

    def __init__(self, flops_per_step: float, n_chips: int,
                 peak_flops_per_chip: float | None, sharding: str = "dp"):
        self.flops_per_step = float(flops_per_step)
        self.n_chips = max(int(n_chips), 1)
        self.peak_flops_per_chip = (
            float(peak_flops_per_chip) if peak_flops_per_chip else None
        )
        # the sharding mode the MFU is reported under (ISSUE 15): the
        # analytic FLOPs are layout-invariant — fsdp changes per-device
        # PARAM BYTES (the telemetry `sharding` event carries the measured
        # inventory) and the collective schedule, never the model math —
        # so the estimator carries the label rather than a different count
        self.sharding = sharding

    @classmethod
    def for_config(cls, config, n_chips: int, device_kind: str = ""):
        peak = config.peak_flops_per_chip or detect_peak_flops(device_kind)
        return cls(train_step_flops(config), n_chips, peak,
                   sharding=getattr(config, "sharding", "dp"))

    def mfu(self, step_s: float) -> float | None:
        if not self.peak_flops_per_chip or step_s <= 0:
            return None
        achieved = self.flops_per_step / step_s
        return achieved / (self.peak_flops_per_chip * self.n_chips)
