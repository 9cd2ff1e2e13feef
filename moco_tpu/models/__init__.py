from moco_tpu.models.resnet import (
    ARCHS,
    FEATURE_DIMS,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    build_resnet,
)
from moco_tpu.models.heads import V3Predictor, V3Projector


# -- token encoders: one door for every family ---------------------------------
# A family is a module of this package whose archs share a name's prefix. It
# has `SIZES` (arch -> the published sizes), `build(arch, num_classes, *,
# layers, held, vocab, **kwargs)`, `STAT_COLLECTIONS` (the flax collections its
# forward pass sows counters into) and `health(counted, tokens)` (those counters
# reduced for the step's `health` block). What the trainer asks of a token
# encoder it asks here, of the sizes, never of a family's name.
_TOKEN_FAMILIES = {"sdar": "moco_tpu.models.sdar",    # routed, block-causal GQA
                   "ouro": "moco_tpu.models.ouro",    # dense, weight-shared and looped
                   "keye": "moco_tpu.models.keye"}    # routed, learned sparse attention


def _family_module(arch: str) -> str | None:
    return next((m for prefix, m in _TOKEN_FAMILIES.items() if arch.startswith(prefix)), None)


def is_token_encoder(arch: str) -> bool:
    """Fed `int32` rows and lengths where an image encoder is fed canvases."""
    return _family_module(arch) is not None


def _token_family(arch: str):
    import importlib

    module = _family_module(arch)
    if module is None:
        raise ValueError(f"unknown token-encoder arch {arch!r}")
    return importlib.import_module(module)


def token_sizes(arch: str) -> dict:
    sizes = _token_family(arch).SIZES
    if arch not in sizes:
        raise ValueError(f"unknown token-encoder arch {arch!r}; choose from {sorted(sizes)}")
    return sizes[arch]


def held_vocab(arch: str, vocab_size: int = 0) -> int:
    """Ids of the vocabulary slice held here: `vocab_size` first ids, or all."""
    return vocab_size or token_sizes(arch)["vocab"]


def has_router(arch: str) -> bool:
    """Whether the encoder routes tokens to experts: a router to mask where a
    share holds it constant, expert counters to read."""
    return "experts" in token_sizes(arch)


def constant_modules(arch: str, held: int = 0) -> tuple:
    """Names of the encoder's modules whose leaves are constants of the step
    (`stop_gradient` in the model; the optimizer's mask, `models/sdar.py::
    trainable_mask`, keeps the weight decay off them): the router of a share of
    the routed layer (`held` under the router's width), and a family's own
    (`CONSTANT_MODULES`: a sparse-attention indexer)."""
    z = token_sizes(arch)
    share = "experts" in z and 0 < held < z["experts"]
    return ("router",) * share + tuple(getattr(_token_family(arch), "CONSTANT_MODULES", ()))


def token_counters(arch: str) -> tuple:
    """The collections the encoder's forward pass sows its counters into, and
    the family's `health(counted, tokens)` that reduces them for the step's
    stride-gated block."""
    family = _token_family(arch)
    return family.STAT_COLLECTIONS, family.health


def attention_path(arch: str, length: int, batch: int = 0, remat: bool = False,
                   dtype="float32") -> dict:
    """The path the encoder's attention takes for views of `length` tokens on
    this backend, with its tile counts and who prepares q and k: the `attn`
    block of the run's `setup` event. An encoder whose attention selects its
    keys adds `select`: how many a query, and who scores and selects them. Under
    `remat`, a family whose layers keep named values from the query forward to
    the backward pass (`kept_by_remat`) adds `kept`: the names this program
    carries and their bytes a layer for `batch` views a device in `dtype`."""
    from moco_tpu.ops.pallas_attention import attention_plan

    z = token_sizes(arch)
    topk = z.get("index_topk", 0)
    plan = attention_plan(length, z["head_dim"], z["block_length"],
                          qk_norm=z.get("qk_norm", True), masked=bool(topk))
    if topk:
        from moco_tpu.ops.pallas_select import select_plan

        plan["select"] = {"topk": topk, "path": select_plan(length, topk, z["index_dim"])}
    kept_by_remat = getattr(_token_family(arch), "kept_by_remat", None)
    kept = kept_by_remat(z, plan["path"], batch, length, dtype) if remat and kept_by_remat else None
    return {**plan, "kept": kept} if kept else plan


def dispatch_path(arch: str, batch: int, length: int, held: int = 0) -> dict | None:
    """How the encoder's routed layers move their rows on this backend for
    `batch` views of `length` tokens a device, with `held` experts here (0: the
    arch's own number), beside the passes' static sizes: the `moe` block of the
    run's `setup` event. `None` for an encoder without a router."""
    if not has_router(arch):
        return None
    z = token_sizes(arch)
    return _token_family(arch).dispatch_path(batch * length, z["hidden"], z["top_k"],
                                             z["experts"], held or z["experts"])


def build_token_encoder(arch: str, num_classes=None, **cut):
    """`cut`: `layers`, `held`, `vocab` (0 is the arch's own number) and the
    module's own arguments (`mlp_head`, `remat`, `dtype`)."""
    return _token_family(arch).build(arch, num_classes, **cut)


def build_backbone(arch: str, *, cifar_stem: bool = False, num_classes=None):
    """Feature-mode encoder for NON-TRAINING consumers (the lincls probe,
    the serve/ embedding service): one arch router for the three kinds
    (ResNet, ViT, a token encoder), so 'which constructor does this
    arch use' is decided in exactly one place. `num_classes=None` yields
    pooled backbone features, the transfer product both consumers read; a
    token encoder's is the mean over the positions, and it comes whole
    (every layer, every expert held: the builder's own arguments cut it)."""
    if is_token_encoder(arch):
        return build_token_encoder(arch, num_classes=num_classes)
    if arch.startswith("vit"):
        from moco_tpu.models.vit import build_vit

        return build_vit(arch, num_classes=num_classes)
    return build_resnet(arch, num_classes=num_classes, cifar_stem=cifar_stem)


__all__ = [
    "build_backbone",
    "is_token_encoder",
    "token_sizes",
    "held_vocab",
    "has_router",
    "token_counters",
    "constant_modules",
    "attention_path",
    "dispatch_path",
    "build_token_encoder",
    "ARCHS",
    "FEATURE_DIMS",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "build_resnet",
    "V3Predictor",
    "V3Projector",
]
