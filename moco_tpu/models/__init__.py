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


def build_backbone(arch: str, *, cifar_stem: bool = False, num_classes=None):
    """Feature-mode encoder for NON-TRAINING consumers (the lincls probe,
    the serve/ embedding service): one arch router for the three families
    (ResNet, ViT, the routed token encoder), so 'which constructor does this
    arch use' is decided in exactly one place. `num_classes=None` yields
    pooled backbone features, the transfer product both consumers read; a
    token encoder's is the mean over the positions, and it comes whole
    (every layer, every expert held: `build_sdar`'s own arguments cut it)."""
    if arch.startswith("sdar"):
        from moco_tpu.models.sdar import build_sdar

        return build_sdar(arch, num_classes=num_classes)
    if arch.startswith("vit"):
        from moco_tpu.models.vit import build_vit

        return build_vit(arch, num_classes=num_classes)
    return build_resnet(arch, num_classes=num_classes, cifar_stem=cifar_stem)


__all__ = [
    "build_backbone",
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
