"""Forward+backward operations of one training step, by the usual analytic
count (copied in arithmetic from `moco_tpu/telemetry/mfu.py`; the original is
listed in PERF.md for a later PR to delete): a multiply-add is two operations,
backward is twice forward, and a step is 4 forward-equivalents per image for
v1/v2 (query forward+backward 3, key forward 1) and 8 for v3 (two views through
both encoders). Recomputation is not counted.
"""

RESNET_STAGES = {"resnet50": (3, 4, 6, 3), "resnet18": (2, 2, 2, 2)}


def _conv(h, w, k, cin, cout, stride=1):
    return 2 * (h // stride) * (w // stride) * k * k * cin * cout


def resnet_forward(arch: str, size: int, embed: int, stem: int = 7) -> float:
    bottleneck = arch == "resnet50"
    h = size // 2 if stem == 7 else size
    total = _conv(size, size, stem, 3, 64, 2 if stem == 7 else 1)
    h = h // 2 if stem == 7 else h
    cin = 64
    for i, blocks in enumerate(RESNET_STAGES[arch]):
        f = 64 * 2 ** i
        out = 4 * f if bottleneck else f
        for j in range(blocks):
            s = 2 if i > 0 and j == 0 else 1
            if bottleneck:
                total += _conv(h, h, 1, cin, f) + _conv(h, h, 3, f, f, s) + _conv(h // s, h // s, 1, f, out)
            else:
                total += _conv(h, h, 3, cin, f, s) + _conv(h // s, h // s, 3, f, f)
            if cin != out or s == 2:
                total += _conv(h, h, 1, cin, out, s)
            h, cin = h // s, out
    return total + 2 * cin * cin + 2 * cin * embed


def vit_forward(width: int, depth: int, tokens: int, patch: int = 16) -> float:
    per_block = 2 * tokens * width * 3 * width + 2 * 2 * tokens * tokens * width \
        + 2 * tokens * width * width + 2 * 2 * tokens * width * 4 * width
    return 2 * (tokens - 1) * patch * patch * 3 * width + depth * per_block


def step_flops(config, model: dict | None = None) -> float:
    """`config` is the trainer's; `model` the `model` group of the configuration's
    file, which a transformer's file has to carry (its widths are nowhere else)."""
    if config.arch.startswith("vit"):
        if not model:
            raise KeyError(f"the configuration's file has no `model` group for {config.arch}")
        width, hidden = model["width"], model["projector_hidden"]
        tokens = (config.image_size // model["patch_size"]) ** 2 + 1
        fwd = vit_forward(width, model["depth"], tokens, model["patch_size"])
        fwd += 2 * (width * hidden + hidden * hidden + hidden * config.embed_dim)
    else:
        fwd = resnet_forward(config.arch, config.image_size, config.embed_dim,
                             3 if config.cifar_stem else 7)
    return fwd * (8 if config.variant == "v3" else 4) * config.batch_size
