"""REAL torch consumers for every export dialect (upgrade over the r2 numpy
emulations — torch-cpu is in the image, so the dialects are verified against
genuine torch module semantics: Conv2d/BatchNorm2d/LayerNorm/Linear NCHW
forward passes).

- torchvision dialect (`module.encoder_q.*`): a from-scratch torch ResNet
  with torchvision's exact module names consumes `export`ed weights
  `strict=True` and reproduces the flax forward.
- timm ViT dialect: a from-scratch torch ViT with timm's fused-qkv layout
  consumes a `vit_to_timm` export and reproduces the flax class-token
  feature (pos_embed consumed the timm way: added AFTER cls concat).
- Detectron2 pkl: renamed back to torchvision names, consumed by the torch
  backbone, features match.

These pin the reference consumer contracts: `main_lincls.py:≈L176-200`
surgery expects torchvision names; `detection/convert-pretrain-to-
detectron2.py:≈L1-40` names; moco-v3's lincls consumes timm ViTs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")


# ---------------------------------------------------------------------------
# minimal torch ResNet with torchvision's exact state_dict names
# ---------------------------------------------------------------------------


class TBasic(torch.nn.Module):
    def __init__(self, cin, cout, stride):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
        self.bn1 = torch.nn.BatchNorm2d(cout)
        self.conv2 = torch.nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
        self.bn2 = torch.nn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = torch.nn.Sequential(
                torch.nn.Conv2d(cin, cout, 1, stride, bias=False),
                torch.nn.BatchNorm2d(cout),
            )

    def forward(self, x):
        r = x if self.downsample is None else self.downsample(x)
        y = torch.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return torch.relu(r + y)


class TBottleneck(torch.nn.Module):
    def __init__(self, cin, width, stride):
        super().__init__()
        cout = width * 4
        self.conv1 = torch.nn.Conv2d(cin, width, 1, bias=False)
        self.bn1 = torch.nn.BatchNorm2d(width)
        self.conv2 = torch.nn.Conv2d(width, width, 3, stride, 1, bias=False)
        self.bn2 = torch.nn.BatchNorm2d(width)
        self.conv3 = torch.nn.Conv2d(width, cout, 1, bias=False)
        self.bn3 = torch.nn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = torch.nn.Sequential(
                torch.nn.Conv2d(cin, cout, 1, stride, bias=False),
                torch.nn.BatchNorm2d(cout),
            )

    def forward(self, x):
        r = x if self.downsample is None else self.downsample(x)
        y = torch.relu(self.bn1(self.conv1(x)))
        y = torch.relu(self.bn2(self.conv2(y)))
        y = self.bn3(self.conv3(y))
        return torch.relu(r + y)


class TResNet(torch.nn.Module):
    def __init__(self, stages, block, width=64, num_classes=16, mlp=False):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(3, width, 7, 2, 3, bias=False)
        self.bn1 = torch.nn.BatchNorm2d(width)
        self.maxpool = torch.nn.MaxPool2d(3, 2, 1)
        cin = width
        for i, n in enumerate(stages):
            blocks = []
            for j in range(n):
                stride = 2 if i > 0 and j == 0 else 1
                if block is TBasic:
                    blocks.append(TBasic(cin, width * 2**i, stride))
                    cin = width * 2**i
                else:
                    blocks.append(TBottleneck(cin, width * 2**i, stride))
                    cin = width * 2**i * 4
            setattr(self, f"layer{i + 1}", torch.nn.Sequential(*blocks))
        self.nstages = len(stages)
        if num_classes is None:
            self.fc = None
        elif mlp:
            self.fc = torch.nn.Sequential(
                torch.nn.Linear(cin, cin), torch.nn.ReLU(),
                torch.nn.Linear(cin, num_classes),
            )
        else:
            self.fc = torch.nn.Linear(cin, num_classes)

    def forward(self, x):
        x = self.maxpool(torch.relu(self.bn1(self.conv1(x))))
        for i in range(self.nstages):
            x = getattr(self, f"layer{i + 1}")(x)
        x = x.mean(dim=(2, 3))
        return x if self.fc is None else self.fc(x)


def _randomized_stats(stats, seed=5):
    """Non-trivial running stats so a mean/var swap can't hide."""
    rng = np.random.RandomState(seed)

    def f(path, leaf):
        name = jax.tree_util.keystr(path)
        arr = 0.5 * rng.rand(*leaf.shape).astype(np.float32)
        return arr + (1.0 if "var" in name else 0.0)

    return jax.tree_util.tree_map_with_path(f, stats)


def _load_torch(model, flat):
    sd = {k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in flat.items()}
    missing, unexpected = model.load_state_dict(sd, strict=False)
    # torch tracks num_batches_tracked per BN; everything else must match
    assert not unexpected, unexpected
    assert all("num_batches_tracked" in m for m in missing), missing
    return model.eval()


def test_torch_resnet18_consumes_export():
    """`module.encoder_q.`-style export → real torch ResNet-18, strict names,
    matching eval forward (the lincls surgery consumer contract)."""
    from moco_tpu.checkpoint import resnet_to_torchvision
    from moco_tpu.models import build_resnet

    model = build_resnet("resnet18", num_classes=16, s2d_stem=False)
    x = jax.random.normal(jax.random.key(0), (2, 64, 64, 3), jnp.float32)
    v = model.init(jax.random.key(1), x, train=False)
    stats = _randomized_stats(v["batch_stats"])
    ours = np.asarray(
        model.apply({"params": v["params"], "batch_stats": stats}, x, train=False)
    )
    flat = resnet_to_torchvision(
        jax.tree.map(np.asarray, v["params"]), jax.tree.map(np.asarray, stats)
    )
    tmodel = _load_torch(TResNet((2, 2, 2, 2), TBasic, num_classes=16), flat)
    with torch.no_grad():
        theirs = tmodel(torch.from_numpy(
            np.asarray(x).transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_torch_bottleneck_mlp_consumes_export():
    """Bottleneck + v2 MLP head (fc.0/fc.2) through the same contract."""
    from moco_tpu.checkpoint import resnet_to_torchvision
    from moco_tpu.models.resnet import Bottleneck, ResNet

    model = ResNet(stage_sizes=(1, 1), block_cls=Bottleneck, width=8,
                   num_classes=12, mlp_head=True, s2d_stem=False)
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3), jnp.float32)
    v = model.init(jax.random.key(3), x, train=False)
    stats = _randomized_stats(v["batch_stats"], seed=6)
    ours = np.asarray(
        model.apply({"params": v["params"], "batch_stats": stats}, x, train=False)
    )
    flat = resnet_to_torchvision(
        jax.tree.map(np.asarray, v["params"]), jax.tree.map(np.asarray, stats)
    )
    tmodel = _load_torch(
        TResNet((1, 1), TBottleneck, width=8, num_classes=12, mlp=True), flat
    )
    with torch.no_grad():
        theirs = tmodel(torch.from_numpy(
            np.asarray(x).transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_torch_consumes_detectron2_pkl():
    """pkl → rename Detectron2 names back to torchvision → torch backbone
    forward matches the flax feature output (value-level consumer check the
    r2 round recorded as impossible without torch)."""
    import pickle

    from moco_tpu.checkpoint import resnet_to_torchvision
    from moco_tpu.export_detectron2 import torchvision_flat_to_detectron2
    from moco_tpu.models import build_resnet

    model = build_resnet("resnet18", num_classes=None, s2d_stem=False)
    x = jax.random.normal(jax.random.key(4), (1, 64, 64, 3), jnp.float32)
    v = model.init(jax.random.key(5), x, train=False)
    stats = _randomized_stats(v["batch_stats"], seed=7)
    ours = np.asarray(
        model.apply({"params": v["params"], "batch_stats": stats}, x, train=False)
    )
    flat = resnet_to_torchvision(
        jax.tree.map(np.asarray, v["params"]), jax.tree.map(np.asarray, stats)
    )
    det2 = torchvision_flat_to_detectron2(
        {f"module.encoder_q.{k}": v_ for k, v_ in flat.items()}
    )
    blob = pickle.loads(pickle.dumps(det2))  # round-trip like the real pkl

    # invert the naming: stem.conv1{,.norm} → conv1/bn1; resN.M.convK{,.norm}
    # → layer(N-1).M.{convK,bnK}; shortcut{,.norm} → downsample.0/1
    back = {}
    bn_leaves = {"weight": "weight", "bias": "bias",
                 "running_mean": "running_mean", "running_var": "running_var"}
    for k, arr in blob.items():
        parts = k.split(".")
        if parts[0] == "stem":
            if parts[2] == "norm":
                back[f"bn1.{bn_leaves[parts[3]]}"] = arr
            else:
                back[f"conv1.{parts[2]}"] = arr
        else:
            stage = int(parts[0][len("res"):]) - 1
            base = f"layer{stage}.{parts[1]}"
            if parts[2] == "shortcut":
                if parts[3] == "norm":
                    back[f"{base}.downsample.1.{bn_leaves[parts[4]]}"] = arr
                else:
                    back[f"{base}.downsample.0.{parts[3]}"] = arr
            elif parts[3] == "norm":
                back[f"{base}.bn{parts[2][len('conv'):]}.{bn_leaves[parts[4]]}"] = arr
            else:
                back[f"{base}.{parts[2]}.{parts[3]}"] = arr
    tmodel = _load_torch(TResNet((2, 2, 2, 2), TBasic, num_classes=None), back)
    with torch.no_grad():
        theirs = tmodel(torch.from_numpy(
            np.asarray(x).transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# minimal torch ViT with timm's fused-qkv layout and names
# ---------------------------------------------------------------------------


class TBlock(torch.nn.Module):
    def __init__(self, d, heads):
        super().__init__()
        # timm's LayerNorm eps is 1e-6 (torch default 1e-5 visibly diverges
        # on the near-zero cls row)
        self.norm1 = torch.nn.LayerNorm(d, eps=1e-6)
        self.attn = torch.nn.Module()
        self.attn.qkv = torch.nn.Linear(d, 3 * d)
        self.attn.proj = torch.nn.Linear(d, d)
        self.norm2 = torch.nn.LayerNorm(d, eps=1e-6)
        self.mlp = torch.nn.Module()
        self.mlp.fc1 = torch.nn.Linear(d, 4 * d)
        self.mlp.fc2 = torch.nn.Linear(4 * d, d)
        self.h = heads
        self.d = d

    def forward(self, x):
        b, n, d = x.shape
        y = self.norm1(x)
        qkv = self.attn.qkv(y).reshape(b, n, 3, self.h, d // self.h)
        q, k, v = qkv.unbind(2)  # [b, n, h, hd]
        q = q.transpose(1, 2)
        k = k.transpose(1, 2)
        v = v.transpose(1, 2)
        a = torch.softmax(q @ k.transpose(-2, -1) / math.sqrt(d // self.h), -1)
        y = (a @ v).transpose(1, 2).reshape(b, n, d)
        x = x + self.attn.proj(y)
        y = self.norm2(x)
        y = self.mlp.fc2(torch.nn.functional.gelu(self.mlp.fc1(y)))
        return x + y


class TViT(torch.nn.Module):
    def __init__(self, d, depth, heads, patch):
        super().__init__()
        self.patch_embed = torch.nn.Module()
        self.patch_embed.proj = torch.nn.Conv2d(3, d, patch, patch)
        self.cls_token = torch.nn.Parameter(torch.zeros(1, 1, d))
        self.pos_embed = None  # set from the export (timm consumes it)
        self.blocks = torch.nn.Sequential(*[TBlock(d, heads) for _ in range(depth)])
        self.norm = torch.nn.LayerNorm(d, eps=1e-6)

    def forward(self, x):
        b = x.shape[0]
        x = self.patch_embed.proj(x).flatten(2).transpose(1, 2)  # [b, n, d]
        x = torch.cat([self.cls_token.expand(b, -1, -1), x], dim=1)
        x = x + self.pos_embed  # timm order: pos added AFTER cls concat
        x = self.blocks(x)
        return self.norm(x)[:, 0]


def test_torch_vit_consumes_timm_export():
    """vit_to_timm export → real torch fused-qkv ViT (timm layout) → class
    token feature matches the flax forward (moco-v3 lincls consumer)."""
    from moco_tpu.checkpoint import vit_to_timm
    from moco_tpu.models.vit import build_vit

    model = build_vit("vit_tiny", num_classes=None)
    x = jax.random.normal(jax.random.key(6), (2, 32, 32, 3), jnp.float32)
    v = model.init(jax.random.key(7), x, train=False)
    ours = np.asarray(model.apply(v, x, train=False))
    flat = vit_to_timm(jax.tree.map(np.asarray, v["params"]), grid=(2, 2))

    tmodel = TViT(64, 2, 2, 16)
    pos = torch.from_numpy(np.ascontiguousarray(flat.pop("pos_embed")))
    sd = {k: torch.from_numpy(np.ascontiguousarray(a)) for k, a in flat.items()}
    missing, unexpected = tmodel.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert missing == [], missing
    tmodel.pos_embed = pos
    tmodel.eval()
    with torch.no_grad():
        theirs = tmodel(torch.from_numpy(
            np.asarray(x).transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)
