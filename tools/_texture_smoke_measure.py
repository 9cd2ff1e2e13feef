"""Derive the CI learning-detection thresholds (VERDICT r4 #5).

First r5 measurement (320 steps, resnet_tiny, 3 seeds): the trained-vs-
untrained VAL kNN delta at CI scale is NEGATIVE on every seed (-0.5 to
-5.7 pts) — the class-clustering dip phase the r5 horizon sweep also
shows at 320 steps. So class-level kNN is NOT a usable frozen-encoder
detector at CI cost; it only becomes one at horizon scale.

What IS separable at CI scale is positive-pair alignment
(`metrics["pos_sim"]`, the mean q·k⁺ cosine): only aug-invariance
optimization moves it, so this tool measures it for a LIVE run vs a
FROZEN null (lr ≈ 0 — same program, optimizer steps that move nothing)
over 3 seeds each, and the CI test asserts a margin between the two
populations. The frozen null is the exact regression CI must catch.

Usage: python tools/_texture_smoke_measure.py [steps] [lr]
"""
import json, os, sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from moco_tpu.parallel.mesh import force_cpu_devices

force_cpu_devices(8)  # mirror the CI conftest topology
from moco_tpu.config import get_preset
from moco_tpu.data.datasets import SyntheticTextureDataset
from moco_tpu.train import train
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

steps = int(sys.argv[1]) if len(sys.argv) > 1 else 256
lr = float(sys.argv[2]) if len(sys.argv) > 2 else 0.12
SPE = 32  # 1024 samples / B32


def run(seed, use_lr):
    cfg = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, dataset="synthetic_texture",
        image_size=32, batch_size=32, num_negatives=512, embed_dim=64,
        lr=use_lr, momentum_ema=0.99, cos=True, epochs=max(steps // SPE, 1),
        knn_monitor=True, knn_every_epochs=max(steps // SPE, 1),
        knn_bank_size=768, num_classes=16, ckpt_dir="", tb_dir="",
        print_freq=SPE - 1, seed=seed,
    )
    data = SyntheticTextureDataset(num_samples=1024, image_size=32,
                                   num_classes=16, seed=seed)
    state, metrics = train(cfg, dataset=data)
    return {
        "seed": seed, "lr": use_lr,
        "untrained_knn": round(metrics["knn_val_top1_untrained"], 4),
        "trained_knn": round(metrics["knn_val_top1"], 4),
        "pos_sim": round(metrics["pos_sim"], 4),
        "loss": round(metrics["loss"], 3), "steps": int(state.step),
    }


live, frozen = [], []
for seed in (0, 1, 2):
    row = run(seed, lr)
    live.append(row)
    print(json.dumps({"live": row}), flush=True)
    row = run(seed, 1e-9)  # frozen null: _effective_lr rejects exactly 0
    frozen.append(row)
    print(json.dumps({"frozen": row}), flush=True)
print(json.dumps({
    # executed count: epochs floor to a multiple of SPE, so a non-multiple
    # request runs fewer steps than asked — report what actually ran
    "lr": lr, "steps": max(steps // SPE, 1) * SPE,
    "live_pos_sim_min": min(r["pos_sim"] for r in live),
    "frozen_pos_sim_max": max(r["pos_sim"] for r in frozen),
    "live_knn_delta": [round(r["trained_knn"] - r["untrained_knn"], 4)
                       for r in live],
    "frozen_knn_delta": [round(r["trained_knn"] - r["untrained_knn"], 4)
                         for r in frozen],
}))
