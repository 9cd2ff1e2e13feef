"""CPU-scale learning-dynamics run (config-1 shape at micro scale): evidence
for hardening test_smoke_train thresholds and for choosing the horizon-run
lr. The r2 log (runs/horizon_cpu_r2.log, lr 0.12 cos) oscillated 49-86%
after peaking — lr churn, not convergence (VERDICT r2 weak #3); this r3
variant runs the cooler lr the TPU horizon run uses. Writes stdout; redirect
to runs/horizon_cpu_r3.log.

Usage: python tools/_horizon_cpu.py [lr]
"""
import json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from moco_tpu.parallel.mesh import force_cpu_devices
force_cpu_devices(8)
import jax
from moco_tpu.config import get_preset
from moco_tpu.train import train
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

lr = float(sys.argv[1]) if len(sys.argv) > 1 else 0.03
cfg = get_preset("cifar10-moco-v1").replace(
    arch="resnet_tiny", cifar_stem=True, dataset="synthetic", image_size=16,
    batch_size=64, num_negatives=512, embed_dim=32, lr=lr, cos=True,
    epochs=24, steps_per_epoch=None,  # 2048/64 = 32 steps x 24 epochs = 768
    knn_monitor=True, knn_bank_size=1024, num_classes=10,
    ckpt_dir="", tb_dir="", print_freq=9999, num_workers=1,
)
print(json.dumps({"lr": lr, "config": "cifar10-moco-v1 micro (resnet_tiny 16px K=512)"}))
t0 = time.time()
state, metrics = train(cfg)
print(json.dumps({"final_knn_train_top1": metrics.get("knn_train_top1"),
                  "final_loss": metrics.get("loss"), "lr": lr,
                  "steps": int(state.step), "wall_s": round(time.time()-t0,1)}))
