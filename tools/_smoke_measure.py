import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import json, sys
from moco_tpu.parallel.mesh import force_cpu_devices
force_cpu_devices(8)
from moco_tpu.config import get_preset
from moco_tpu.train import train
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()
res = []
for seed in (0, 1, 2):
    cfg = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=32,
        num_negatives=128, embed_dim=32, lr=0.12, epochs=3, steps_per_epoch=16,
        knn_monitor=True, num_classes=10, ckpt_dir="", tb_dir="",
        print_freq=9999, seed=seed,
    )
    state, metrics = train(cfg)
    res.append(round(metrics["knn_train_top1"], 4))
    print("seed", seed, "knn", metrics["knn_train_top1"], flush=True)
print(json.dumps(res))
