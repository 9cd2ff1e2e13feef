"""On-chip step-time A/B: times the SAME fused MoCo-v2 R50 program as
`bench.py --mode step` under one knob setting per invocation, so the knob
is applied before
any moco_tpu import (fast_bn / augment read MOCO_TPU_DISABLE_PALLAS at
trace time).

    python tools/_perf_ab.py [--disable-pallas] [--batches 128,256]
        [--stats-tile-kib N]   # override pallas_stats tile target

Prints one JSON line per batch size:
    {"ab": "...", "batch": B, "ms_per_step": T, "imgs_per_s": R}

r2's 1780 imgs/s/chip operating point was ~72 ms/step at B=128; first
contact (r5) measured 124 ms/step — this tool bisects whether the Pallas
BN-stats kernels (whose tile budget the r5 VMEM fix cut 2 MB -> 1 MB for
BOTH kernels, though only grad_sums needed it) account for the difference.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

p = argparse.ArgumentParser()
p.add_argument("--disable-pallas", action="store_true")
p.add_argument("--pallas-bn", action="store_true",
               help="opt the fast_bn stats kernels back IN (default OFF "
                    "since the r5 A/B: ~52 ms/step launch overhead)")
p.add_argument("--disable-pallas-blur", action="store_true",
               help="disable only the aug blur stencil kernel")
p.add_argument("--batches", default="128,256")
p.add_argument("--preset", default="imagenet-moco-v2",
               help="any pretrain preset; v3 presets time the queue-free "
                    "step with the asymmetric aug pair")
p.add_argument("--remat", choices=("true", "false"), default=None,
               help="force per-block rematerialization on/off (the train "
                    "driver's bool convention); default = the preset's own "
                    "value — NOTE imagenet-moco-v3-vitb defaults remat=TRUE, "
                    "so a no-remat ViT-B baseline needs --remat false "
                    "(review, r5)")
p.add_argument("--stats-tile-kib", type=int, default=0,
               help="override pallas_stats per-operand tile target (KiB)")
p.add_argument("--label", default="")
args = p.parse_args()

if args.stats_tile_kib and not (args.pallas_bn or args.disable_pallas):
    # the tile knob tunes the BN-stats kernels, which default OFF since
    # the r5 A/B — without the opt-in the sweep would time a program with
    # zero pallas_stats calls under a 'tileNk' label (review, r5)
    args.pallas_bn = True
if args.disable_pallas:
    os.environ["MOCO_TPU_DISABLE_PALLAS"] = "1"
if args.pallas_bn:
    os.environ["MOCO_TPU_PALLAS_BN"] = "1"
if args.disable_pallas_blur:
    os.environ["MOCO_TPU_DISABLE_PALLAS_BLUR"] = "1"
if args.stats_tile_kib:
    os.environ["MOCO_TPU_STATS_TILE_KIB"] = str(args.stats_tile_kib)

from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

import jax

from moco_tpu.config import get_preset
from moco_tpu.parallel.mesh import create_mesh
from moco_tpu.utils.benchkit import build_v2_fused_bench, time_fused_step

# labels COMPOSE: every active knob appears, so a combined invocation
# (e.g. --pallas-bn --stats-tile-kib 512) cannot log ambiguously
# (review, r5)
parts = []
if args.disable_pallas:
    parts.append("no_pallas")
if args.pallas_bn:
    parts.append("pallas_bn_on")
if args.disable_pallas_blur:
    parts.append("no_pallas_blur")
if args.stats_tile_kib:
    parts.append(f"tile{args.stats_tile_kib}k")
label = args.label or ("+".join(parts) if parts else "default")
# the label must reflect the EFFECTIVE remat: the vitb preset defaults
# remat=True, so a flagless run is NOT a no-remat baseline. Computed ONCE
# from the preset (remat is batch-independent) and appended
# unconditionally when effective — a substring test would let a label
# like "noremat" suppress the marker, the exact mislabel this prevents
# (review, r5)
_effective_remat = (args.remat == "true" if args.remat is not None
                    else get_preset(args.preset).remat)
if _effective_remat:
    label += "+remat"
# echo the EFFECTIVE tile at two reference shapes (R50 layer1/layer4): a
# budget that aliases the default program shows up here instead of being
# reported as a distinct sweep point (review, r5)
from moco_tpu.ops.pallas_stats import _tile_rows

print(json.dumps({"ab": label, "backend": jax.default_backend(),
                  "tile_rows_c64": _tile_rows(128 * 56 * 56, 64),
                  "tile_rows_c2048": _tile_rows(128 * 7 * 7, 2048)}),
      flush=True)

for B in (int(b) for b in args.batches.split(",")):
    mesh = create_mesh(1)
    # IDENTICAL program to bench.py's step mode: the assembly and timing
    # live in moco_tpu.utils.benchkit, shared with bench.py and
    # tools/_tpu_validate.py, so the A/B cannot drift from what the bench
    # publishes (review, r5)
    config = get_preset(args.preset).replace(
        batch_size=B, dataset="synthetic", remat=_effective_remat)
    fused, state, imgs, ext = build_v2_fused_bench(config, mesh)
    best, warm_s, _loss, state = time_fused_step(
        fused, state, imgs, ext, warmup=10, steps=20, rounds=3)
    print(json.dumps({"ab": label, "batch": B,
                      "ms_per_step": round(best * 1e3, 2),
                      "imgs_per_s": round(B / best, 1),
                      "compile_warmup_s": round(warm_s, 1)}), flush=True)
