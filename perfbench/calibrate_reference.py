"""Upper readings for the limits of `correct`, without the program in memory.

    python3 perfbench/calibrate_reference.py --workload <cell> --seeds 3 \
        --variants float8,half,fault_causal

`calibrate.py` follows each run of three steps with the reference while the
trainer's state stays on the device; where that state and the float32
reference's do not fit one chip together (a cell whose state is half the chip),
this reads the controls and the planted faults alone: for each seed, three
batches of the cell's traffic in the seed's order, the float32 reference from
the seed's weights, and each variant of the reference against it (`half`: half
of each batch; any other name: `build(cfg, precision=<name>)`, so `float8` is
the control and a reference's own `fault_*` names its planted faults). The
variant `routing` is no reference: it is the program's own query forward of
step 1 (its `compute_dtype`, the seed's weights, no trainer state) beside the
float32 reference's, and reads the share of (token, layer) pairs whose set of
chosen experts differs: `routing_set_share`. The
lower readings are the timed runs' own (`run.py` prints every number of
`compare`, compared or not). One JSON line per seed on standard output, and all
of them in `chiprun_out/calibrate-reference-<cell>.json`.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def routing_set_share(p: dict, weights: dict, rows, lengths) -> float:
    """A routed encoder only (the reference has `chosen_sets`, the program sows
    `moe_choices`): both from the same view of the same rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moco_tpu.models.sdar import MOE_CHOICES
    from moco_tpu.train_step import build_encoder

    ref = p["reference"]
    want = np.sort(np.asarray(ref.chosen_sets(weights, rows, lengths)), -1)   # [layers, tokens, k]
    view, _ = ref._views(jnp.asarray(rows), jnp.asarray(lengths), 0)
    model = build_encoder(p["config"])
    _, taps = jax.jit(lambda w, x: model.apply({"params": w}, x, mutable=[MOE_CHOICES]))(
        harness.nest(weights), view)
    have = np.sort(np.stack([np.asarray(taps[MOE_CHOICES][f"layer_{i}"]["moe"]["chosen"])
                             for i in range(len(want))]), -1)
    return float(np.mean(np.any(have != want, -1)))


def main(argv=None, platform="tpu"):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--variants", default="float8,half")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--platform", default=platform, help="tests and rehearsals pass cpu")
    args = ap.parse_args(argv)

    p = harness.prepare(args, args.platform, args.first_seed, "calibrate")
    manifest, config_file, config, cfg = p["manifest"], p["config_file"], p["config"], p["ref_cfg"]
    hyper = {"weight_decay": config.weight_decay, "trainable": p["reference"].trainable}
    queue_shape = (config.num_negatives, config.embed_dim) if config.variant != "v3" else None
    names = [v for v in args.variants.split(",") if v and v != "routing"]
    lines = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        dataset = harness.SeedOrder(p["dataset"]._dataset, seed)
        inputs = []
        for i in range(harness.CHECK_STEPS):
            imgs, _, extents = dataset.get_batch(range(i * config.batch_size, (i + 1) * config.batch_size))
            inputs.append((imgs, extents))
        ref_out, weights = harness.run_reference(p["reference"], seed, inputs, queue_shape)
        line = {"seed": seed, "losses": ref_out["losses"]}
        if "routing" in args.variants.split(","):
            line["routing_set_share"] = routing_set_share(p, weights, *inputs[0])
        for name in names:
            other = (harness.build_reference(manifest, config_file, cfg, rows=config.batch_size // 2)
                     if name == "half" else
                     harness.build_reference(manifest, config_file, cfg, precision=name))
            out, _ = harness.run_reference(other, seed, inputs, queue_shape)
            line[name] = {k: v[0] for k, v in harness.compare(out, ref_out, weights, hyper).items()}
            del other, out
            gc.collect()
        line["seconds"] = time.perf_counter() - t0
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"calibrate-reference-{p['cell']['name']}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    keys = list(lines[0][names[0]]) if names else []
    print(json.dumps({"summary": {name + "_min": {k: min(ln[name][k] for ln in lines) for k in keys}
                                  for name in names}, "seeds": len(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
