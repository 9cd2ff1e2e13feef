"""Upper readings for the limits of `correct` of a cell whose encoder selects its
keys, without the trainer in memory: `calibrate_reference.py`'s loop (for each
seed, three batches of the cell's traffic in the seed's order, the float32
reference from the seed's weights, and each variant of the reference against it)
with one variant of its own.

    python3 perfbench/calibrate_sparse.py --workload <cell> --seeds 2 \
        --variants float8,half,fault_select_all,fault_topk_half,fault_recent,fault_no_relu,selection

`selection` is no reference: it is the program's own query forward of step 1 (its
`compute_dtype`, the seed's weights, no trainer state) beside the float32
reference's, and reads `select_pair_share`: of the (query, key, layer) pairs the
reference selects, the share that the program does not (both select the same
number a query, so it is also the share the program selects otherwise). It cannot
enter `correct` (the harness's list of numbers is fixed). One JSON line per seed
on standard output, and all of them in `chiprun_out/calibrate-sparse-<cell>.json`.
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


def select_pair_share(p: dict, weights: dict, rows, lengths) -> float:
    """The reference has `picked_pairs`, the program sows `sel_choices`: both from
    the same view of the same rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moco_tpu.models.keye import SEL_CHOICES
    from moco_tpu.train_step import build_encoder

    ref = p["reference"]
    want = np.asarray(ref.picked_pairs(weights, rows, lengths))          # [layers, B, L, L] bool
    view, _ = ref._views(jnp.asarray(rows), jnp.asarray(lengths), 0)
    model = build_encoder(p["config"])
    _, taps = jax.jit(lambda w, x: model.apply({"params": w}, x, mutable=[SEL_CHOICES]))(
        harness.nest(weights), view)
    have = np.stack([np.asarray(taps[SEL_CHOICES][f"layer_{i}"]["indexer"]["live"])
                     for i in range(len(want))]).astype(bool)
    return float(np.sum(want & ~have) / np.sum(want))


def main(argv=None, platform="tpu"):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
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
    queue_shape = (config.num_negatives, config.embed_dim)
    asked = [v for v in args.variants.split(",") if v]
    names = [v for v in asked if v != "selection"]
    lines = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        dataset = harness.SeedOrder(p["dataset"]._dataset, seed)
        inputs = []
        for i in range(harness.CHECK_STEPS):
            rows, _, lengths = dataset.get_batch(range(i * config.batch_size, (i + 1) * config.batch_size))
            inputs.append((rows, lengths))
        ref_out, weights = harness.run_reference(p["reference"], seed, inputs, queue_shape)
        line = {"seed": seed, "losses": ref_out["losses"]}
        if "selection" in asked:
            line["select_pair_share"] = select_pair_share(p, weights, *inputs[0])
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
    with open(os.path.join(args.out, f"calibrate-sparse-{p['cell']['name']}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    keys = list(lines[0][names[0]]) if names else []
    print(json.dumps({"summary": {name + "_min": {k: min(ln[name][k] for ln in lines) for k in keys}
                                  for name in names}, "seeds": len(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
