"""Readings that the limits of `correct` are set from, many seeds in one process.

    python3 perfbench/calibrate.py --workload <cell> --seeds 12 --controls 3

Set-up is most of a run, so this drives `train.train()` once and starts a run of
three steps again and again inside it: before each, the wrapper puts in fresh
weights from the next seed, zeroes the optimizer's state, the step count and the
queue pointer, and the feed goes on handing new batches. After each it follows
the same three steps with the plain reference (the lower reading: program
against reference), and for the first `--controls` seeds with the reference in
float8 (the control) and with the reference on half of each batch (the planted
fault), both against the float32 reference. `--program-variants bfloat16` then
drives `train.train()` once more with the program's own `compute_dtype` lowered
(the control of a float32 cell: the program's own path in the precision below),
on the same first `--controls` seeds. One JSON line per seed on standard
output, and all of them in `chiprun_out/calibrate-<cell>.json`.
"""

import argparse
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


class CalibrationHook(harness.StepHook):
    def __init__(self, *, first_seed, n_seeds, n_controls, after_each, **kw):
        super().__init__(seed=first_seed, seconds=0.0, trace_dir=None, **kw)
        self.first_seed, self.n_seeds, self.n_controls = first_seed, n_seeds, n_controls
        self.after_each = after_each
        self.pristine = None
        self.calls = 0

    def __call__(self, state, imgs, extents, step):
        import jax
        import numpy as np

        episode, phase = divmod(self.calls, harness.CHECK_STEPS)
        if episode >= self.n_seeds:           # the trainer finishes its in-flight step
            return self._real(state, imgs, extents, step)
        if self.pristine is None:
            self.pristine = jax.device_get(
                (state.opt_state, state.step, state.queue_ptr, state.batch_stats_q,
                 state.batch_stats_k))
        if phase == 0:
            self.seed = self.first_seed + episode
            self.inputs, self.losses = [], []
            self.data_step = int(step)
            if episode > 0:
                opt, st, ptr, bq, bk = jax.device_put(self.pristine)
                state = state.replace(opt_state=opt, step=st, queue_ptr=ptr,
                                      batch_stats_q=bq, batch_stats_k=bk)
            state = self._inject(state)
            self.bn0 = jax.device_get(harness.flatten(state.batch_stats_q))
        self.inputs.append((np.array(imgs, copy=True), np.array(extents, copy=True)))
        out, metrics = self._real(state, imgs, extents, step)
        self.losses.append(metrics["loss"])
        if phase == 0:
            self.keep_after_one(out)
        self.calls += 1
        if phase == harness.CHECK_STEPS - 1:
            self.keep_after_three(out, imgs.shape[0])
            self.after_each(self, episode)
            if episode == self.n_seeds - 1:
                os.kill(os.getpid(), signal.SIGTERM)
        return out, metrics


def aug_check(config, ref, hook, devices):
    """The program's own jitted two-crop augmentation (in the configuration's
    dtype) against the reference's float32 one on the last batch kept: how many
    pixels differ by more than bfloat16 rounding can explain."""
    import jax
    import numpy as np
    from moco_tpu.data import aug_config_for, build_two_crops_sharded
    from moco_tpu.data.augment import with_dtype
    from moco_tpu.parallel.mesh import create_mesh

    from perfbench.reference import augment

    imgs, extents = hook.inputs[-1]
    key = jax.random.key(config.seed + 1)
    step = hook.data_step + len(hook.inputs) - 1
    cfg = with_dtype(aug_config_for(config), config.compute_dtype)
    prog = build_two_crops_sharded(cfg, create_mesh(devices=devices))(
        jax.numpy.asarray(imgs), jax.random.fold_in(key, step), jax.numpy.asarray(extents))
    ours = jax.jit(lambda a, b: augment.two_crops(a, b, key, step, ref.views))(imgs, extents)
    out = []
    for a, b in zip(prog, ours):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(b))
        rows = d.reshape(d.shape[0], -1)
        out.append({"median": float(np.median(d)), "p99": float(np.quantile(d, 0.99)),
                    "share_over_0.1": float((d > 0.1).mean()),
                    "rows_with_a_pixel_over_0.5": int((rows.max(1) > 0.5).sum()),
                    "rows": int(d.shape[0])})
    return out


def main(argv=None, platform="tpu"):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--variants", default="float8,half",
                    help="what the first --controls seeds also read, each against the float32 "
                         "reference: `half` is the reference on half of each batch, any other "
                         "name the reference in that precision (float8 is the control)")
    ap.add_argument("--program-variants", default="",
                    help="`compute_dtype`s to run the program itself in, one more pass of "
                         "`train()` each over the first --controls seeds, against the float32 reference")
    ap.add_argument("--aug-check", action="store_true",
                    help="also hold the program's two-crop augmentation alone against the reference's")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--platform", default=platform, help="tests and rehearsals pass cpu")
    args = ap.parse_args(argv)

    p = harness.prepare(args, args.platform, args.first_seed, "calibrate")
    manifest, config_file, config, cfg = p["manifest"], p["config_file"], p["config"], p["ref_cfg"]
    cell, devices, dataset, ref = p["cell"], p["devices"], p["dataset"], p["reference"]
    labels = {"float8": "control_float8", "half": "fault_half_batch"}
    variants = [(labels.get(v, "reference_" + v),
                 harness.build_reference(manifest, config_file, cfg, rows=config.batch_size // 2)
                 if v == "half" else
                 harness.build_reference(manifest, config_file, cfg, precision=v))
                for v in filter(None, args.variants.split(","))]
    hyper = {"weight_decay": config.weight_decay, "trainable": ref.trainable}
    lines = []

    def numbers(a, b, weights):
        compared = harness.compare(a, b, weights, hyper)
        return {k: v[0] for k, v in compared.items()}, {k: v[1] for k, v in compared.items()}

    def after_each(hook, episode):
        t0 = time.perf_counter()
        prog = hook.captured()
        ref_out, weights = harness.run_reference(ref, hook.seed, hook.inputs, hook.queue_shape,
                                                 hook.data_step)
        lower, leaves = numbers(prog, ref_out, weights)
        line = {"seed": hook.seed, "program": lower, "leaves": leaves,
                "losses": {"program": prog["losses"], "reference": ref_out["losses"]}}
        if episode < hook.n_controls:
            line["bn_layers"] = {"program": harness.bn_var_layers(prog, ref_out, 0.9)}
        if episode < hook.n_controls:
            for name, other in variants:
                out, _ = harness.run_reference(other, hook.seed, hook.inputs, hook.queue_shape,
                                               hook.data_step)
                line[name] = numbers(out, ref_out, weights)[0]
                line["bn_layers"][name] = harness.bn_var_layers(out, ref_out, 0.9)
        line["seconds"] = time.perf_counter() - t0
        lines.append(line)
        print(json.dumps(line), flush=True)

    hook = CalibrationHook(first_seed=args.first_seed, n_seeds=args.seeds, n_controls=args.controls,
                           after_each=after_each, spec=ref.spec, key_paths=ref.key_paths())
    harness.drive(hook, config, devices, dataset)

    def after_lowered(dtype):
        def after(hook, episode):
            ref_out, weights = harness.run_reference(ref, hook.seed, hook.inputs, hook.queue_shape,
                                                     hook.data_step)
            line = next(ln for ln in lines if ln["seed"] == hook.seed)
            line["control_program_" + dtype] = numbers(hook.captured(), ref_out, weights)[0]
            print(json.dumps({"seed": hook.seed, "control_program_" + dtype:
                              line["control_program_" + dtype]}), flush=True)
        return after

    lowered = [v for v in args.program_variants.split(",") if v]
    for dtype in lowered:
        again = CalibrationHook(first_seed=args.first_seed, n_seeds=args.controls, n_controls=0,
                                after_each=after_lowered(dtype), spec=ref.spec,
                                key_paths=ref.key_paths())
        harness.drive(again, config.replace(compute_dtype=dtype), devices, dataset)
    variants += [("control_program_" + dtype, None) for dtype in lowered]
    if args.aug_check:
        lines.append({"aug_check": aug_check(config, ref, hook, devices)})
        print(json.dumps(lines[-1]), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"calibrate-{cell['name']}.json"), "w") as f:
        json.dump(lines, f, indent=1)
    seeds = [ln for ln in lines if "program" in ln]
    keys = list(seeds[0]["program"])
    summary = {"program_max": {k: max(ln["program"][k] for ln in seeds) for k in keys},
               "program_min": {k: min(ln["program"][k] for ln in seeds) for k in keys}}
    for name, _ in variants:
        have = [ln[name] for ln in seeds if name in ln]
        if have:
            summary[name + "_min"] = {k: min(h[k] for h in have) for k in keys}
    print(json.dumps({"summary": summary, "seeds": len(seeds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
