"""The benchmark's harness: one cell, one run.

A cell of `BENCHMARK.json` is `{config, traffic, chips, why}`. Everything else
is found by name under the manifest's `paths`: the configuration's file (given
in the manifest), `traffic/<name>.json` (the parameters of a mix, which name their
generator, `generators/<name>.py`), `layer_metrics/<name>.py` (a reader),
`limits/<cell>.json` (the limits of `correct`), `reference/<name>.py` (the plain
reference the configuration names). Nothing here names a cell, a configuration,
a generator or a metric.

The run drives the program's own `moco_tpu.train.train()`. The only hold the
harness has on it is the step program that the trainer builds
(`train_step.build_fused_step`), which it wraps: the wrapper puts the
benchmark's weights in before step 1, keeps what steps 1-3 took and gave (for
the comparison with the reference), opens and closes the window, takes the
profiler trace, and ends the run through the trainer's own preemption flag.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import queue
import shutil
import signal
import statistics
import sys
import threading
import time

import numpy as np

CHECK_STEPS = 3          # the reference follows the first three steps
WARM_STEPS = 8           # both loads of the step program, then steady steps
TRACE_STEPS = 2          # traced after the window: an R50 step is 150 MiB of trace, 7 GiB of host memory
WORK_DIR = "_work"       # under the benchmark's first path; git-ignored


class BenchError(SystemExit):
    """Ends the run with a non-zero code and no result line."""

    def __init__(self, msg: str, code: int = 2):
        print(f"perfbench: {msg}", file=sys.stderr)
        super().__init__(code)


# -- manifest and files found by name ------------------------------------------


class Manifest:
    def __init__(self, root: str, path: str = "BENCHMARK.json"):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, path), encoding="utf-8") as f:
            self.data = json.load(f)
        self.paths = [os.path.join(self.root, p) for p in self.data["paths"]]

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in the manifest")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"]), encoding="utf-8") as f:
                    return json.load(f)
        raise BenchError(f"no configuration {name!r} in the manifest")

    def find(self, *parts: str) -> str | None:
        """The first file `<path>/<parts...>` under the manifest's paths."""
        for base in self.paths:
            p = os.path.join(base, *parts)
            if os.path.exists(p):
                return p
        return None

    def load_json(self, *parts: str) -> dict:
        p = self.find(*parts)
        if p is None:
            raise BenchError(f"no file {'/'.join(parts)} under {self.data['paths']}")
        with open(p, encoding="utf-8") as f:
            return json.load(f)

    def metrics(self, group: str, cell: str) -> list[dict]:
        """The metrics of `end_to_end` or `per_layer` that this cell reports."""
        return [m for m in self.data[group] if cell in m.get("workloads", [cell])]

    def work_dir(self, *parts: str) -> str:
        d = os.path.join(self.paths[0], WORK_DIR, *parts)
        os.makedirs(d, exist_ok=True)
        return d


def note(what: str) -> None:
    """One line on standard error: where the run stands and the host memory it
    has needed so far (a traced run of a large step can need many GiB)."""
    import resource

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"perfbench: {what}; host peak {peak:.2f} GiB", file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- traffic: a mix is a data file, its generator a file found by name ---------


class SeedOrder:
    """The data set's entries in the order `--seed` draws, under the trainer's
    own epoch permutation: every seed gets the same set of entries in another
    order, so set-up is the same work for every seed. Everything else (decode,
    canvases, counters) is the wrapped data set's own."""

    def __init__(self, dataset, seed: int):
        self._dataset = dataset
        self._order = np.random.default_rng(seed).permutation(len(dataset))
        if hasattr(dataset, "get_batch_into"):   # the staging-canvas protocol, where it has it
            self.get_batch_into = self._get_batch_into

    def __len__(self):
        return len(self._dataset)

    def __getattr__(self, name):
        return getattr(self._dataset, name)

    def get_batch(self, indices):
        return self._dataset.get_batch(self._order[np.asarray(indices)])

    def _get_batch_into(self, indices, out_imgs, out_extents):
        return self._dataset.get_batch_into(self._order[np.asarray(indices)], out_imgs, out_extents)


def find_generator(manifest: Manifest, mix: dict):
    name = mix.get("generator", "")
    path = manifest.find("generators", name + ".py") if name else None
    if path is None:
        raise BenchError(f"no generators/{name}.py under {manifest.data['paths']} for the traffic mix")
    return load_module(path, "perfbench_generator_" + name)


def build_traffic(manifest: Manifest, mix: dict, config, seed: int):
    dataset = find_generator(manifest, mix).build(mix, config, manifest.work_dir("data"))
    if len(dataset) < config.batch_size:
        raise BenchError("the traffic mix has fewer entries than one batch")
    return SeedOrder(dataset, seed)


# -- weights from the seed -----------------------------------------------------


_MAKERS: dict = {}


def make_weights(spec: list, seed: int, queue_shape=None):
    """Every leaf on the device in one jitted call from the seed, float32 (the
    type the trainer keeps its parameters in): normal(0, sqrt(2/fan_in)) for
    kernels, ones / zeros for scales and biases, the queue unit rows."""
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (path, shape, kind, fan_in) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                out[path] = jax.random.normal(k, shape, jnp.float32) * math.sqrt(2.0 / fan_in)
            elif kind == "tiny":
                out[path] = jax.random.normal(k, shape, jnp.float32) * 1e-6
            elif kind in ("ones", "zeros"):
                out[path] = jnp.full(shape, float(kind == "ones"), jnp.float32)
            else:
                raise ValueError(f"unknown init {kind!r} for {path}")
        queue = None
        if queue_shape is not None:
            q = jax.random.normal(jax.random.fold_in(key, len(spec)), queue_shape, jnp.float32)
            queue = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        return out, queue

    cache_key = (tuple(spec), queue_shape)
    if cache_key not in _MAKERS:
        _MAKERS[cache_key] = jax.jit(make)
    return _MAKERS[cache_key](jax.random.key(seed))


def nest(flat: dict, keep=lambda p: True) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        if not keep(path):
            continue
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return tree


def flatten(tree) -> dict:
    """A pytree of dicts -> `path -> leaf`, by dict keys only."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)] = leaf
    return out


def optimizer_moment(opt_state, name: str) -> dict:
    """The `trace` (SGD) or `mu` (Adam) tree inside an optax state, flat."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(k, "name", None) for k in path]
        if name in names:
            tail = path[names.index(name) + 1:]
            out["/".join(str(k.key) for k in tail)] = leaf
    return out


# -- the hold on the trainer's step program ------------------------------------


class StepHook:
    """Wraps the fused step that `train()` builds. One object per run."""

    def __init__(self, *, seed: int, spec: list, key_paths: list, seconds: float,
                 trace_dir: str | None, warm_steps: int = WARM_STEPS, wrap_step=None):
        self.seed, self.spec, self.key_paths = seed, spec, set(key_paths)
        self.seconds, self.trace_dir, self.warm = seconds, trace_dir, warm_steps
        self.wrap_step = wrap_step  # a test breaks the step program underneath by wrapping it
        self.n = 0
        self.t_done: list[float] = []     # when each step's loss was there, by the watcher below
        self._losses: queue.Queue = queue.Queue()
        self._watcher = threading.Thread(target=self._watch, daemon=True)
        self.inputs: list = []
        self.losses: list = []
        self.moment_name = None
        self.moment1 = self.q3 = self.k3 = self.keys3 = self.bn0 = self.bn1 = None
        self.t0 = self.t1 = self.n0 = self.n1 = None
        self.trace_window = None
        self._last_loss = None
        self._real = None

    def builder(self, real_builder):
        def build(step_fn, two_crops_fn, data_key):
            self._real = real_builder(step_fn, two_crops_fn, data_key)
            if self.wrap_step is not None:
                self._real = self.wrap_step(self._real)
            return self
        return build

    def _watch(self):
        """Waits for each step's loss in turn and notes when it came: the step's
        completion on the host's clock, without holding the trainer's loop up."""
        import jax

        while (loss := self._losses.get()) is not None:
            jax.block_until_ready(loss)
            self.t_done.append(time.perf_counter())

    def close(self):
        """Every step's completion is noted and the watcher has ended."""
        if self._watcher.is_alive():
            self._losses.put(None)
            self._watcher.join()

    def _inject(self, state):
        import jax
        import jax.numpy as jnp

        self.queue_shape = None if state.queue is None else tuple(state.queue.shape)
        weights, queue = make_weights(self.spec, self.seed, self.queue_shape)
        have = {p: (tuple(v.shape), str(v.dtype)) for p, v in flatten(state.params_q).items()}
        want = {p: (tuple(v.shape), str(v.dtype)) for p, v in weights.items()}
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))[:6]
            raise BenchError(f"the reference's parameter list is not the program's: {odd}")
        copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        new = state.replace(
            params_q=nest(weights),
            params_k=copy(nest(weights, lambda p: p in self.key_paths)))
        if queue is not None:
            new = new.replace(queue=queue)
        return new

    def __call__(self, state, imgs, extents, step):
        import jax

        n = self.n
        if n == 0:
            state = self._inject(state)
            self._watcher.start()
        if self.trace_dir and self.t1 is not None:
            self._trace_edges(n)
        if self.trace_window and self.trace_window[1] is None:
            with jax.profiler.TraceAnnotation("perfbench_dispatch"):
                return self._step(state, imgs, extents, step, n)
        return self._step(state, imgs, extents, step, n)

    def _step(self, state, imgs, extents, step, n):
        import jax

        if n < CHECK_STEPS:
            self.inputs.append((np.array(imgs, copy=True), np.array(extents, copy=True)))
        if n == 0:
            self.bn0 = jax.device_get(flatten(state.batch_stats_q))
        out, metrics = self._real(state, imgs, extents, step)
        self._losses.put(metrics["loss"])
        if n < CHECK_STEPS:
            self.losses.append(metrics["loss"])
            if n == 0:
                self.keep_after_one(out)
            if n == CHECK_STEPS - 1:
                self.keep_after_three(out, imgs.shape[0])
        self._last_loss = metrics["loss"]
        self.n = n + 1
        if self.n == self.warm:
            jax.block_until_ready(metrics["loss"])
            self.t0, self.n0 = time.perf_counter(), self.n
        elif self.t0 is not None and self.t1 is None and \
                time.perf_counter() - self.t0 >= self.seconds:
            jax.block_until_ready((out, metrics))
            self.t1, self.n1 = time.perf_counter(), self.n
            if not self.trace_dir:  # else the traced steps follow the window, which they would disturb
                self._end_run()
        return out, metrics

    @staticmethod
    def _end_run():
        os.kill(os.getpid(), signal.SIGTERM)   # the trainer's preemption flag

    def keep_after_one(self, out):
        """Host copies of what step 1 left: the optimizer's first moment (the
        first gradient as it was handed over) and the running statistics."""
        import jax

        for name in ("trace", "mu"):
            found = optimizer_moment(out.opt_state, name)
            if found:
                self.moment_name, self.moment1 = name, jax.device_get(found)
        self.bn1 = jax.device_get(flatten(out.batch_stats_q))

    def keep_after_three(self, out, batch: int):
        """Host copies of what step 3 left: both parameter trees, and the rows
        of the queue that the three steps' keys were written to."""
        import jax

        self.q3 = jax.device_get(flatten(out.params_q))
        self.k3 = jax.device_get(flatten(out.params_k))
        self.keys3 = None if out.queue is None else \
            jax.device_get(out.queue[: CHECK_STEPS * batch])

    def captured(self) -> dict:
        return {"losses": [float(x) for x in self.losses], "moment_name": self.moment_name,
                "moment1": self.moment1, "q3": self.q3, "k3": self.k3, "keys3": self.keys3,
                "bn0": self.bn0, "bn1": self.bn1}

    def _trace_edges(self, n: int):
        """At a call's entry, with the device drained: the profiler starts before
        the first step after the window and stops after `TRACE_STEPS` of them."""
        import jax

        if n == self.n1:
            jax.block_until_ready(self._last_loss)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1      # the harness's own annotation and little else
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.trace_window = [time.perf_counter(), None, n, None]
        elif n == self.n1 + TRACE_STEPS:
            self.stop_trace_if_open()
            self._end_run()

    def stop_trace_if_open(self):
        import jax

        if self.trace_window and self.trace_window[1] is None:
            jax.block_until_ready(self._last_loss)
            self.trace_window[1], self.trace_window[3] = time.perf_counter(), self.n
            jax.profiler.stop_trace()
            note("trace stopped")


# -- the comparison that decides `correct` -------------------------------------


def leaf_norms(tree: dict, base: dict | None = None) -> dict:
    out = {}
    for p, v in tree.items():
        v = np.asarray(v, np.float64)
        if base is not None:
            v = v - np.asarray(base[p], np.float64)
        out[p] = float(np.sqrt(np.sum(v * v)))
    return out


def leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    """For each leaf the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    leaves = list(leaves)
    if not leaves:
        return {}
    median = statistics.median(ref[p] for p in leaves)
    return {p: abs(prog[p] - ref[p]) / max(ref[p], median, 1e-30) for p in leaves}


def worst_gap(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    """The widest of `leaf_gaps`, and its leaf."""
    worst, where = 0.0, ""
    for p, gap in leaf_gaps(prog, ref, leaves).items():
        if math.isnan(gap):        # nothing is worse, and no limit admits it
            return gap, p
        if gap > worst:
            worst, where = gap, p
    return worst, where


def median_gap(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    """The median leaf's gap: steady where the worst leaf's is the noise of one
    early layer (PERF.md, section 2)."""
    gaps = list(leaf_gaps(prog, ref, leaves).values())
    if not gaps:
        return 0.0, ""
    return (float("nan") if any(math.isnan(g) for g in gaps) else statistics.median(gaps)), ""


def compare(prog: dict, ref: dict, weights: dict, hyper: dict) -> dict:
    """`prog` / `ref`: losses, first gradient (or the optimizer's moment after
    step 1), and `q3`, `k3` after step 3. Returns `name -> (number, leaf)`."""
    numbers = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        numbers[f"loss{i}"] = (abs(a - b) / max(abs(b), 1e-30), "")
    g_ref = leaf_norms(ref["grad1"])
    moved = [p for p in g_ref if hyper["trainable"](p)]
    if prog.get("grad1") is not None:
        g_prog = prog["grad1"]
    elif prog["moment_name"] == "trace":   # SGD: trace_1 = g + wd * p0
        g_prog = {p: np.asarray(prog["moment1"][p], np.float64)
                  - hyper["weight_decay"] * np.asarray(weights[p], np.float64) for p in moved}
    else:                                  # Adam: mu_1 = (1 - b1) * g
        g_prog = {p: np.asarray(prog["moment1"][p], np.float64) / (1 - 0.9) for p in moved}
    g_prog = leaf_norms(g_prog)
    numbers["grad1"] = worst_gap(g_prog, g_ref, moved)
    numbers["grad1_med"] = median_gap(g_prog, g_ref, moved)
    # leaves whose gradient is nought to rounding move by round-off alone
    floor = 1e-3 * statistics.median(g_ref[p] for p in moved)
    steady = [p for p in moved if g_ref[p] >= floor]
    dq_prog, dq_ref = leaf_norms(prog["q3"], weights), leaf_norms(ref["q3"], weights)
    numbers["dq3"] = worst_gap(dq_prog, dq_ref, steady)
    numbers["dq3_med"] = median_gap(dq_prog, dq_ref, steady)
    in_k = [p for p in steady if p in ref["k3"]]
    dk_prog, dk_ref = leaf_norms(prog["k3"], weights), leaf_norms(ref["k3"], weights)
    numbers["dk3"] = worst_gap(dk_prog, dk_ref, in_k)
    numbers["dk3_med"] = median_gap(dk_prog, dk_ref, in_k)
    if prog.get("keys3") is not None and ref.get("keys3") is not None:
        # the keys the three steps enqueued (unit rows): the key encoder's forward
        # pass, row by row; rows that are missing or misplaced read about sqrt(2)
        a, b = np.asarray(prog["keys3"], np.float64), np.asarray(ref["keys3"], np.float64)
        rows = min(len(a), len(b))
        d = np.sqrt(np.sum(np.square(a[:rows] - b[:rows]), -1))
        d = np.concatenate([d, np.full(max(len(a), len(b)) - rows, math.sqrt(2.0))])
        numbers["keys_max"] = (float(np.max(d)), f"row {int(np.argmax(d))}")
        numbers["keys_med"] = (float(np.median(d)), "")
    numbers.update(bn_var_gaps(prog, ref, hyper.get("bn_momentum", 0.9)))
    return numbers


def bn_var_layers(prog: dict, ref: dict, momentum: float) -> dict:
    """Step 1's batch variance at every BatchNorm of the query encoder, layer by
    layer: the distance between the program's vector and the reference's against
    the reference's norm. The program's is read back from its running variance
    before and after the step (`running = m * running + (1 - m) * batch`). A sum
    over the whole batch at every depth of the forward pass: rounding noise
    averages out of it, a coarser rounding of the weights does not."""
    want = ref.get("bn_var") or {}
    if prog.get("bn_var") is not None:
        have = prog["bn_var"]
    elif prog.get("bn1"):
        have = {p[: -len("/var")]: (np.asarray(v, np.float64) - momentum * np.asarray(
            prog["bn0"][p], np.float64)) / (1.0 - momentum)
            for p, v in prog["bn1"].items() if p.endswith("/var")}
    else:
        have = {}
    if not want or set(want) - set(have):
        return {}
    gaps = {}
    for name, v in want.items():
        v = np.asarray(v, np.float64)
        gaps[name] = float(np.linalg.norm(np.asarray(have[name], np.float64) - v)
                           / max(np.linalg.norm(v), 1e-30))
    return gaps


def bn_var_gaps(prog: dict, ref: dict, momentum: float) -> dict:
    gaps = bn_var_layers(prog, ref, momentum)
    if not gaps:
        return {}
    if any(math.isnan(g) for g in gaps.values()):
        return {"bnvar_med": (float("nan"), "")}
    first = next(iter(gaps))
    worst = max(gaps, key=gaps.get)
    return {"bnvar_med": (statistics.median(gaps.values()), ""),
            "bnvar_first": (gaps[first], first), "bnvar_max": (gaps[worst], worst)}


def run_reference(ref, seed: int, inputs: list, queue_shape, data_step: int = 0):
    """Three steps of a reference from the seed's weights; host copies out."""
    import jax

    weights, queue = make_weights(ref.spec, seed, queue_shape)
    state = ref.init_state(weights, queue, data_step)
    losses, grad1, seen1 = [], None, {}
    for i, (imgs, extents) in enumerate(inputs):
        state, loss, grads, seen = ref.step(state, imgs, extents)
        losses.append(float(loss))
        if i == 0:
            grad1, seen1 = jax.device_get((grads, seen))
        del grads, seen
    out = {"losses": losses, "grad1": grad1, "q3": jax.device_get(state["q"]),
           "k3": jax.device_get(state["k"]), "keys3": None, "bn_var": seen1.get("bn_var")}
    if "queue" in state:
        rows = sum(len(imgs) if ref.rows is None else ref.rows for imgs, _ in inputs)
        out["keys3"] = jax.device_get(state["queue"][:rows])
    return out, jax.device_get(weights)


def reference_cfg(config_file: dict, pretrain_config, steps_per_epoch: int) -> dict:
    cfg = dict(config_file["trainer"])
    cfg.update(steps_per_epoch=steps_per_epoch, seed=pretrain_config.seed)
    return cfg


def build_reference(manifest: Manifest, config_file: dict, cfg: dict, precision="float32",
                    rows=None):
    name = config_file["reference"]
    path = manifest.find("reference", name + ".py")
    if path is None:
        raise BenchError(f"no reference/{name}.py under the manifest's paths")
    return load_module(path, f"perfbench_reference_{name}").build(cfg, precision, rows)


# -- one run -------------------------------------------------------------------


def trainer_config(config_file: dict, telemetry_dir: str):
    """The preset the file names, with the file's `trainer` group laid over it:
    the file holds the configuration as it is run, the trainer's own seed too
    (`--seed` makes the weights and orders the inputs: `traffic.SeedOrder`)."""
    import dataclasses

    from moco_tpu.config import get_preset

    preset = get_preset(config_file["preset"])
    fields = {f.name for f in dataclasses.fields(preset)}
    over = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in config_file["trainer"].items() if k in fields}
    return preset.replace(telemetry_dir=telemetry_dir, ckpt_dir="", **over)


def read_events(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def prepare(args, platform: str, seed: int, work: str):
    """What a run and a calibration share: the cell's files, the chips, the
    trainer's configuration, the traffic and the plain reference."""
    manifest = Manifest(args.root, args.manifest)
    cell = manifest.workload(args.workload)
    config_file = manifest.config(cell["config"])
    mix = manifest.load_json("traffic", cell["traffic"] + ".json")

    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        raise BenchError(
            f"cell {cell['name']} needs {cell['chips']} x {platform}; JAX found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})", 3)
    devices = devices[: cell["chips"]]
    print(f"perfbench: {cell['name']} seed {seed} on {len(devices)} x "
          f"{devices[0].device_kind}; host cpus {os.cpu_count()}", file=sys.stderr)

    from moco_tpu.utils.cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    run_dir = manifest.work_dir(work + "-" + cell["name"])
    for sub in ("telemetry", "trace"):
        shutil.rmtree(os.path.join(run_dir, sub), ignore_errors=True)
    config = trainer_config(config_file, os.path.join(run_dir, "telemetry"))
    dataset = build_traffic(manifest, mix, config, seed)
    note("traffic built")
    steps_per_epoch = len(dataset) // config.batch_size
    ref_cfg = reference_cfg(config_file, config, steps_per_epoch)
    return dict(manifest=manifest, cell=cell, config_file=config_file, mix=mix, devices=devices,
                cache_dir=cache_dir, run_dir=run_dir, config=config, dataset=dataset,
                steps_per_epoch=steps_per_epoch, ref_cfg=ref_cfg,
                reference=build_reference(manifest, config_file, ref_cfg))


def drive(hook: StepHook, config, devices, dataset):
    """The trainer's own `train()` with the hook around the step program it builds."""
    from moco_tpu import train as train_mod
    from moco_tpu import train_step as step_mod
    from moco_tpu.parallel.mesh import create_mesh

    real_builder = step_mod.build_fused_step
    step_mod.build_fused_step = hook.builder(real_builder)
    try:
        return train_mod.train(config, create_mesh(devices=devices), dataset=dataset)
    finally:
        step_mod.build_fused_step = real_builder
        hook.stop_trace_if_open()
        hook.close()


def run(args, t_process_start: float, platform: str = "tpu", wrap_step=None,
        out=sys.stdout) -> int:
    p = prepare(args, platform, args.seed, "run")
    manifest, cell, config_file, devices = p["manifest"], p["cell"], p["config_file"], p["devices"]
    config, dataset, reference, run_dir = p["config"], p["dataset"], p["reference"], p["run_dir"]
    steps_per_epoch, cache_dir = p["steps_per_epoch"], p["cache_dir"]

    hook = StepHook(seed=args.seed, spec=reference.spec, key_paths=reference.key_paths(),
                    seconds=args.seconds, wrap_step=wrap_step,
                    trace_dir=os.path.join(run_dir, "trace") if args.trace else None,
                    warm_steps=getattr(args, "warm_steps", WARM_STEPS))
    t_train = time.time()
    final_state = drive(hook, config, devices, dataset)
    note(f"trainer returned after {hook.n} steps")
    if hook.t1 is None:
        raise BenchError("the trainer returned before the window closed "
                         f"(after {hook.n} steps; one epoch has {steps_per_epoch})")
    if args.trace and (hook.trace_window is None or hook.trace_window[3] is None):
        raise BenchError("the trainer returned before the traced steps were done")
    stats = [d.memory_stats() or {} for d in devices]
    print("perfbench: memory_stats " + json.dumps(stats[0]), file=sys.stderr)
    # `peak_bytes_in_use` counts buffers; a running program's temporaries are the
    # runtime's `reserved` bytes, beside the buffers that are alive with them
    memory_peak = max(max(int(s.get("peak_bytes_in_use", 0)),
                          int(s.get("bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
                      for s in stats)
    del final_state
    hook._real = hook._last_loss = None
    gc.collect()

    # -- the window's own numbers (host clock, the harness's) -----------------
    window_s = hook.t1 - hook.t0
    steps = hook.n1 - hook.n0
    # every step of the window, unsmoothed: from one step's loss being there to the next's
    done = [hook.t0] + hook.t_done[hook.n0: hook.n1]
    step_ms = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
    longest = int(np.argmax(step_ms))
    end_to_end = {
        "train_imgs_per_s_per_chip": steps * config.batch_size / window_s / len(devices),
        "setup_s": hook.t0 - t_process_start,
    }

    # -- correct: the first three steps against the plain reference -----------
    t_ref = time.perf_counter()
    prog = hook.captured()
    hook.losses = []
    ref_out, weights = run_reference(reference, args.seed, hook.inputs, hook.queue_shape)
    hyper = {"weight_decay": config.weight_decay, "trainable": reference.trainable}
    numbers = compare(prog, ref_out, weights, hyper)
    check_inputs = getattr(find_generator(manifest, p["mix"]), "check_inputs", None)
    if check_inputs is not None:   # what the feed staged against the generator's own reading of its files
        numbers.update(check_inputs(p["mix"], manifest.work_dir("data"), hook.inputs, args.seed))
    limits = manifest.load_json("limits", cell["name"] + ".json")["limits"]
    compared = {k: {"value": v[0], "limit": limits[k], "leaf": v[1]}
                for k, v in numbers.items() if k in limits}
    correct = bool(compared) and all(c["value"] <= c["limit"] for c in compared.values())
    others = {k: v[0] for k, v in numbers.items() if k not in limits}
    reference_s = time.perf_counter() - t_ref
    note(f"reference followed {CHECK_STEPS} steps in {reference_s:.1f} s")

    # -- per-layer metrics: readers found by name ------------------------------
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": steps, "failed": 0}
    records = [r for r in read_events(os.path.join(run_dir, "telemetry", "events.jsonl"))
               if r.get("kind") == "step"]
    # the window's longest step beside the trainer's own record of it: where a stall lay
    slow = {"ms": step_ms[longest], "median_ms": statistics.median(step_ms), "step": hook.n0 + longest + 1}
    slow["record"] = next(({k: r[k] for k in ("step_s", "data_s", "host_s", "telemetry_s") if k in r}
                           for r in records if r["step"] == slow["step"]), None)
    if args.trace:
        from perfbench import trace_reduce

        trace = trace_reduce.reduce_dir(os.path.join(run_dir, "trace"), devices[0].platform)
        note(f"trace of {trace['file_bytes'] / 2 ** 20:.1f} MiB reduced")
        print("perfbench: trace planes " + json.dumps(trace["planes"]), file=sys.stderr)
        with open(os.path.join(run_dir, "custom_calls.json"), "w", encoding="utf-8") as f:
            json.dump(trace_reduce.custom_calls(trace), f, indent=1)
        info = dict(
            manifest=manifest, cell=cell, config=config, config_file=config_file,
            records=records, t_train_entry=t_train, trace=trace,
            window_records=[r for r in records if hook.n0 < r["step"] <= hook.n1],
            traced_steps=hook.trace_window[3] - hook.trace_window[2],
            window_step_s=window_s / steps, step_ms=step_ms, memory_peak_bytes=memory_peak,
            device_kind=devices[0].device_kind, chips=len(devices))
        metrics = {}
        for m in manifest.metrics("per_layer", cell["name"]):
            path = manifest.find("layer_metrics", m["name"] + ".py")
            if path is None:
                raise BenchError(f"no layer_metrics/{m['name']}.py for the manifest's metric")
            value = load_module(path, "layer_metric_" + m["name"]).read(info)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = {"device_ops": trace["device_ops"][:10], "idle_gaps": trace["idle_gaps"][:10]}
    else:
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]), "unit": m["unit"]}
                   for m in manifest.metrics("end_to_end", cell["name"])}
    result.update(metrics=metrics, device=device)
    if args.trace:
        result["breakdown"] = breakdown
    result["run"] = {"window_s": window_s, "steps": steps, "reference_s": reference_s,
                     "cache_dir": cache_dir, "steps_per_epoch": steps_per_epoch,
                     "end_to_end": end_to_end, "read_not_compared": others, "longest_step": slow}
    result["compared"] = compared
    for k, c in compared.items():
        print(f"perfbench: compared {k} = {c['value']:.6g} (limit {c['limit']:.6g})"
              f"{' at ' + c['leaf'] if c['leaf'] else ''}", file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return 0
