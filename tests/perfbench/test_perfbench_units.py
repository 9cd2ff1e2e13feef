"""What needs no training run: the manifest against the contract's shape, the
ops/bytes and FLOP arithmetic against hand counts, the peaks table, the trace
reduction on a small trace recorded here, the measure that `correct` uses."""

import json
import os
import re
import time

import numpy as np
import pytest

from pb_helpers import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


def test_manifest_has_exactly_the_contracts_keys(manifest):
    m = manifest.data
    assert sorted(m) == sorted(["command", "paths", "run_seconds", "configs", "workloads",
                                "end_to_end", "per_layer"])
    assert m["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for c in m["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
    for w in m["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace") and 0 < e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for x in m[g]]
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_name_in_the_manifest_finds_its_file(manifest):
    for p in manifest.data["per_layer"]:
        assert manifest.find("layer_metrics", p["name"] + ".py"), p["name"]
    for w in manifest.data["workloads"]:
        mix = manifest.load_json("traffic", w["traffic"] + ".json")
        assert manifest.find("generators", mix["generator"] + ".py")
        limits = manifest.load_json("limits", w["name"] + ".json")["limits"]
        assert limits and all(v >= 0 for v in limits.values())   # 0: an exact comparison
        assert manifest.find("reference", manifest.config(w["config"])["reference"] + ".py")
    step_metrics = [p["name"] for p in manifest.data["per_layer"] if "mfu" in p["name"]]
    assert step_metrics, "the whole step's share of the peak stands beside the kernels'"


def test_configuration_files_hold_what_is_run(manifest):
    """Each `trainer` key is a field of the preset's config, differs from the
    preset only where `reduced` says, and no width is among those. Held for every
    file under `configs/`, those whose cells wait on the program's repair too."""
    from moco_tpu.config import get_preset

    listed = {c["name"]: c for c in manifest.data["configs"]}
    files = sorted(os.listdir(os.path.join(ROOT, "perfbench", "configs")))
    assert set(listed) <= {f[: -len(".json")] for f in files}
    for name in files:
        f = manifest.load_json("configs", name)
        preset = get_preset(f["preset"])
        changed = [k for k, v in f["trainer"].items()
                   if getattr(preset, k) != (tuple(v) if isinstance(v, list) else v)]
        assert sorted(changed) == sorted(f["reduced"]), (name, changed)
        if f["name"] in listed:
            assert sorted(changed) == sorted(listed[f["name"]]["reduced"])
        assert not [k for k in changed if k.endswith(("_dim", "_rank")) or k in ("arch", "image_size")]


def test_blur_kernel_work_against_a_hand_count():
    from perfbench.kernels import blur

    assert blur.radius(224) == 11
    w = blur.work(224, 2)
    # 23 taps; rows pass over [3, 224, 246], columns pass over [3, 224, 224]
    assert w["flops"] == 2 * 23 * 3 * 224 * 246 + 2 * 23 * 3 * 224 * 224
    assert w["bytes"] == 3 * 246 * 246 * 2 + 3 * 224 * 224 * 2
    # the event as the chip's trace names it (my chip run, PR 24), and one that is not the kernel
    event = ('%vmap__.46 = bf16[256,3,224,224]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[256,3,246,246]'
             '{3,2,1,0:T(8,128)(2,1)} %pad_maximum_fusion.3, f32[256,1,23]{2,1,0:T(1,128)S(1)} '
             '%bitcast.469), custom_call_target="tpu_custom_call"')
    assert blur.is_call(event, 224) and not blur.is_call(event, 32)
    assert not blur.is_call(event.replace("tpu_custom_call", "ConcatBitcast"), 224)


def test_step_flops_against_hand_counts():
    from types import SimpleNamespace

    from perfbench import flops

    # one ViT block of width 4 on 3 tokens, by hand: qkv, scores + mix, out, mlp
    block = 2 * 3 * 4 * 12 + 2 * 2 * 3 * 3 * 4 + 2 * 3 * 4 * 4 + 2 * 2 * 3 * 4 * 16
    assert flops.vit_forward(4, 1, 3, patch=1) == 2 * 2 * 1 * 1 * 3 * 4 + block
    r50 = SimpleNamespace(arch="resnet50", image_size=224, embed_dim=128, cifar_stem=False,
                          variant="v2", batch_size=256)
    vits = SimpleNamespace(arch="vit_small", image_size=224, embed_dim=256, variant="v3",
                           batch_size=256)
    from perfbench import harness

    model = harness.Manifest(ROOT).load_json("configs", "vits-v3.json")["model"]
    # the published 4.1 GMACs of ResNet-50; ISSUE 24 reckoned 8.38e12 and 18.8e12 a step
    assert flops.resnet_forward("resnet50", 224, 128) == pytest.approx(2 * 4.1e9, rel=0.03)
    assert flops.step_flops(r50) == pytest.approx(8.38e12, rel=0.02)
    assert flops.step_flops(vits, model) == pytest.approx(18.8e12, rel=0.02)
    with pytest.raises(KeyError):     # a transformer's widths come from its file alone
        flops.step_flops(vits)


def test_peaks_table_refuses_an_unknown_kind():
    from perfbench import peaks

    assert peaks.for_kind("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


def test_trace_reduction_on_a_small_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from perfbench import trace_reduce

    @jax.jit
    def fused_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    fused_step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(trace_reduce.DISPATCH):
            y = fused_step(x)
        y.block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    t = trace_reduce.reduce_dir(str(tmp_path), "cpu")
    assert 0 < t["busy_s"] < t["window_s"]
    assert t["window_s"] > 0.04            # two sleeps lie inside the window
    assert t["device_ops"] and t["device_ops"][0][1] > 0
    assert any("between step calls" in g[0] for g in t["idle_gaps"])
    assert trace_reduce.durations(t["ops"], "dot") and not trace_reduce.durations(t["ops"], "_blur_kernel")
    assert trace_reduce.merge([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


def test_readers_return_nothing_where_there_is_nothing_to_read(manifest):
    from perfbench import harness

    from types import SimpleNamespace

    run = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
           "config": SimpleNamespace(image_size=224), "config_file": {}, "step_ms": [], "memory_peak_bytes": 0, "traced_steps": 8, "chips": 1, "device_kind": "cpu"}
    for name in ("pre_step_s", "first_two_steps_s", "host_ms_per_step", "step_ms_p90", "data_wait_pct",
                 "staged_batch_ms", "fused_step_device_ms", "step_mfu_pct",
                 "blur_roofline", "hbm_peak_pct"):
        mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "t_" + name)
        assert mod.read(run) is None, name


def test_the_measure_of_correct():
    from perfbench import harness

    ref = {"a": 1.0, "b": 2.0, "c": 0.001}
    # a state left unchanged reads 1 on a leaf at or above the median
    assert harness.worst_gap({"a": 0.0, "b": 0.0, "c": 0.0}, ref, ref)[0] == 1.0
    # a small leaf is measured against the median leaf, not against itself
    gap, leaf = harness.worst_gap({"a": 1.0, "b": 2.0, "c": 0.002}, ref, ref)
    assert gap == pytest.approx(0.001) and leaf == "c"
    assert np.isnan(harness.worst_gap({"a": float("nan"), "b": 2.0, "c": 0.001}, ref, ref)[0])
    w = {"x": np.ones(4), "y": np.ones(4)}
    out = {"losses": [1.0, 1.0, 1.0], "grad1": {"x": np.full(4, 0.5), "y": np.full(4, 1e-9)},
           "q3": {"x": np.full(4, 0.9), "y": np.ones(4)}, "k3": {"x": np.full(4, 0.99)}}
    hyper = {"weight_decay": 0.1, "trainable": lambda p: True}
    same = harness.compare(dict(out), out, w, hyper)
    assert all(v[0] == 0 for v in same.values())
    # SGD: the optimizer's trace after one step is g + wd * p0
    prog = dict(out, grad1=None, moment_name="trace",
                moment1={"x": np.full(4, 0.5 + 0.1), "y": np.full(4, 1e-9 + 0.1)})
    assert harness.compare(prog, out, w, hyper)["grad1"][0] < 1e-6
    # a leaf whose gradient is nought to rounding is left out of the change
    moved = dict(out, q3={"x": np.full(4, 0.9), "y": np.full(4, 5.0)})
    assert harness.compare(moved, out, w, hyper)["dq3"][0] == 0
    halved = dict(out, q3={"x": np.full(4, 0.95), "y": np.ones(4)})
    assert harness.compare(halved, out, w, hyper)["dq3"][0] == pytest.approx(0.5)


def test_traffic_generator_is_found_by_file_and_orders_by_seed(tmp_path):
    """A mix names its generator; the harness finds `generators/<name>.py` under
    the manifest's paths, also one that a later PR adds beside the benchmark's."""
    from types import SimpleNamespace

    from perfbench import harness
    from pb_helpers import copy_benchmark

    root = copy_benchmark(str(tmp_path))
    extra = os.path.join(root, "tests", "perfbench", "extra", "generators")
    os.makedirs(extra)
    with open(os.path.join(extra, "ones.py"), "w") as f:
        f.write("import numpy as np\n\n\nclass Ones:\n    num_classes = 1\n\n"
                "    def __len__(self):\n        return 8\n\n"
                "    def get_batch(self, idx):\n        n = len(idx)\n"
                "        return np.ones((n, 4, 4, 3), np.uint8), np.zeros(n, np.int32), np.tile([4, 4, 0], (n, 1))\n\n\n"
                "def build(params, config, data_dir):\n    return Ones()\n")
    manifest = harness.Manifest(root, "tests/perfbench/extra/tiny_manifest.json")
    cfg = SimpleNamespace(batch_size=4, image_size=16, stage_size=0, num_workers=0)
    assert len(harness.build_traffic(manifest, {"generator": "ones"}, cfg, seed=1)) == 8
    mix = {"generator": "memory_u8", "distinct": 8, "entries": 64, "data_seed": 3}
    ds = harness.build_traffic(manifest, mix, cfg, seed=5)
    assert len(ds) == 64 and ds.num_classes == 1
    raw = ds._dataset
    imgs, labels, extents = raw.get_batch(np.array([0, 8, 9, 63]))
    assert imgs.shape == (4, 16, 16, 3) and imgs.dtype == np.uint8
    assert (extents == [16, 16, 0]).all()
    assert not (imgs[0] == imgs[1]).all() and (np.roll(imgs[0], 1, axis=1) == imgs[1]).all()
    # a seed is an order of the same pictures, and the same seed the same order
    every = np.arange(64)
    a, b = ds.get_batch(every)[0], harness.build_traffic(manifest, mix, cfg, seed=5).get_batch(every)[0]
    c = harness.build_traffic(manifest, mix, cfg, seed=2 ** 31 + 7).get_batch(every)[0]
    assert (a == b).all() and not (a == c).all()
    assert sorted(map(bytes, a)) == sorted(map(bytes, c)) == sorted(map(bytes, raw.get_batch(every)[0]))
    with pytest.raises(SystemExit):
        harness.build_traffic(manifest, {"generator": "nope"}, cfg, seed=1)


def test_json_files_parse():
    for base, _, files in os.walk(os.path.join(ROOT, "perfbench")):
        if "_work" in base:
            continue
        for f in files:
            if f.endswith(".json"):
                with open(os.path.join(base, f), encoding="utf-8") as fh:
                    json.load(fh)
