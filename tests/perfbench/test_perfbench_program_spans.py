"""ISSUE 25 (c), (f): `perfbench/program_spans.py` and the twelve readers that
rest on it, on a small recorded run: the tiny v2 proxy driven by the program's
own `train()` for six steps on one CPU device, steps 3-6 under `jax.profiler`
with the harness's options. A CPU trace names a device event by its
instruction and carries no `op_name`, so the test writes each event's `tf_op`
from the compiled step's text, which is what a TPU trace holds in each event's
metadata."""

import glob
import json
import os
import re
import statistics
import struct
from types import SimpleNamespace

import pytest

from pb_helpers import ROOT

STEPS = 6
TRACED_FROM = 2          # the third call: both step programs are loaded by then
NEW_READERS = ("aug_device_ms", "k_fwd_device_ms", "q_fwd_bwd_device_ms", "loss_queue_device_ms",
               "opt_ema_device_ms", "unscoped_device_pct", "async_copy_wait_ms",
               "loop_unspanned_ms_per_step",
               "h2d_mb_per_step", "compile_s", "compiles_in_window", "model_init_s")


class FakeManifest:
    """`work_dir` as the harness's manifest has it, under a directory of the test's."""

    def __init__(self, base):
        self.base = str(base)

    def work_dir(self, *parts):
        d = os.path.join(self.base, *parts)
        os.makedirs(d, exist_ok=True)
        return d


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax

    from moco_tpu import train as train_mod
    from moco_tpu import train_step as step_mod
    from moco_tpu.config import get_preset
    from moco_tpu.parallel.mesh import create_mesh
    from perfbench import program_spans

    base = tmp_path_factory.mktemp("recorded")
    manifest = FakeManifest(base)
    run_dir = manifest.work_dir("run-tiny")
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, image_size=32, num_negatives=256, batch_size=16,
        dataset="synthetic", epochs=1, steps_per_epoch=STEPS, knn_monitor=False, ckpt_dir="",
        compute_dtype="float32", telemetry_dir=os.path.join(run_dir, "telemetry"),
        telemetry_stride=2, print_freq=3, trace_mode="off")
    trace_dir = os.path.join(run_dir, "trace")
    kept = {"n": 0}
    real_builder = step_mod.build_fused_step

    def builder(step_fn, two_crops_fn, data_key):
        fused = real_builder(step_fn, two_crops_fn, data_key)
        kept["fused"] = fused

        def call(state, imgs, extents, step):
            if kept["n"] == TRACED_FROM:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                options.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            kept["n"] += 1
            kept["batch"] = (imgs, extents)
            return fused(state, imgs, extents, step)
        return call

    step_mod.build_fused_step = builder
    try:
        state, _ = train_mod.train(config, create_mesh(devices=jax.devices()[:1]))
    finally:
        step_mod.build_fused_step = real_builder
        if kept["n"] > TRACED_FROM:
            jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    text = kept["fused"].lower(state, *kept["batch"], jax.device_put(0)).compile().as_text()
    op_names = dict(re.findall(r'%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text))
    with open(os.path.join(run_dir, "telemetry", "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    records = [r for r in events if r.get("kind") == "step"]
    planes = program_spans.read_space(path, program_spans.wanted("cpu"))
    for plane in planes:
        for line in plane["lines"].values():
            line[:] = [(n, s, d, dict(st, tf_op=op_names[st["hlo_op"]])
                        if st.get("hlo_op") in op_names else st) for n, s, d, st in line]
    return SimpleNamespace(
        manifest=manifest, path=path, events=events, records=records,
        red=program_spans.reduce_planes(planes, "cpu"),
        bare=program_spans.reduce_file(path, "cpu"))


def run_dict(recorded, **over):
    window = [r for r in recorded.records if r["step"] > 2]
    run = dict(manifest=recorded.manifest, cell={"name": "tiny"}, records=recorded.records,
               window_records=window, traced_steps=STEPS - TRACED_FROM, chips=1,
               device_kind="cpu", trace={"ops": {}, "programs": {}})
    run.update(over)
    return run


def load_reader(name):
    from perfbench import harness

    return harness.load_module(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".py"),
                               "t25_" + name)


# -- the reduction's pieces ------------------------------------------------------


@pytest.mark.parametrize("op_name,scope,block", [
    ("jit(fused_step)/aug/vmap(body)/mul", "aug", "aug/body"),
    ("jit(fused_step)/jit(train_step)/shard_map/k_fwd/ResNet/layer3_2/bn1/reduce_sum", "k_fwd",
     "k_fwd/layer3"),
    ("jit(fused_step)/jit(train_step)/q_fwd_bwd/transpose(jvp(ResNet))/layer1_0/conv1/conv",
     "q_fwd_bwd", "q_fwd_bwd/layer1 bwd"),
    ("jit(fused_step)/jit(train_step)/q_fwd_bwd/jvp(ResNet)/layer1_0/add", "q_fwd_bwd",
     "q_fwd_bwd/layer1"),
    ("jit(fused_step)/jit(train_step)/q_fwd_bwd/checkpoint/rematted_computation/ResNet/layer2_1/mul",
     "q_fwd_bwd", "q_fwd_bwd/layer2"),
    ("jit(fused_step)/jit(train_step)/shard_map/q_fwd_bwd/transpose(jvp(loss_queue))/dot_general",
     "loss_queue", "loss_queue/- bwd"),
    ("jit(fused_step)/jit(train_step)/shard_map/opt_ema/grad_sync/psum", "opt_ema",
     "opt_ema/grad_sync"),
    ("jit(fused_step)/jit(train_step)/shard_map", None, None),
    ("", None, None),
])
def test_scope_of_takes_the_innermost_recognised_name(op_name, scope, block):
    from perfbench import program_spans

    assert program_spans.scope_of(op_name) == (scope, block)


def test_the_readers_names_are_the_programs():
    """The benchmark keeps its own copy of the names (it imports nothing of the
    program); the two copies agree."""
    from moco_tpu.telemetry import scopes
    from perfbench import program_spans

    assert program_spans.SCOPES == scopes.STEP_SCOPES
    assert program_spans.STEP_EVENT == scopes.STEP_ANNOTATION
    assert set(scopes.LOOP_SPANS) <= set(program_spans.SPANS)


def test_self_times_take_nested_events_out_of_their_parents():
    from perfbench import program_spans

    events = [("while", 0, 100, {}), ("body.1", 10, 30, {}), ("body.2", 50, 40, {}),
              ("after", 100, 20, {}), ("deep", 55, 10, {})]
    got = {name: self_ps for name, self_ps, _ in program_spans.self_times(events)}
    assert got == {"while": 30, "body.1": 30, "body.2": 30, "deep": 10, "after": 20}
    assert sum(got.values()) == 120       # the union of the intervals


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_the_wire_reader_on_a_hand_made_xspace(tmp_path):
    from perfbench import program_spans

    stat_meta = field(5, field(1, 1) + field(2, field(1, 1) + field(2, "tf_op")))
    stat_meta += field(5, field(1, 2) + field(2, field(1, 2) + field(2, "flops")))
    stat_meta += field(5, field(1, 3) + field(2, field(1, 3) + field(2, "jit(f)/aug/mul")))
    meta = field(4, field(1, 7) + field(2, field(1, 7) + field(2, "%fusion.1 = f32[] fusion()")
                                       + field(5, field(1, 1) + field(7, 3))
                                       + field(5, field(1, 2) + field(3, 4096))))
    event = field(1, 7) + field(2, 2500) + field(3, 1500) + field(4, field(1, 2) + field(2, 0.5))
    line = field(1, 3) + field(2, "XLA Ops") + field(3, 9) + field(4, event)
    skipped = field(1, 4) + field(2, "TC Overlay") + field(4, event)
    plane = field(1, 1) + field(2, "/device:TPU:0") + field(3, line) + field(3, skipped) + meta + stat_meta
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(field(1, plane) + field(4, "host"))
    planes = program_spans.read_space(str(path), lambda p, ln: ln == "XLA Ops")
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert planes[0]["line_names"] == ["XLA Ops", "TC Overlay"] and list(planes[0]["lines"]) == ["XLA Ops"]
    (name, start_ps, dur_ps, stats), = planes[0]["lines"]["XLA Ops"]
    assert name == "%fusion.1 = f32[] fusion()" and (start_ps, dur_ps) == (9000 + 2500, 1500)
    # the metadata's statistics under the event's own; a `ref_value` is another entry's name
    assert stats == {"tf_op": "jit(f)/aug/mul", "flops": 0.5}
    red = program_spans.reduce_file(str(path), "tpu")
    assert red["scope_ps"] == {"aug": 1500} and red["total_ps"] == 1500 and red["scoped_events"] == 1
    assert red["loop"] is None and red["gaps"] == {}


def test_async_copies_are_a_bucket_of_their_own_and_the_rest_without_a_scope_is_unscoped():
    """The compiler's `copy-done` / `slice-done` carry no `op_name`: they are not
    laid to any scope, and not counted as unscoped either."""
    from perfbench import program_spans

    scoped = {"tf_op": "jit(fused_step)/jit(train_step)/q_fwd_bwd/jvp(ResNet)/layer1_0/mul"}
    outside = {"tf_op": "jit(fused_step)/jit(train_step)/shard_map/mul"}
    events = [("%copy-start.7 = (f32[8]) copy-start(f32[8] %param.1)", 0, 1, {}),
              ("%copy-done.7 = f32[8] copy-done((f32[8]) %copy-start.7)", 10, 40, {}),
              ("%slice-done.2 = f32[8] slice-done((f32[8]) %slice-start.2)", 60, 5, {}),
              ("%fusion.3 = f32[8] fusion(f32[8] %copy-done.7), kind=kLoop", 70, 10, scoped),
              ("%custom-call.1 = f32[8] custom-call()", 90, 3, {}),
              ("%copy.5 = f32[8] copy(f32[8] %fusion.3)", 95, 2, {}),
              ("%fusion.4 = f32[8] fusion(f32[8] %copy.5), kind=kLoop", 100, 7, outside)]
    planes = [{"name": "/device:TPU:0", "lines": {"XLA Ops": events}, "line_names": ["XLA Ops"]}]
    red = program_spans.reduce_planes(planes, "tpu")
    assert red["scope_ps"] == {"q_fwd_bwd": 10} and red["async_copy_ps"] == 46
    assert red["unscoped_ops_ps"] == {"custom-call.1": 3, "copy.5": 2, "fusion.4": 7}
    assert red["total_ps"] == 68 and program_spans.unscoped_ps(red) == 12


# -- (c) the recorded run ---------------------------------------------------------


def test_five_scopes_and_the_unscoped_rest_are_the_device_time(recorded):
    from perfbench import program_spans

    red = recorded.red
    assert set(red["scope_ps"]) == set(program_spans.SCOPES)
    assert all(v > 0 for v in red["scope_ps"].values())
    unscoped = sum(red["unscoped_ops_ps"].values())
    assert sum(red["scope_ps"].values()) + red["async_copy_ps"] + unscoped == red["total_ps"]
    assert unscoped == program_spans.unscoped_ps(red)
    # against the trace read on its own: per thread, the union of the step program's events
    planes = program_spans.read_space(recorded.path, lambda p, ln: ln.startswith(("tf_XLA",)))
    union = 0
    for p in planes:
        for events in p["lines"].values():
            spans = sorted((s, s + d) for n, s, d, st in events
                           if d > 0 and "fused_step" in str(st.get("hlo_module", "")) and "hlo_op" in st)
            end = None
            for s, e in spans:
                if end is None or s >= end:
                    union += e - s
                    end = e
                elif e > end:
                    union += e - end
                    end = e
    assert red["total_ps"] == pytest.approx(union, rel=1e-9)
    assert unscoped / red["total_ps"] < 0.25      # the compiler's own copies and constants
    assert any(k.endswith(" bwd") for k in red["block_ps"])
    assert any(k.startswith("q_fwd_bwd/layer") for k in red["block_ps"])


def test_without_a_table_a_cpu_trace_carries_no_scope(recorded):
    assert recorded.bare["scoped_events"] == 0 and recorded.bare["scope_ps"] == {}
    assert recorded.bare["total_ps"] == recorded.red["total_ps"]


def test_the_loops_spans_are_on_the_main_thread_inside_the_step(recorded):
    red = recorded.red
    loop = red["loop"]
    assert loop["steps"] == STEPS - TRACED_FROM - 1      # the profiler starts inside step 3's call
    assert [s["step"] for s in red["steps"]] == [4, 5, 6]
    assert {s["thread"] for s in red["steps"]} == {loop["thread"]}
    assert {"data_wait", "dispatch", "telemetry", "sentinel"} <= set(loop["children"])
    assert "fence" in loop["children"] and "loss_readback" in loop["children"]
    for st in red["steps"]:
        names = [n for n, s, e in st["spans"]]
        assert {"data_wait", "dispatch", "telemetry"} <= set(names)
        assert all(st["start"] <= s and e <= st["end"] for n, s, e in st["spans"])
        # the jitted call is the runtime's own event inside `dispatch`
        dispatch = next((s, e) for n, s, e in st["spans"] if n == "dispatch")
        s, e = next((s, e) for n, s, e in st["spans"] if n.startswith("PjitFunction(fused_step)"))
        assert dispatch[0] <= s and e <= dispatch[1]
    assert all(0 <= u <= total for u, total in zip(loop["unspanned_s"], loop["step_s"]))
    staging = [t for t, spans in red["threads"].items()
               if t != loop["thread"] and any(n == "stage_batch" for n, s, e in spans)]
    assert staging, "stage_batch on a staging thread"
    from moco_tpu.telemetry import scopes

    off_main = {n for t, spans in red["threads"].items() if t != loop["thread"] for n, s, e in spans}
    assert {"stage_batch", "gather"} <= off_main & set(scopes.INPUT_SPANS)
    assert not any(n == "stage_batch" for n, s, e in red["threads"][loop["thread"]])


def test_step_records_and_spans_agree(recorded):
    """`data_s` / `host_s` end on the marks made as the last statement inside the
    `data_wait` / `dispatch` spans: record and trace read the same intervals."""
    by_step = {r["step"]: r for r in recorded.records}
    gaps = []
    for st in recorded.red["steps"]:
        spans = {n: (e - s) * 1e-12 for n, s, e in st["spans"]}
        rec = by_step[st["step"]]
        gaps += [abs(rec["data_s"] - spans["data_wait"]), abs(rec["host_s"] - spans["dispatch"])]
        assert rec["host_s"] <= rec["step_s"] and spans["dispatch"] <= rec["step_s"] + 1e-3
    # to the clock's resolution but for a thread switch between a mark and a span's edge
    assert statistics.median(gaps) < 2e-4 and max(gaps) < 2e-2


# -- (f) the readers ---------------------------------------------------------------


@pytest.fixture()
def seeded(recorded):
    """The recorded run's reduction (with the table a TPU trace brings itself)
    where `program_spans.reduction` looks for it."""
    from perfbench import program_spans

    saved = dict(program_spans._CACHE)
    run = run_dict(recorded)
    path = program_spans.trace_file(run)
    assert path == recorded.path
    program_spans._CACHE.clear()
    program_spans._CACHE[(path, os.path.getmtime(path))] = recorded.red
    yield run
    program_spans._CACHE.clear()
    program_spans._CACHE.update(saved)


@pytest.mark.parametrize("name", NEW_READERS)
def test_each_new_reader_reads_the_recorded_run(seeded, recorded, name):
    value = load_reader(name).read(seeded)
    assert value is not None
    red = recorded.red
    steps = STEPS - TRACED_FROM
    if name.endswith("_device_ms"):
        assert value == pytest.approx(red["scope_ps"][name[: -len("_device_ms")]] * 1e-9 / steps)
        assert value > 0
    elif name == "unscoped_device_pct":
        assert 0 <= value < 25
        assert value == pytest.approx(100.0 * sum(red["unscoped_ops_ps"].values()) / red["total_ps"])
    elif name == "async_copy_wait_ms":
        assert value == red["async_copy_ps"] == 0      # the CPU compiler prefetches nothing
    elif name == "loop_unspanned_ms_per_step":
        assert 0 <= value < 50
    elif name == "h2d_mb_per_step":
        assert value == pytest.approx((16 * 32 * 32 * 3 + 16 * 4 + 16 * 3 * 4) / 1e6)
    elif name == "compile_s":
        last = [r for r in recorded.records if r["step"] == 2][0]["compile"]
        assert value == last["fused_step_s"] and last["fused_step_n"] == 2
        assert 0 < value < last["backend_s"] + last["trace_lower_s"]
    elif name == "compiles_in_window":
        assert value == 0
    elif name == "model_init_s":
        setup = [e for e in recorded.events if e.get("event") == "setup"]
        assert value == setup[0]["spans"]["model_init"] > 0


def test_the_scope_readers_sum_to_the_step_programs_device_time(seeded, recorded):
    total = sum(load_reader(s + "_device_ms").read(seeded)
                for s in ("aug", "k_fwd", "q_fwd_bwd", "loss_queue", "opt_ema"))
    total += load_reader("async_copy_wait_ms").read(seeded)
    pct = load_reader("unscoped_device_pct").read(seeded)
    whole = recorded.red["total_ps"] * 1e-9 / (STEPS - TRACED_FROM)
    assert total + whole * pct / 100.0 == pytest.approx(whole)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(name):
    """As the accepted readers do: `None`, never 0, from a run whose program has
    no scopes, spans or counters (a parent commit), and from no run at all."""
    bare = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
            "traced_steps": 2, "chips": 1, "device_kind": "cpu"}
    assert load_reader(name).read(bare) is None
    old_records = [{"step": n, "step_s": 0.1, "data_s": 0.0, "host_s": 0.01, "t": 1.0 + n,
                    "input": {"staged_mb": 18.4 * n, "staged_batch_s_p50": 0.1}} for n in range(1, 9)]
    old = dict(bare, records=old_records, window_records=old_records[4:])
    assert load_reader(name).read(old) is None


@pytest.mark.parametrize("name", [n for n in NEW_READERS
                                  if n.endswith(("_device_ms", "_pct", "_wait_ms"))])
def test_scope_readers_say_nothing_of_a_trace_without_scopes(recorded, name, capsys):
    """A program whose device events carry no scope (the parent's, or a step
    program read from a compile cache filled before the scopes existed)."""
    from perfbench import program_spans

    saved = dict(program_spans._CACHE)
    run = run_dict(recorded)
    program_spans._CACHE.clear()
    program_spans._CACHE[(recorded.path, os.path.getmtime(recorded.path))] = recorded.bare
    try:
        assert load_reader(name).read(run) is None
    finally:
        program_spans._CACHE.clear()
        program_spans._CACHE.update(saved)
    if name.endswith("_device_ms"):
        assert "no device event carries a scope" in capsys.readouterr().err


def test_the_manifest_lists_the_new_readers_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {p["name"]: p for p in manifest["per_layer"]}
    assert [p["name"] for p in manifest["per_layer"]][-len(NEW_READERS):] == list(NEW_READERS)
    layers = {p["layer"] for p in manifest["per_layer"][: -len(NEW_READERS)]}
    for name in NEW_READERS:
        assert entries[name]["workloads"] == ["r50-v2-f32.synthetic"]
        assert entries[name]["layer"] in layers      # a layer the benchmark already names
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", name + ".py"))
