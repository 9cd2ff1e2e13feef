"""ISSUE 31, the benchmark's side: the looped configuration's files, its plain
reference against the program at a small size on the CPU (`ouro_tiny`: hidden
64, 2 layers, 4 heads of 16, MLP width 160, 3 passes, vocabulary 512), the
command end to end on a tiny looped cell with the five faults planted under the
timed path, and the new readers."""

import json
import os
import sys
import unittest.mock as mock
from types import SimpleNamespace

import numpy as np
import pytest

import pb_helpers
from pb_helpers import CONTRACT_KEYS, ROOT, copy_benchmark, run_cell

sys.path.insert(0, os.path.join(ROOT, "tests"))    # the loop written out: tests/looped_unrolled.py

CELL = "ouro-2.6b-l6.tokens512-v49k"
SDAR_CELL = "sdar-30b-a3b-ep8.tokens512"
TINY_CELL = "ouro-tiny.tiny_tokens_v512"
NEW_METRICS = ("looped_step_mfu_pct", "mlp_device_ms", "norm_device_ms", "ut_pass_delta")
ANY_TOKEN_CELL = ("aug_device_ms", "k_fwd_device_ms", "q_fwd_bwd_device_ms", "loss_queue_device_ms",
                  "opt_ema_device_ms", "async_copy_wait_ms", "loop_unspanned_ms_per_step",
                  "h2d_mb_per_step", "compile_s", "compiles_in_window", "model_init_s",
                  "attn_device_ms", "embed_pool_device_ms")
TIGHT = {"loss1": 5e-5, "loss2": 5e-5, "loss3": 5e-5, "grad1": 5e-3, "grad1_med": 5e-4,
         "dq3": 5e-2, "dk3": 5e-2, "keys_max": 1e-4}
PUBLISHED = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
             "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
             "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
             "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
             "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
             "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


@pytest.fixture(scope="module")
def config_file(manifest):
    return manifest.config("ouro-2.6b-l6")


# -- the configuration's file -----------------------------------------------------


def test_the_file_holds_every_published_number_and_cuts_the_depth_alone(config_file, manifest):
    """The catalog row's `config`, key for key; one cut with the published number
    beside it; the trainer's group repeats the cut as it runs it."""
    differs = sorted(k for k, v in PUBLISHED.items() if config_file[k] != v)
    assert differs == config_file["reduced"] == ["num_hidden_layers"]
    assert config_file["published"] == {"num_hidden_layers": 48} and config_file["num_hidden_layers"] == 6
    assert config_file["trainer"]["num_hidden_layers"] == 6 and "vocab_size" not in config_file["trainer"]
    assert set(config_file["reduced_why"]) == {"num_hidden_layers"}
    assert "8 pipeline stages of 6" in config_file["deployment"] and "first stage" in config_file["deployment"]
    assert "larger share" in config_file["deployment"]        # fewer layers: host and set-up weigh more
    listed = next(c for c in manifest.data["configs"] if c["name"] == "ouro-2.6b-l6")
    assert listed["source"] in config_file["source"] and listed["source"].endswith("config.json")
    assert listed["reduced"] == ["num_hidden_layers"] and len(manifest.data["configs"]) == 3
    for key in ("sandwich_norms", "attention", "left_out", "pooling_and_head", "recipe", "optimizer",
                "masking", "weights", "run_length", "remat", "health_stride"):
        assert config_file["assumed"][key]
    t = config_file["trainer"]
    assert (t["arch"], t["seq_len"], t["batch_size"], t["compute_dtype"], t["remat"]) == (
        "ouro_2p6b", 512, 16, "bfloat16", True)
    assert (t["num_negatives"], t["temperature"], t["momentum_ema"], t["lr"], t["weight_decay"],
            t["warmup_epochs"], t["health_stride"]) == (65536, 0.2, 0.99, 1e-5, 0.01, 0, 16)


def test_the_programs_table_is_the_files_and_the_references(config_file):
    from moco_tpu.models.ouro import OURO_SIZES
    from perfbench.reference import looped_nets

    z = OURO_SIZES["ouro_2p6b"]
    for name in ("ouro_2p6b", "ouro_tiny"):
        assert {k: OURO_SIZES[name][k] for k in looped_nets.SIZES[name]} == looped_nets.SIZES[name]
    pairs = {"hidden": "hidden_size", "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "width": "intermediate_size", "rope_theta": "rope_theta",
             "eps": "rms_norm_eps", "ut_steps": "total_ut_steps", "vocab": "vocab_size"}
    assert all(z[a] == config_file[b] for a, b in pairs.items())
    assert z["layers"] == PUBLISHED["num_hidden_layers"] and z["block_length"] == 1 and not z["qk_norm"]


def test_the_cell_stands_in_the_manifest_with_its_metrics(manifest):
    cell = manifest.workload(CELL)
    assert cell["chips"] == 1 and "loop is the step" in cell["why"] and len(manifest.data["workloads"]) == 3
    assert (cell["config"], cell["traffic"]) == ("ouro-2.6b-l6", "tokens512-v49k")
    entries = {p["name"]: p for p in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "train_imgs_per_s_per_chip"
        assert manifest.find("layer_metrics", name + ".py")
    assert [p["name"] for p in manifest.data["per_layer"]][-len(NEW_METRICS):] == list(NEW_METRICS)
    assert entries["looped_step_mfu_pct"]["layer"] == "fused step"
    assert {entries[n]["layer"] for n in NEW_METRICS[1:]} == {"token encoder"}
    # what holds for any token cell lists both; what is one encoder's lists its own cell
    for name in ANY_TOKEN_CELL:
        assert entries[name]["workloads"][-2:] == [SDAR_CELL, CELL], name
    for name in ("seq_step_mfu_pct", "moe_router_device_ms", "moe_dispatch_device_ms",
                 "moe_experts_device_ms", "moe_experts_roofline", "expert_load_max_over_mean"):
        assert entries[name]["workloads"] == [SDAR_CELL]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) | set(ANY_TOKEN_CELL) <= reported
    assert not {"step_mfu_pct", "seq_step_mfu_pct", "blur_roofline", "moe_experts_roofline"} & reported
    # the check on the step's five scopes: every fusion of the loop carries an `op_name` but 24,
    # so the share under no scope is measured here (the routed cell's `ragged_dot` keeps it off)
    assert entries["unscoped_device_pct"]["workloads"] == ["r50-v2-f32.synthetic", CELL]
    limits = manifest.load_json("limits", CELL + ".json")
    assert set(limits["limits"]) <= set(limits["set_from"]) and limits["not_compared"] is not None
    for name, where in limits["set_from"].items():
        assert where["lower"] < limits["limits"][name] < where["upper"], name
        assert where["lower_is"] and where["upper_is"]
    # an unchanged state reads 1 on either: the limits lie well under it
    assert max(limits["limits"]["dq3_med"], limits["limits"]["dk3_med"]) <= 0.1


def test_the_parameters_of_the_cell_are_414_million_and_20_bytes_each(config_file):
    from perfbench.reference import looped_nets

    spec = looped_nets.spec(looped_nets.sizes_for(config_file["trainer"]), 128)
    n = sum(int(np.prod(shape)) for _, shape, _, _ in spec)
    assert n == pytest.approx(413.6e6, rel=0.001) and 20 * n == pytest.approx(8.27e9, rel=0.001)
    layer = sum(int(np.prod(s)) for p, s, _, _ in spec if p.startswith("loop/layer_0/"))
    assert layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51388416
    # the momentum encoder holds every leaf, and every leaf trains
    from perfbench import harness

    ref = harness.build_reference(harness.Manifest(ROOT), config_file,
                                  dict(config_file["trainer"], steps_per_epoch=1, seed=0))
    assert len(ref.key_paths()) == len(spec) == 6 * 11 + 1 + 1 + 4
    assert all(ref.trainable(p) for p, *_ in spec)


# -- arithmetic --------------------------------------------------------------------


def test_looped_flops_against_hand_counts(config_file):
    from perfbench import flops_looped, harness

    assert flops_looped.mask_density(512) == pytest.approx(513 / 1024)
    assert flops_looped.mask_density(1) == 1.0
    # a token of a layer application by hand: q/k/v 2048 x 6144, scores + mix over 256.5 positions of
    # 2048, o, the MLP's three products (ISSUE 31: 33.6 + 2.1 + 69.2 = 104.9 MFLOP)
    token = 2 * 2048 * 6144 + 4 * 512 * (513 / 1024) * 2048 + 2 * 2048 * 2048 + 6 * 2048 * 5632
    assert flops_looped.layer_forward(config_file, 512) == pytest.approx(token) == pytest.approx(104.9e6, rel=0.001)
    head = 2 * 2048 * 2048 + 2 * 2048 * 128
    assert flops_looped.view_forward(config_file, 512) == pytest.approx(4 * 6 * 512 * token + head)
    config = SimpleNamespace(batch_size=16, seq_len=512)
    step = flops_looped.step_flops(config, config_file)
    assert step == pytest.approx(82.5e12, rel=0.002)        # ISSUE 31 reckoned 82.5 TFLOP a step
    # a layer is counted once a PASS: twice the passes, twice the stack's work
    twice = dict(config_file, total_ut_steps=8)
    assert flops_looped.view_forward(twice, 512) - head == pytest.approx(
        2 * (flops_looped.view_forward(config_file, 512) - head))
    # the trainer's own count is the benchmark's
    from moco_tpu.telemetry.mfu import train_step_flops

    assert train_step_flops(harness.trainer_config(config_file, "")) == pytest.approx(step, rel=1e-6)


def test_the_looped_readers_names_are_the_programs_and_reduce_by_innermost_name():
    from moco_tpu.telemetry import scopes
    from perfbench import looped_spans, nested_spans

    assert looped_spans.LOOPED == scopes.LOOPED_SCOPES
    assert nested_spans.NESTED == scopes.ENCODER_SCOPES       # the routed encoder's five stay
    base = "jit(fused_step)/jit(train_step)/shard_map/"
    loop = "jvp(OuroEncoder)/while/body/closed_call/"
    events = [
        ("fusion.1", 0, 1000, {"tf_op": base + "q_fwd_bwd/" + loop + "layer_0/attn/attn/q/dot_general"}),
        ("fusion.2", 1000, 500, {"tf_op": base + "q_fwd_bwd/transpose(" + loop[:-1] + ")/checkpoint/layer_1/mlp/mlp/gate/dot_general"}),
        ("fusion.3", 1500, 250, {"tf_op": base + "k_fwd/OuroEncoder/while/body/closed_call/layer_1/norm/norm3/mul"}),
        ("fusion.4", 1750, 125, {"tf_op": base + "k_fwd/OuroEncoder/embed_pool/embed/take"}),
        ("fusion.5", 1875, 60, {"tf_op": base + "loss_queue/dot_general"}),
        ("fusion.6", 1935, 40, {"tf_op": base + "k_fwd/OuroEncoder/while/body/dynamic_update_slice"}),
        ("copy-start.1", 1975, 5, {}),
    ]
    planes = [{"name": "/device:TPU:0", "lines": {"XLA Ops": events}}]
    red = looped_spans.reduce_planes(planes, "tpu")
    assert red == {"scope_ps": {"attn": 1000, "mlp": 500, "norm": 250, "embed_pool": 125}, "device_planes": 1}
    # the accepted reader of `attn` and `embed_pool` sees the same events by the same rule
    old = nested_spans.reduce_planes(planes, "tpu")["scope_ps"]
    assert old == {"attn": 1000, "embed_pool": 125}
    # a routed encoder's trace holds neither new name: the new readers return nothing
    routed = [("fusion.1", 0, 10, {"tf_op": base + "k_fwd/SDAREncoder/layer_0/attn/attn/q/dot_general"})]
    red = looped_spans.reduce_planes([{"name": "/device:TPU:0", "lines": {"XLA Ops": routed}}], "tpu")
    saved = looped_spans.reduction
    looped_spans.reduction = lambda run: red
    try:
        assert looped_spans.scope_ms({"traced_steps": 1}, "mlp") is None
    finally:
        looped_spans.reduction = saved


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(manifest, name, config_file):
    from perfbench import harness

    run = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
           "config": SimpleNamespace(batch_size=16, seq_len=512, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2, "chips": 1, "device_kind": "TPU v5 lite"}
    mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "t_" + name)
    assert mod.read(run) is None
    # and on another configuration's run (a parent that lacks the loop): nothing, and no raise
    other = dict(run, config_file=manifest.config("sdar-30b-a3b-ep8"),
                 trace={"programs": {"jit_fused_step(1)": [0.3, 0.3]}, "ops": {}})
    assert mod.read(other) is None


def test_the_mfu_and_counter_readers_on_a_made_up_run(manifest, config_file):
    """82.5 TFLOP in 0.8 s is 52.3 % of 197 TFLOP/s."""
    from perfbench import harness, looped_spans

    records = [{"step": 16, "health": {"ut_pass_delta": 0.25, "ut_passes": 4.0}},
               {"step": 32, "health": {"ut_pass_delta": 0.35, "ut_passes": 4.0}}]
    run = {"records": records, "window_records": records, "chips": 1, "device_kind": "TPU v5 lite",
           "trace": {"programs": {"jit_fused_step(123)": [0.8, 0.8]}, "ops": {}},
           "config": SimpleNamespace(batch_size=16, seq_len=512, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2}

    def read(name):
        return harness.load_module(manifest.find("layer_metrics", name + ".py"), "m_" + name).read(run)

    assert read("looped_step_mfu_pct") == pytest.approx(100 * 82.5e12 / 0.8 / 197e12, rel=0.002)
    assert read("ut_pass_delta") == pytest.approx(0.30)
    saved = looped_spans.reduction
    looped_spans.reduction = lambda run: {"scope_ps": {"mlp": 2 * 300e9, "attn": 2 * 100e9}, "device_planes": 1}
    try:
        assert read("mlp_device_ms") == pytest.approx(300.0) and read("norm_device_ms") == 0.0
    finally:
        looped_spans.reduction = saved


def test_the_token_mix_draws_from_the_whole_vocabulary_but_the_mask_id(manifest):
    real = manifest.load_json("traffic", "tokens512-v49k.json")
    assert (real["generator"], real["distinct"], real["entries"], real["length"], real["vocab"],
            real["zipf"], real["data_seed"]) == ("memory_tokens", 8192, 524288, 1024, 49152, 1.0, 20261003)
    from perfbench import harness

    mix = dict(real, distinct=8, entries=64)
    ds = harness.build_traffic(manifest, mix, SimpleNamespace(batch_size=4, vocab_size=0, seq_len=512), seed=5)
    rows, _, lengths = ds.get_batch(np.arange(8))
    assert rows.shape == (8, 1024) and rows.max() < 49151 and (lengths == 1024).all()
    with pytest.raises(ValueError):      # a configuration that holds a slice cannot take this mix
        harness.build_traffic(manifest, mix, SimpleNamespace(batch_size=4, vocab_size=18992, seq_len=512), seed=1)


# -- the reference against the program, from the same token rows --------------------


def _three_steps(compute_dtype="float32", precision=None, rows=None, seed=3):
    """Numbers of `compare` for the program (or, with `precision` / `rows`, the
    reference so built) against the float32 reference over three steps."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.data import build_token_views_sharded, token_view_config_for
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_encoder, build_fused_step, build_optimizer, build_train_step
    from perfbench import harness

    manifest = harness.Manifest(ROOT)
    with open(os.path.join(ROOT, "tests/perfbench/extra/configs/ouro-tiny.json")) as f:
        config_file = json.load(f)
    config = harness.trainer_config(config_file, "").replace(compute_dtype=compute_dtype)
    cfg = harness.reference_cfg(config_file, config, 64)
    ref = harness.build_reference(manifest, config_file, cfg)
    qshape = (config.num_negatives, config.embed_dim)
    rng = np.random.default_rng(seed)
    inputs = [(rng.integers(0, 511, (config.batch_size, 48)).astype(np.int32),
               np.full((config.batch_size, 1), 48, np.int32)) for _ in range(3)]
    ref_out, weights = harness.run_reference(ref, seed, inputs, qshape)
    hyper = {"weight_decay": config.weight_decay, "trainable": ref.trainable}
    if precision or rows:
        other = harness.build_reference(manifest, config_file, cfg, precision or "float32", rows)
        out, _ = harness.run_reference(other, seed, inputs, qshape)
        return {k: v[0] for k, v in harness.compare(out, ref_out, weights, hyper).items()}, ref_out, out

    mesh = create_mesh(devices=jax.devices()[:1])
    model = build_encoder(config)
    tx, sched = build_optimizer(config, 64)
    state = create_train_state(jax.random.key(0), model, tx, (config.batch_size, config.seq_len),
                               config.num_negatives, config.embed_dim, input_dtype=jnp.int32)
    w, queue = harness.make_weights(ref.spec, seed, qshape)
    state = state.replace(params_q=harness.nest(w), params_k=jax.tree.map(jnp.copy, harness.nest(w)),
                          queue=queue)
    fused = build_fused_step(build_train_step(config, model, tx, mesh, 64, sched),
                             build_token_views_sharded(token_view_config_for(config), mesh),
                             jax.random.key(config.seed + 1))
    losses, moment1, delta1 = [], None, None
    for i, (rows_i, lengths) in enumerate(inputs):
        state, metrics = fused(state, jnp.asarray(rows_i), jnp.asarray(lengths), i)
        losses.append(float(metrics["loss"]))
        if i == 0:
            moment1 = jax.device_get(harness.optimizer_moment(state.opt_state, "mu"))
            delta1 = (float(metrics["h_ut_pass_delta"]), float(metrics["h_ut_passes"]))
    prog = {"losses": losses, "grad1": None, "moment_name": "mu", "moment1": moment1,
            "q3": jax.device_get(harness.flatten(state.params_q)),
            "k3": jax.device_get(harness.flatten(state.params_k)),
            "keys3": jax.device_get(state.queue[: 3 * config.batch_size])}
    numbers = {k: v[0] for k, v in harness.compare(prog, ref_out, weights, hyper).items()}
    return numbers, ref_out, {"delta": delta1, "want": float(ref.pass_delta(weights, *inputs[0]))}


def test_program_in_float32_agrees_with_the_reference_to_rounding():
    numbers, ref_out, extra = _three_steps("float32")
    for k, limit in TIGHT.items():
        assert numbers[k] <= limit, (k, numbers[k])
    assert all(np.isfinite(ref_out["losses"]))
    # the program's counter is the reference's last pass: how far it moved the state
    assert extra["delta"][1] == 3.0 and extra["delta"][0] == pytest.approx(extra["want"], rel=1e-4)


@pytest.mark.parametrize("fault", ["float8", "fault_pass_short", "fault_norm_once",
                                   "fault_last_pass_grad", "half"])
def test_the_control_and_every_planted_fault_fail_what_float32_passes(fault):
    numbers, _, _ = (_three_steps(rows=4) if fault == "half" else _three_steps(precision=fault))
    over = {k for k, limit in TIGHT.items() if not numbers.get(k, 0) <= limit}
    assert over & {"grad1", "grad1_med", "keys_max", "loss2", "loss3"}, numbers
    if fault == "fault_last_pass_grad":     # the forward pass is whole: the keys and the first loss are right
        assert numbers["loss1"] <= TIGHT["loss1"] and "grad1_med" in over


def test_the_reference_imports_nothing_of_the_program_and_loops_in_python():
    for name in ("moco_looped.py", "looped_nets.py"):
        with open(os.path.join(ROOT, "perfbench", "reference", name)) as f:
            text = f.read()
        assert "import moco_tpu" not in text and "from moco_tpu" not in text
    with open(os.path.join(ROOT, "perfbench", "reference", "looped_nets.py")) as f:
        nets = f.read()
    assert "lax.scan" not in nets and "checkpoint(" not in nets and "pallas" not in nets


# -- the command end to end on a tiny looped cell --------------------------------------


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """The benchmark with a tiny looped cell: its configuration, traffic and limits
    are files of the tree; the entries are added to a copy of the tiny manifest."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("looped_cell")))
    path = os.path.join(root, "tests", "perfbench", "extra", "tiny_manifest.json")
    with open(path) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        top = json.load(f)
    m["configs"].append({"name": "ouro-tiny", "source": "test", "reduced": [], "why": "test",
                         "file": "tests/perfbench/extra/configs/ouro-tiny.json"})
    m["workloads"].append({"name": TINY_CELL, "config": "ouro-tiny", "traffic": "tiny_tokens_v512",
                           "chips": 1, "why": "test"})
    m["per_layer"] += [dict(p, workloads=[TINY_CELL]) for p in top["per_layer"]
                       if p["name"] in NEW_METRICS + ("attn_device_ms", "embed_pool_device_ms")]
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def test_tiny_looped_cell_runs_traced_and_reports_its_counters(extended):
    rc, result, _ = run_cell(TINY_CELL, trace=1, root=extended, seconds=2, seed=2 ** 31 + 31)
    assert rc == 0 and list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True and result["attempted"] >= 2 and result["failed"] == 0
    for k, limit in TIGHT.items():
        assert result["compared"][k]["value"] <= limit, k
    metrics = result["metrics"]
    assert 0.0 < metrics["ut_pass_delta"]["value"] < 10.0
    # a CPU trace carries no scope and no program line: nothing under a device metric's name
    assert not {"looped_step_mfu_pct", "mlp_device_ms", "norm_device_ms", "attn_device_ms",
                "seq_step_mfu_pct", "step_mfu_pct", "fused_step_device_ms"} & set(metrics)
    assert {"pre_step_s", "host_ms_per_step", "step_ms_p90", "data_wait_pct"} <= set(metrics)
    events = os.path.join(extended, "perfbench", "_work", "run-" + TINY_CELL, "telemetry", "events.jsonl")
    with open(events) as f:
        records = [json.loads(line) for line in f]
    sampled = [r["health"] for r in records if r.get("kind") == "step" and "ut_passes" in r.get("health", {})]
    assert sampled and all(h["ut_passes"] == 3.0 and h["ut_pass_delta"] > 0 for h in sampled)
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"]["path"] == "einsum" and setup["attn"]["qk_prep"] == "xla"


@pytest.mark.parametrize("fault", ["pass_short", "norm_once", "last_pass_grad", "half_batch",
                                   "key_unchanged"])
def test_each_of_the_five_faults_under_the_timed_path_is_not_correct(extended, fault):
    """Three passes for four (here two for three), the closing norm after the
    last pass only, the gradient of the last pass alone: the trainer builds the
    loop written out with the fault in it (`tests/looped_unrolled.py`). Half of
    each batch and the momentum update left out: the step program wrapped."""
    import looped_unrolled
    from moco_tpu.models import ouro

    if fault in ("half_batch", "key_unchanged"):
        from perfbench import fault_run

        wrap = fault_run.key_unchanged if fault == "key_unchanged" else pb_helpers.half_batch
        rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12, wrap_step=wrap)
    else:
        with mock.patch.object(ouro, "looped", looped_unrolled.unrolled(fault)):
            rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12)
    assert rc == 0 and result["correct"] is False
    over = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    want = {"pass_short": {"keys_max", "keys_med"}, "norm_once": {"keys_max", "keys_med"},
            "last_pass_grad": {"grad1_med"}, "half_batch": {"keys_max", "keys_med", "grad1"},
            "key_unchanged": {"dk3_med"}}[fault]
    assert over & want, (over, {k: c["value"] for k, c in result["compared"].items()})
    if fault == "key_unchanged":     # and nothing else sees it
        assert result["compared"]["dk3_med"]["value"] > 0.9 and not over - {"dk3", "dk3_med"}
    if fault == "last_pass_grad":    # the forward pass is whole: the first loss and the keys of step 1 are right
        assert result["compared"]["loss1"]["value"] <= result["compared"]["loss1"]["limit"]


def test_the_loop_written_out_without_a_fault_is_correct(extended):
    """The planting itself changes nothing: unrolled and whole, the cell reads true."""
    import looped_unrolled
    from moco_tpu.models import ouro

    with mock.patch.object(ouro, "looped", looped_unrolled.unrolled()):
        rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12)
    assert rc == 0 and result["correct"] is True


def test_the_upper_readings_come_from_the_reference_alone(extended, tmp_path, capsys):
    """`calibrate_reference.py` on the looped cell: the control and the planted
    faults against the float32 reference from the cell's own traffic."""
    from perfbench import calibrate_reference

    rc = calibrate_reference.main(
        ["--workload", TINY_CELL, "--seeds", "2", "--first-seed", "40", "--variants",
         "float8,half,fault_pass_short,fault_norm_once,fault_last_pass_grad", "--root", extended,
         "--manifest", "tests/perfbench/extra/tiny_manifest.json", "--out", str(tmp_path),
         "--platform", "cpu"])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert [ln["seed"] for ln in lines[:2]] == [40, 41] and lines[-1]["seeds"] == 2
    for ln in lines[:2]:
        assert ln["half"]["keys_max"] == pytest.approx(2 ** 0.5)     # the rows left out
        for name in ("float8", "half", "fault_pass_short", "fault_norm_once", "fault_last_pass_grad"):
            assert any(ln[name][k] > limit for k, limit in TIGHT.items()), (name, ln[name])


def test_fault_run_plants_the_momentum_fault_through_the_command(extended):
    """`perfbench/fault_run.py`, the chip's way to the fault no reference variant
    can stand for, here on the CPU: `correct` false by `dk3_med` alone."""
    import io

    from perfbench import fault_run, run

    out, real = io.StringIO(), run.main
    with mock.patch.object(run, "main", lambda argv, platform="tpu", wrap_step=None: real(
            argv, platform="cpu", wrap_step=wrap_step, out=out)):
        rc = fault_run.main(["--workload", TINY_CELL, "--seed", "13",
                             "--seconds", "1.5", "--root", extended, "--manifest", pb_helpers.TINY])
    result = json.loads([ln for ln in out.getvalue().splitlines() if ln.strip()][-1])
    over = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    assert rc == 0 and result["correct"] is False and over and over <= {"dk3", "dk3_med"}
