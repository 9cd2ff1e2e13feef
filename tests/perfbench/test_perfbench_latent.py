"""The benchmark's side of the latent-attention configuration: its files, its
plain reference against the program at a small size on the CPU (hidden 64, a
dense layer and two routed ones, latent attention of 4 heads of 16 + 8 over a
latent of 32, 16 experts of which 4 held, top-3, shared experts, views of 32
tokens), the share test, the command end to end on a tiny cell with the planted
faults under the timed path, and the new readers."""

import json
import os
import unittest.mock as mock
from types import SimpleNamespace

import numpy as np
import pytest

import pb_helpers
from pb_helpers import CONTRACT_KEYS, ROOT, copy_benchmark, run_cell

CELL = "kanana-2-30b-a3b-ep16.tokens4k"
CONFIG = "kanana-2-30b-a3b-ep16"
TINY_CELL = "kanana-tiny.tiny_tokens_l64"
NEW_METRICS = ("mla_attn_roofline", "mla_step_mfu_pct", "mla_latent_device_ms",
               "shared_experts_device_ms")
SHARED_METRICS = ("aug_device_ms", "k_fwd_device_ms", "q_fwd_bwd_device_ms", "loss_queue_device_ms",
                  "opt_ema_device_ms", "async_copy_wait_ms", "h2d_mb_per_step", "compile_s",
                  "compiles_in_window", "model_init_s", "attn_device_ms", "mlp_device_ms",
                  "embed_pool_device_ms", "moe_router_device_ms", "moe_dispatch_device_ms",
                  "moe_experts_device_ms", "expert_load_max_over_mean")
TIGHT = {"loss1": 5e-5, "loss2": 5e-5, "loss3": 5e-5, "grad1": 5e-3, "grad1_med": 5e-4,
         "dq3": 5e-2, "dk3": 5e-2, "keys_max": 1e-4}
# the catalog's row for the model, key for key (model-configs guide)
PUBLISHED = {"attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
             "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
             "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
             "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
             "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
             "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
             "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
             "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


@pytest.fixture(scope="module")
def config_file(manifest):
    return manifest.config(CONFIG)


# -- the configuration's file -----------------------------------------------------


def test_the_file_holds_every_published_number_and_lists_its_cuts(config_file, manifest):
    """The catalog's numbers under their keys, two of them cut; the experts held
    here under the trainer's key beside the published `n_routed_experts`, and
    nothing else at the top level but notes."""
    differs = sorted(k for k, v in PUBLISHED.items() if config_file[k] != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(config_file["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert config_file["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 128256}
    assert {k: config_file[k] for k in config_file["reduced"]} == {
        "num_hidden_layers": 5, "num_experts": 8, "vocab_size": 16032}
    notes = {"name", "source", "preset", "reference", "deployment", "published", "trainer", "reduced",
             "reduced_why", "assumed"}
    assert set(config_file) - set(PUBLISHED) == notes | {"num_experts"}
    trainer = config_file["trainer"]
    assert all(trainer[k] == config_file[k] for k in config_file["reduced"])
    assert config_file["num_experts"] * 16 == config_file["n_routed_experts"] == 128
    assert "num_experts" in config_file["assumed"]["held_experts"]
    assert set(config_file["reduced_why"]) == set(config_file["reduced"]) and "16 v5e" in config_file["deployment"]
    assert (trainer["seq_len"], trainer["batch_size"]) == (4096, 4)
    listed = next(c for c in manifest.data["configs"] if c["name"] == CONFIG)
    assert listed["source"].split(";")[0] in config_file["source"] and len(listed["source"]) <= 200
    assert listed["reduced"] == config_file["reduced"] and len(listed["why"]) <= 200
    for key in ("held_experts", "head_dim", "latent_attention", "column_order", "rope_interleave", "router",
                "correction_bias", "shared_experts", "dense_layer", "left_out", "pooling_and_head",
                "recipe", "masking", "weights", "remat", "health_stride"):
        assert config_file["assumed"][key]


def test_the_programs_table_is_the_files_and_the_references(config_file):
    from moco_tpu.config import get_preset
    from moco_tpu.models.kanana import KANANA_SIZES
    from perfbench.reference import mla_nets

    assert KANANA_SIZES == mla_nets.SIZES
    z = KANANA_SIZES["kanana_2_30b_a3b"]
    pairs = {"hidden": "hidden_size", "heads": "num_attention_heads", "nope": "qk_nope_head_dim",
             "rope": "qk_rope_head_dim", "v_dim": "v_head_dim", "kv_rank": "kv_lora_rank",
             "dense_layers": "first_k_dense_replace", "dense_width": "intermediate_size",
             "top_k": "num_experts_per_tok", "expert_width": "moe_intermediate_size",
             "routed_scale": "routed_scaling_factor", "rope_theta": "rope_theta", "eps": "rms_norm_eps",
             "experts": "n_routed_experts"}
    assert all(z[a] == config_file[b] for a, b in pairs.items())
    assert z["shared_width"] == config_file["n_shared_experts"] * config_file["moe_intermediate_size"]
    assert z["nope"] + z["rope"] == config_file["qk_head_dim"]
    assert {k: z[k] for k in ("layers", "experts", "vocab")} == {
        "layers": 48, "experts": 128, "vocab": 128256}
    preset = get_preset(config_file["preset"])
    assert (preset.arch, preset.seq_len, preset.batch_size) == ("kanana_2_30b_a3b", 4096, 4)


def test_the_cell_stands_in_the_manifest_with_its_metrics(manifest):
    cell = manifest.workload(CELL)      # (later PRs append after it: no count, no place asserted)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "tokens4k"
    assert "4 documents" in cell["why"] and "4 096" in cell["why"] and len(cell["why"]) <= 200
    entries = {p["name"]: p for p in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "train_imgs_per_s_per_chip"
        assert manifest.find("layer_metrics", name + ".py")
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"], name
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) | set(SHARED_METRICS) | {"hbm_peak_pct"} <= reported
    # their counts are other models' (moe_experts_roofline counts every layer as routed)
    assert not {"moe_experts_roofline", "seq_step_mfu_pct", "sparse_step_mfu_pct", "step_mfu_pct",
                "looped_step_mfu_pct", "sparse_attn_roofline"} & reported
    mix = manifest.load_json("traffic", cell["traffic"] + ".json")
    assert (mix["generator"], mix["distinct"], mix["entries"], mix["length"], mix["vocab"]) == (
        "memory_tokens", 1024, 65536, 8192, 16032)
    limits = manifest.load_json("limits", CELL + ".json")
    assert limits["limits"] and set(limits["limits"]) <= set(limits["set_from"])
    for name, reading in limits["set_from"].items():
        assert reading["lower"] < limits["limits"][name] < reading["upper"], name


def test_the_parameters_of_the_cell_are_397_million_and_20_bytes_each(config_file):
    from perfbench.reference import mla_nets

    spec = mla_nets.spec(mla_nets.sizes_for(config_file["trainer"]), 128)
    n = sum(int(np.prod(shape)) for _, shape, _, _ in spec)
    assert n == pytest.approx(396.6e6, rel=0.002) and 20 * n == pytest.approx(7.93e9, rel=0.002)

    def layer(i):
        return sum(int(np.prod(s)) for p, s, _, _ in spec if p.startswith(f"layer_{i}/"))

    assert layer(0) == pytest.approx(64.1e6, rel=0.002) and layer(1) == pytest.approx(73.8e6, rel=0.002)
    attention = sum(int(np.prod(s)) for p, s, _, _ in spec if p.startswith("layer_1/attn/"))
    assert attention == pytest.approx(26.35e6, rel=0.002)


# -- arithmetic ----------------------------------------------------------------------


def test_the_step_and_the_kernels_work_against_hand_counts(config_file):
    from perfbench import flops_mla, harness
    from perfbench.kernels import mla_attn

    config = SimpleNamespace(batch_size=4, seq_len=4096, compute_dtype="bfloat16")
    step = flops_mla.step_flops(config, config_file, 0.375)
    assert step == pytest.approx(42.0e12, rel=0.003)            # 16 384 tokens a pass, 4 passes
    assert flops_mla.step_flops(config, config_file, 0.75) > step
    one = mla_attn.work(4096, 32, 128, 64, 128, 2)
    assert one["fwd"]["flops"] == 2 * (4096 * 4097 / 2) * 32 * 320
    assert one["bwd"]["flops"] == 2.5 * one["fwd"]["flops"]
    assert one["fwd"]["bytes"] == 4096 * 2 * (32 * 192 + 32 * 128 + 64 + 2 * 32 * 128)
    s = mla_attn.step_work(config_file, 4, 4096, 2)
    assert s["flops"] == 4 * 5 * 4.5 * one["fwd"]["flops"]
    # the trainer counts the same step at uniform routing
    from moco_tpu.telemetry.mfu import train_step_flops

    assert train_step_flops(harness.trainer_config(config_file, "")) == pytest.approx(step, rel=1e-6)


def test_the_latent_readers_names_are_the_programs_and_reduce_by_innermost_name():
    from moco_tpu.telemetry import scopes
    from perfbench import latent_spans, nested_spans, looped_spans

    assert latent_spans.LATENT == scopes.LATENT_SCOPES
    assert not set(latent_spans.LATENT) & set(nested_spans.NESTED)
    base = "jit(fused_step)/jit(train_step)/shard_map/"
    layer = "q_fwd_bwd/jvp(SDAREncoder)/checkpoint/layer_1/"
    events = [
        ("fusion.1", 0, 1000, {"tf_op": base + layer + "attn/attn/mla_latent/kv_a/dot_general"}),
        ("fusion.2", 1000, 500, {"tf_op": base + layer + "attn/attn/q/dot_general"}),
        ("custom-call.3", 1500, 300, {"tf_op": base + layer
                                      + "attn/attn/jit(_forward)/mla_attention_fwd/pallas_call"}),
        ("custom-call.4", 1800, 200, {"tf_op": base + "q_fwd_bwd/transpose(jvp(SDAREncoder))/layer_1/"
                                      "attn/attn/jit(_backward)/mla_attention_bwd/pallas_call"}),
        ("fusion.5", 2000, 120, {"tf_op": base + layer + "moe/moe_shared/shared/gate/dot_general"}),
        ("fusion.6", 2120, 80, {"tf_op": base + "k_fwd/SDAREncoder/layer_0/mlp/mlp/up/dot_general"}),
        ("fusion.7", 2200, 5, {"tf_op": base + "loss_queue/dot_general"}),
    ]
    planes = [{"name": "/device:TPU:0", "lines": {"XLA Ops": events}}]
    assert latent_spans.reduce_planes(planes, "tpu") == {
        "scope_ps": {"mla_latent": 1000, "moe_shared": 120},
        "kernel_ps": {"mla_attention_fwd": 300, "mla_attention_bwd": 200}, "device_planes": 1}
    # the accepted readers: `attn` holds the latent and the kernels, `mlp` the dense layer
    assert nested_spans.reduce_planes(planes, "tpu")["scope_ps"] == {"attn": 2000}
    assert looped_spans.reduce_planes(planes, "tpu")["scope_ps"] == {"attn": 2000, "mlp": 80}
    assert latent_spans.reduce_planes([{"name": "/device:TPU:0", "lines": {"XLA Ops": events[-1:]}}],
                                      "tpu")["scope_ps"] == {}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(manifest, name, config_file):
    from perfbench import harness

    run = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
           "config": SimpleNamespace(batch_size=4, seq_len=4096, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2, "chips": 1, "device_kind": "TPU v5 lite"}
    mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "t_" + name)
    assert mod.read(run) is None
    other = dict(run, config_file=manifest.config("keye-vl2-30b-a3b-ep8"))
    assert mod.read(other) is None


def test_the_mfu_and_roofline_readers_on_a_made_up_run(manifest, config_file):
    """42.0 TFLOP in 0.9 s is 23.7 % of 197 TFLOP/s; the latent attention of a
    step is 15.46 TFLOP (78.5 ms at the peak)."""
    from perfbench import harness, latent_spans

    records = [{"step": 16, "health": {"moe_assign_per_token": 0.375}}]
    run = {"records": records, "window_records": records, "chips": 1, "device_kind": "TPU v5 lite",
           "trace": {"programs": {"jit_fused_step(123)": [0.9, 0.9]}, "ops": {}},
           "config": SimpleNamespace(batch_size=4, seq_len=4096, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2}

    def read(name):
        return harness.load_module(manifest.find("layer_metrics", name + ".py"), "m_" + name).read(run)

    assert read("mla_step_mfu_pct") == pytest.approx(100 * 42.0e12 / 0.9 / 197e12, rel=0.005)
    saved = latent_spans.reduction
    latent_spans.reduction = lambda run: {
        "scope_ps": {"mla_latent": 2 * 20e9, "moe_shared": 2 * 15e9}, "device_planes": 1,
        "kernel_ps": {"mla_attention_fwd": 2 * 100e9, "mla_attention_bwd": 2 * 100e9}}
    try:
        assert read("mla_latent_device_ms") == pytest.approx(20.0)
        assert read("shared_experts_device_ms") == pytest.approx(15.0)
        assert read("mla_attn_roofline") == pytest.approx(100 * 15.46e12 / 197e12 / 0.200, rel=0.002)
        assert 0 < read("mla_attn_roofline") < 100
    finally:
        latent_spans.reduction = saved


# -- the reference against the program, from the same token rows --------------------


def _tiny():
    from perfbench import harness

    manifest = harness.Manifest(ROOT)
    with open(os.path.join(ROOT, "tests/perfbench/extra/configs/kanana-tiny.json")) as f:
        config_file = json.load(f)
    return harness, manifest, config_file


def _three_steps(precision=None, rows=None, seed=3):
    """Numbers of `compare` for the float32 program (or, with `precision` / `rows`,
    the reference so built) against the float32 reference over three steps."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.data import build_token_views_sharded, token_view_config_for
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_encoder, build_fused_step, build_optimizer, build_train_step

    harness, manifest, config_file = _tiny()
    config = harness.trainer_config(config_file, "")
    cfg = harness.reference_cfg(config_file, config, 64)
    ref = harness.build_reference(manifest, config_file, cfg)
    qshape = (config.num_negatives, config.embed_dim)
    rng = np.random.default_rng(seed)
    inputs = [(rng.integers(0, 63, (config.batch_size, 64)).astype(np.int32),
               np.full((config.batch_size, 1), 64, np.int32)) for _ in range(3)]
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
    assert {p: v.shape for p, v in harness.flatten(state.params_q).items()} == {
        p: v.shape for p, v in w.items()}                     # the reference's list is the program's
    state = state.replace(params_q=harness.nest(w), params_k=jax.tree.map(jnp.copy, harness.nest(w)),
                          queue=queue)
    fused = build_fused_step(build_train_step(config, model, tx, mesh, 64, sched),
                             build_token_views_sharded(token_view_config_for(config), mesh),
                             jax.random.key(config.seed + 1))
    losses, moment1 = [], None
    for i, (rows_i, lengths) in enumerate(inputs):
        state, metrics = fused(state, jnp.asarray(rows_i), jnp.asarray(lengths), i)
        losses.append(float(metrics["loss"]))
        if i == 0:
            moment1 = jax.device_get(harness.optimizer_moment(state.opt_state, "mu"))
    prog = {"losses": losses, "grad1": None, "moment_name": "mu", "moment1": moment1,
            "q3": jax.device_get(harness.flatten(state.params_q)),
            "k3": jax.device_get(harness.flatten(state.params_k)),
            "keys3": jax.device_get(state.queue[: 3 * config.batch_size])}
    numbers = {k: v[0] for k, v in harness.compare(prog, ref_out, weights, hyper).items()}
    return numbers, ref_out, {"weights": weights, "q3": prog["q3"], "k3": prog["k3"], "ref": ref,
                              "model": model, "inputs": inputs, "config": config}


@pytest.fixture(scope="module")
def float32_program():
    return _three_steps()


def test_program_in_float32_agrees_with_the_reference_to_rounding(float32_program):
    """The first-step loss and gradient, three MoCo steps' parameters and
    enqueued keys: float32 program and float32 reference agree to rounding."""
    numbers, ref_out, _ = float32_program
    for k, limit in TIGHT.items():
        assert numbers[k] <= limit, (k, numbers[k])
    assert all(np.isfinite(ref_out["losses"]))


def test_the_forward_features_are_the_references(float32_program):
    """The pooled features of one view before any step, the program's encoder
    against `mla_nets.forward`, from the seed's weights."""
    import jax
    import jax.numpy as jnp

    from perfbench import harness
    from perfbench.reference import mla_nets

    _, _, extra = float32_program
    ref, weights, model = extra["ref"], extra["weights"], extra["model"]
    ids = jnp.asarray(extra["inputs"][0][0][:, :32])
    got = model.apply({"params": harness.nest({p: jnp.asarray(v) for p, v in weights.items()})}, ids)
    with jax.default_matmul_precision("highest"):
        want = mla_nets.forward(ref.ops, {p: jnp.asarray(v) for p, v in weights.items()}, ids, ref.z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_the_routers_leaves_are_constants_in_both(float32_program):
    _, ref_out, extra = float32_program
    ref, weights = extra["ref"], extra["weights"]
    constant = {p for p in weights if "/router/" in p}
    assert len(constant) == 2 * 2 and constant == {p for p in weights if not ref.trainable(p)}
    for side, q3, k3 in (("program", extra["q3"], extra["k3"]), ("reference", ref_out["q3"], ref_out["k3"])):
        for p, w in weights.items():
            moved = bool(np.abs(np.asarray(q3[p]) - w).max() > 0)
            assert moved == (p not in constant), (side, p)
            assert bool(np.abs(np.asarray(k3[p]) - w).max() > 0) == moved, (side, p)


@pytest.mark.parametrize("fault", ["float8", "fault_rotate_half", "fault_no_kv_norm", "fault_no_shared",
                                   "fault_bias_in_weights", "fault_scale_128", "half"])
def test_the_control_and_every_planted_fault_fail_what_float32_passes(fault):
    numbers, _, _ = (_three_steps(rows=2) if fault == "half" else _three_steps(precision=fault))
    over = {k for k, limit in TIGHT.items() if not numbers.get(k, 0) <= limit}
    assert over & {"grad1", "grad1_med", "keys_max", "loss1", "loss2", "loss3"}, numbers


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """16 experts over 16 chips (one held each) at the tiny size: what every
    share of the program's layer gives, with the shared experts (which every
    chip computes alike) counted once, adds up to the uncut reference layer's
    result; and one share is not the whole."""
    import jax
    import jax.numpy as jnp

    from moco_tpu.models import sdar
    from perfbench.reference import mla_nets

    z = dict(mla_nets.SIZES["kanana_tiny"], held=16)
    u = jax.random.normal(jax.random.key(0), (96, z["hidden"]))
    whole = sdar.Experts(z["experts"], 16, z["top_k"], z["expert_width"], rule="sigmoid",
                         scale=z["routed_scale"], shared=z["shared_width"])
    params = whole.init(jax.random.key(1), u)["params"]
    ref_p = {"l/router/kernel": params["router"]["kernel"],
             "l/router/correction_bias": params["router"]["correction_bias"],
             **{f"l/{k}": params[k] for k in ("gate", "up", "down")},
             **{f"l/shared/{k}/kernel": params["shared"][k]["kernel"] for k in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        want = mla_nets.routed(mla_nets.Ops("float32"), ref_p, "l", u, z)
        shared = mla_nets.swiglu(mla_nets.Ops("float32"), ref_p, "l/shared", u)
        share = sdar.Experts(z["experts"], 1, z["top_k"], z["expert_width"], rule="sigmoid",
                             scale=z["routed_scale"], shared=z["shared_width"])
        parts = []
        for j in range(16):
            perm = np.concatenate([[j], np.setdiff1d(np.arange(16), [j])])
            p = {"router": {"kernel": params["router"]["kernel"][:, perm],
                            "correction_bias": params["router"]["correction_bias"][perm]},
                 **{k: params[k][j:j + 1] for k in ("gate", "up", "down")}, "shared": params["shared"]}
            parts.append(share.apply({"params": p}, u))
    total = sum(parts) - 15 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(parts[0] - shared).max()) > 0
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3


def test_the_reference_imports_nothing_of_the_program():
    for name in ("moco_mla.py", "mla_nets.py"):
        with open(os.path.join(ROOT, "perfbench", "reference", name)) as f:
            text = f.read()
        assert "import moco_tpu" not in text and "from moco_tpu" not in text


# -- the command end to end on a tiny cell --------------------------------------------


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """The benchmark with a tiny latent-attention cell: its configuration, traffic
    and limits are files of the tree; the entries are added to a copy of the tiny
    manifest."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("latent_cell")))
    path = os.path.join(root, "tests", "perfbench", "extra", "tiny_manifest.json")
    with open(path) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        top = json.load(f)
    m["configs"].append({"name": "kanana-tiny", "source": "test", "reduced": [], "why": "test",
                         "file": "tests/perfbench/extra/configs/kanana-tiny.json"})
    m["workloads"].append({"name": TINY_CELL, "config": "kanana-tiny", "traffic": "tiny_tokens_l64",
                           "chips": 1, "why": "test"})
    m["per_layer"] += [dict(p, workloads=[TINY_CELL]) for p in top["per_layer"]
                       if p["name"] in NEW_METRICS + ("expert_load_max_over_mean", "mlp_device_ms")]
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def test_tiny_cell_runs_traced_and_reports_its_counters(extended):
    rc, result, _ = run_cell(TINY_CELL, trace=1, root=extended, seconds=2, seed=2 ** 31 + 39)
    assert rc == 0 and list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True and result["attempted"] >= 2 and result["failed"] == 0
    for k, limit in TIGHT.items():
        assert result["compared"][k]["value"] <= limit, k
    metrics = result["metrics"]
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    # a CPU trace carries no scope and no program line: nothing under a device metric's name
    assert not set(NEW_METRICS) & set(metrics)
    events = os.path.join(extended, "perfbench", "_work", "run-" + TINY_CELL, "telemetry", "events.jsonl")
    with open(events) as f:
        records = [json.loads(line) for line in f]
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"]["path"] == "einsum" and setup["attn"]["v_dim"] == 16
    assert setup["moe"]["dispatch"] == "xla"


def _planted(fault):
    """A fault under the timed path: the program's own modules and functions,
    changed, with its parameter tree as it was."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax import lax

    from moco_tpu.models import kanana, sdar

    f32 = jnp.float32

    class NoLatentNorm(sdar.RMSNorm):
        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), f32)
            x = x.astype(f32)
            if self.name == "kv_a_norm":
                return x
            return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale

    class NoShared(sdar.SwiGLU):
        @nn.compact
        def __call__(self, u):
            def dense(n, name):
                return nn.Dense(n, use_bias=False, dtype=self.dtype, param_dtype=f32, name=name)

            y = dense(u.shape[-1], "down")(jax.nn.silu(dense(self.width, "gate")(u))
                                           * dense(self.width, "up")(u))
            return y * 0.0 if self.name == "shared" else y

    class BiasInWeights(sdar.Router):
        @nn.compact
        def __call__(self, u):
            kernel = lax.stop_gradient(self.param("kernel", nn.initializers.lecun_normal(),
                                                  (u.shape[-1], self.experts), f32))
            bias = self.param("correction_bias", nn.initializers.normal(0.1), (self.experts,), f32)
            logits = jnp.matmul(u.astype(f32), kernel, precision=lax.Precision.HIGHEST)
            # sigmoid of the new logits is s + b: the same choice, the weights from s + b
            s = jnp.clip(jax.nn.sigmoid(logits) + lax.stop_gradient(bias), 1e-6, 1 - 1e-6)
            return jnp.log(s) - jnp.log1p(-s), jnp.zeros_like(bias)

    real = kanana.latent_einsum_attention

    def scale_128(q_nope, q_pe, k_nope, k_pe, v):
        up = ((q_nope.shape[-1] + q_pe.shape[-1]) / q_nope.shape[-1]) ** 0.5
        return real(q_nope * up, q_pe * up, k_nope, k_pe, v)

    return {"rotate_half": (kanana, "interleaved_rotary", sdar.rotary),
            "no_kv_norm": (kanana, "RMSNorm", NoLatentNorm),
            "no_shared": (sdar, "SwiGLU", NoShared),
            "bias_in_weights": (sdar, "Router", BiasInWeights),
            "scale_128": (kanana, "latent_einsum_attention", scale_128)}[fault]


def key_unchanged(real):
    """The momentum update left out: the step hands back the key encoder it got."""
    import jax

    def step(state, rows, lengths, n):
        kept = jax.tree.map(lambda x: x.copy(), state.params_k)
        state, metrics = real(state, rows, lengths, n)
        return state.replace(params_k=kept), metrics
    return step


@pytest.mark.parametrize("fault", ["rotate_half", "no_kv_norm", "no_shared", "bias_in_weights",
                                   "scale_128", "half_batch", "key_unchanged"])
def test_a_broken_timed_path_is_not_correct(extended, fault):
    """The seven faults the cell's limits must catch, each planted under the
    timed path at test size."""
    if fault in ("half_batch", "key_unchanged"):
        wrap = key_unchanged if fault == "key_unchanged" else pb_helpers.half_batch
        rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12, wrap_step=wrap)
    else:
        module, name, planted = _planted(fault)
        with mock.patch.object(module, name, planted):
            rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12)
    assert rc == 0 and result["correct"] is False
    over = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    if fault == "key_unchanged":     # and nothing else sees it
        assert result["compared"]["dk3_med"]["value"] > 0.9 and not over - {"dk3", "dk3_med"}
    else:
        assert over & {"keys_max", "keys_med", "grad1", "grad1_med", "loss1"}, result["compared"]
