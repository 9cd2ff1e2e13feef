"""ISSUE 27, the benchmark's side: the token configuration's files, its plain
reference against the program at a small size on the CPU (hidden 64, 2 layers,
16 experts of which 4 held, top-4, block length 2), the command end to end on
a tiny token cell with both planted faults, and the new readers."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import pb_helpers
from pb_helpers import CONTRACT_KEYS, ROOT, copy_benchmark, run_cell

CELL = "sdar-30b-a3b-ep8.tokens512"
TINY_CELL = "sdar-tiny.tiny_tokens"
NEW_METRICS = ("seq_step_mfu_pct", "attn_device_ms", "moe_router_device_ms",
               "moe_dispatch_device_ms", "moe_experts_device_ms", "moe_experts_roofline",
               "expert_load_max_over_mean", "embed_pool_device_ms")
TIGHT = {"loss1": 5e-5, "loss2": 5e-5, "loss3": 5e-5, "grad1": 5e-3, "grad1_med": 5e-4,
         "dq3": 5e-2, "dk3": 5e-2, "keys_max": 1e-4}


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


@pytest.fixture(scope="module")
def config_file(manifest):
    return manifest.config("sdar-30b-a3b-ep8")


# -- the configuration's file -----------------------------------------------------


def test_the_file_holds_every_published_width_and_lists_its_cuts(config_file, manifest):
    """The catalog row's `config`, key for key; the three cuts with the published
    numbers beside them; the trainer's group repeats the cuts as it runs them."""
    published = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
                 "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
                 "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
                 "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if config_file[k] != v)
    # the recipe's values (lr, momentum) cut nothing: they are `assumed`, not `reduced`
    assert differs == sorted(config_file["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert config_file["published"] == {k: published[k] for k in differs}
    assert {k: config_file[k] for k in differs} == {"num_hidden_layers": 4, "num_experts": 16,
                                                    "vocab_size": 18992}
    assert all(config_file["trainer"][k] == config_file[k] for k in differs)
    assert config_file["vocab_size"] * 8 == published["vocab_size"]
    assert config_file["num_experts"] * 8 == published["num_experts"] == config_file["num_router_outputs"]
    assert set(config_file["reduced_why"]) == set(config_file["reduced"]) and "8" in config_file["deployment"]
    listed = next(c for c in manifest.data["configs"] if c["name"] == "sdar-30b-a3b-ep8")
    assert listed["source"] in config_file["source"] and listed["source"].endswith("config.json")
    for key in ("block_length", "qk_norm", "pooling_and_head", "recipe", "masking", "optimizer",
                "weights", "run_length"):
        assert config_file["assumed"][key]


def test_the_programs_table_is_the_files_and_the_references(config_file):
    from moco_tpu.models.sdar import SDAR_SIZES
    from perfbench.reference import seq_nets

    z = SDAR_SIZES["sdar_30b_a3b"]
    assert z == seq_nets.SIZES["sdar_30b_a3b"] and SDAR_SIZES["sdar_tiny"] == seq_nets.SIZES["sdar_tiny"]
    pairs = {"hidden": "hidden_size", "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "top_k": "num_experts_per_tok", "expert_width": "moe_intermediate_size",
             "rope_theta": "rope_theta", "eps": "rms_norm_eps", "block_length": "block_length",
             "experts": "num_router_outputs"}
    assert all(z[a] == config_file[b] for a, b in pairs.items())
    assert {k: z[k] for k in ("layers", "experts", "vocab")} == {
        "layers": 48, "experts": 128, "vocab": 151936}


def test_the_cell_stands_in_the_manifest_with_its_metrics(manifest):
    cell = manifest.workload(CELL)
    assert cell["chips"] == 1 and "document" in cell["why"] and "attention" in cell["why"]
    entries = {p["name"]: p for p in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "train_imgs_per_s_per_chip"
        assert manifest.find("layer_metrics", name + ".py")
    assert [p["name"] for p in manifest.data["per_layer"]][-len(NEW_METRICS):] == list(NEW_METRICS)
    # a step of images has no token encoder and a token step no blur
    assert entries["step_mfu_pct"]["workloads"] == entries["blur_roofline"]["workloads"] == [
        "r50-v2-f32.synthetic"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) <= reported and not {"step_mfu_pct", "blur_roofline"} & reported
    limits = manifest.load_json("limits", CELL + ".json")
    assert set(limits["limits"]) <= set(limits["set_from"]) and limits["not_compared"] is not None


def test_the_cell_can_see_the_momentum_update(manifest, config_file):
    """REVIEW of PR 27: at lr 1e-7 the key encoder's change was under half a float32
    step and `dk3_med` had to leave `correct`, so an EMA left out passed every
    limit. The recipe's own lr and momentum move a key weight by over a hundred
    float32 steps in the three compared steps, and the limits hold both medians."""
    from moco_tpu.config import get_preset

    t, preset = config_file["trainer"], get_preset("text-moco-v2-sdar")
    assert (t["lr"], t["momentum_ema"]) == (preset.lr, preset.momentum_ema) and t["warmup_epochs"] == 0
    # k moves by (1 - m) * (q - k): nothing in step 1, one and two of AdamW's steps after
    moved = (1 - t["momentum_ema"]) * t["lr"] * (0 + 1 + 2)
    assert moved >= 100 * float(np.spacing(np.float32(0.03)))
    limits = manifest.load_json("limits", CELL + ".json")
    assert {"dq3_med", "dk3_med"} <= set(limits["limits"])
    assert not {"dq3_med", "dk3_med", "lr"} & (set(limits["not_compared"]) | set(config_file["reduced"]))
    # an unchanged state reads 1 on either: the limits lie well under it
    assert max(limits["limits"]["dq3_med"], limits["limits"]["dk3_med"]) <= 0.1


def test_the_parameters_of_the_cell_are_422_million_and_20_bytes_each(config_file):
    from perfbench.reference import seq_nets

    spec = seq_nets.spec(seq_nets.sizes_for(config_file["trainer"]), 128)
    n = sum(int(np.prod(shape)) for _, shape, _, _ in spec)
    assert n == pytest.approx(422e6, rel=0.002) and 20 * n == pytest.approx(8.44e9, rel=0.002)
    layer = sum(int(np.prod(s)) for p, s, _, _ in spec if p.startswith("layer_0/"))
    assert layer == pytest.approx(94.6e6, rel=0.002)


# -- arithmetic --------------------------------------------------------------------


def test_seq_flops_and_the_kernels_work_against_hand_counts(config_file):
    from perfbench import flops_seq
    from perfbench.kernels import moe_experts

    assert flops_seq.mask_density(512, 4) == pytest.approx(129 / 256)
    assert flops_seq.mask_density(8, 8) == 1.0 and flops_seq.mask_density(4, 1) == 10 / 16
    # a token of a layer by hand: q/k/v 2048 x 5120, scores + mix over 258 positions of 4096, o, router, experts
    token = (2 * 2048 * 5120 + 4 * 512 * (129 / 256) * 4096 + 2 * 4096 * 2048 + 2 * 2048 * 128
             + 1.0 * 6 * 2048 * 768)
    head = 2 * 2048 * 2048 + 2 * 2048 * 128
    assert flops_seq.view_forward(config_file, 512, 1.0) == pytest.approx(4 * 512 * token + head)
    config = SimpleNamespace(batch_size=32, seq_len=512)
    step = flops_seq.step_flops(config, config_file, 1.0)
    assert step == pytest.approx(13.6e12, rel=0.02)       # ISSUE 27 reckoned 13.6 TFLOP a step
    assert flops_seq.step_flops(config, config_file, 2.0) > step
    w = moe_experts.work(1000, 16, 2048, 768, 2)
    assert w["flops"] == 6 * 1000 * 2048 * 768
    assert w["bytes"] == 3 * 16 * 2048 * 768 * 2 + (2 * 1000 * 2048 + 3 * 1000 * 768 + 1000 * 2048) * 2
    s = moe_experts.step_work(config_file, 16384, 2)
    assert s["flops"] == 16 * moe_experts.work(16384, 16, 2048, 768, 2)["flops"]
    # the trainer's own count at uniform routing is the benchmark's at one assignment a token
    from moco_tpu.telemetry.mfu import train_step_flops

    from perfbench import harness

    assert train_step_flops(harness.trainer_config(config_file, "")) == pytest.approx(step, rel=1e-6)


def test_the_nested_readers_names_are_the_programs_and_reduce_by_innermost_name():
    from moco_tpu.telemetry import scopes
    from perfbench import nested_spans

    assert nested_spans.NESTED == scopes.ENCODER_SCOPES
    base = "jit(fused_step)/jit(train_step)/shard_map/"
    events = [
        ("fusion.1", 0, 1000, {"tf_op": base + "q_fwd_bwd/jvp(SDAREncoder)/layer_0/attn/attn/q/dot_general"}),
        ("fusion.2", 1000, 500, {"tf_op": base + "q_fwd_bwd/transpose(jvp(SDAREncoder))/checkpoint/layer_0/"
                                          "moe_router/moe/moe_dispatch/moe_experts/ragged_dot"}),
        ("fusion.3", 1500, 250, {"tf_op": base + "k_fwd/SDAREncoder/layer_1/moe_router/moe/moe_dispatch/sort"}),
        ("fusion.4", 1750, 125, {"tf_op": base + "k_fwd/SDAREncoder/embed_pool/embed/take"}),
        ("fusion.5", 1875, 60, {"tf_op": base + "loss_queue/dot_general"}),
        ("copy-start.1", 1935, 5, {}),
        # the TPU's grouped product: a custom call whose `op_name` is its own name
        ("%ragged-dot-none.27 = bf16[32768,768]{1,0} custom-call(...)", 1940, 40, {"tf_op": "ragged-dot-none:"}),
    ]
    red = nested_spans.reduce_planes([{"name": "/device:TPU:0", "lines": {"XLA Ops": events}}], "tpu")
    assert red == {"scope_ps": {"attn": 1000, "moe_experts": 540, "moe_dispatch": 250, "embed_pool": 125},
                   "device_planes": 1}
    run = {"traced_steps": 1}
    assert nested_spans.scope_ms_of(red, "attn", run) == pytest.approx(1e-6)
    records = [{"step": 1, "health": {"moe_assign_per_token": 1.5}}, {"step": 2}, {"step": 17, "health": {"moe_assign_per_token": 0.5}}]
    assert nested_spans.counter({"window_records": records[1:], "records": records}, "moe_assign_per_token") == 0.5
    assert nested_spans.counter({"window_records": [], "records": records}, "moe_assign_per_token") == 1.0
    assert nested_spans.counter({"window_records": [], "records": []}, "moe_assign_per_token") is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(manifest, name, config_file):
    from perfbench import harness

    run = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
           "config": SimpleNamespace(batch_size=32, seq_len=512, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2, "chips": 1, "device_kind": "TPU v5 lite"}
    mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "t_" + name)
    assert mod.read(run) is None


def test_the_mfu_and_roofline_readers_on_a_made_up_run(manifest, config_file):
    """13.6 TFLOP in 0.2 s is 34.5 % of 197 TFLOP/s; 16 forward passes of the
    experts at one assignment a token are 2.48 TFLOP, 12.6 ms at the peak."""
    from perfbench import harness, nested_spans

    records = [{"step": 16, "health": {"moe_assign_per_token": 1.0, "moe_load_max_over_mean": 1.25}}]
    run = {"records": records, "window_records": records, "chips": 1, "device_kind": "TPU v5 lite",
           "trace": {"programs": {"jit_fused_step(123)": [0.2, 0.2]}, "ops": {}},
           "config": SimpleNamespace(batch_size=32, seq_len=512, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2}

    def read(name):
        return harness.load_module(manifest.find("layer_metrics", name + ".py"), "m_" + name).read(run)

    assert read("seq_step_mfu_pct") == pytest.approx(100 * 13.6e12 / 0.2 / 197e12, rel=0.02)
    assert read("expert_load_max_over_mean") == 1.25
    saved = nested_spans.reduction
    nested_spans.reduction = lambda run: {"scope_ps": {"moe_experts": 2 * 25.2e9}, "device_planes": 1}
    try:
        assert read("moe_experts_device_ms") == pytest.approx(25.2)
        assert read("moe_experts_roofline") == pytest.approx(100 * 16 * 6 * 16384 * 2048 * 768 / 197e12 / 25.2e-3)
        assert read("attn_device_ms") == 0.0
    finally:
        nested_spans.reduction = saved


# -- traffic -----------------------------------------------------------------------


def test_the_token_mix_rotates_documents_and_orders_by_seed(manifest):
    from perfbench import harness

    mix = {"generator": "memory_tokens", "distinct": 8, "entries": 64, "length": 40, "vocab": 50,
           "zipf": 1.0, "data_seed": 3}
    cfg = SimpleNamespace(batch_size=4, vocab_size=50, seq_len=16)
    ds = harness.build_traffic(manifest, mix, cfg, seed=5)
    rows, labels, lengths = ds._dataset.get_batch(np.array([0, 8, 9, 63]))
    assert rows.shape == (4, 40) and rows.dtype == np.int32 and (lengths == 40).all() and lengths.shape == (4, 1)
    assert rows.max() < 49 and rows.min() >= 0             # the last id is the mask id
    assert (np.roll(rows[0], 1) == rows[1]).all() and not (rows[0] == rows[1]).all()
    every = np.arange(64)
    a = ds.get_batch(every)[0]
    b = harness.build_traffic(manifest, mix, cfg, seed=5).get_batch(every)[0]
    c = harness.build_traffic(manifest, mix, cfg, seed=2 ** 31 + 7).get_batch(every)[0]
    assert (a == b).all() and not (a == c).all()
    assert sorted(map(bytes, a)) == sorted(map(bytes, c))
    # Zipf(1): the commonest id is about twice the second
    big = harness.find_generator(manifest, mix).MemoryTokens(64, 64, 1024, 18992, 1.0, 1).docs
    counts = np.bincount(big.ravel(), minlength=18992)
    assert counts[0] > 1.6 * counts[1] > 1.6 * 1.2 * counts[3]
    with pytest.raises(ValueError):
        harness.build_traffic(manifest, mix, SimpleNamespace(batch_size=4, vocab_size=51, seq_len=16), seed=1)
    real = manifest.load_json("traffic", "tokens512.json")
    assert (real["distinct"], real["entries"], real["length"], real["vocab"]) == (8192, 524288, 1024, 18992)


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
    with open(os.path.join(ROOT, "tests/perfbench/extra/configs/sdar-tiny.json")) as f:
        config_file = json.load(f)
    config = harness.trainer_config(config_file, "").replace(compute_dtype=compute_dtype)
    cfg = harness.reference_cfg(config_file, config, 64)
    ref = harness.build_reference(manifest, config_file, cfg)
    qshape = (config.num_negatives, config.embed_dim)
    rng = np.random.default_rng(seed)
    inputs = [(rng.integers(0, 63, (config.batch_size, 48)).astype(np.int32),
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
    losses, moment1, counts1 = [], None, None
    for i, (rows_i, lengths) in enumerate(inputs):
        state, metrics = fused(state, jnp.asarray(rows_i), jnp.asarray(lengths), i)
        losses.append(float(metrics["loss"]))
        if i == 0:
            moment1 = jax.device_get(harness.optimizer_moment(state.opt_state, "mu"))
            counts1 = float(metrics["h_moe_assign_per_token"])
    prog = {"losses": losses, "grad1": None, "moment_name": "mu", "moment1": moment1,
            "q3": jax.device_get(harness.flatten(state.params_q)),
            "k3": jax.device_get(harness.flatten(state.params_k)),
            "keys3": jax.device_get(state.queue[: 3 * config.batch_size])}
    numbers = {k: v[0] for k, v in harness.compare(prog, ref_out, weights, hyper).items()}
    chosen = np.asarray(ref.chosen_sets(weights, *inputs[0]))          # [layers, tokens, top_k]
    return numbers, ref_out, {"assign": counts1, "chosen": chosen, "held": ref.z["held"]}


def test_program_in_float32_agrees_with_the_reference_to_rounding():
    numbers, ref_out, extra = _three_steps("float32")
    for k, limit in TIGHT.items():
        assert numbers[k] <= limit, (k, numbers[k])
    assert all(np.isfinite(ref_out["losses"]))
    # the program's counter is the reference's routing: assignments to held experts a token
    chosen = extra["chosen"]
    assert chosen.shape == (2, 8 * 16, 4)
    assert extra["assign"] == pytest.approx((chosen < extra["held"]).sum() / chosen.shape[0] / chosen.shape[1])


@pytest.mark.parametrize("fault", ["float8", "fault_causal", "fault_top_half", "fault_renorm_held", "half"])
def test_the_control_and_every_planted_fault_fail_what_float32_passes(fault):
    numbers, _, _ = (_three_steps(rows=4) if fault == "half" else _three_steps(precision=fault))
    over = {k for k, limit in TIGHT.items() if not numbers.get(k, 0) <= limit}
    assert over & {"grad1", "grad1_med", "keys_max", "loss2", "loss3"}, numbers


def test_the_references_shares_add_up_to_the_uncut_layer():
    """In the reference: the parts of the result that the eight shares of two
    experts each give add up to what the layer gives whole."""
    import jax

    from perfbench import harness
    from perfbench.reference import nets, seq_nets

    z = dict(seq_nets.sizes_for({"arch": "sdar_tiny"}))
    spec = [s for s in seq_nets.spec(z, 128) if s[0].startswith("layer_0/moe/")]
    p, _ = harness.make_weights(spec, 9)
    u = jax.random.normal(jax.random.key(2), (64, z["hidden"]))
    weights, chosen = seq_nets.routing(p, "layer_0/moe", u, z)
    assert chosen.shape == (64, 4) and np.allclose(weights.sum(-1), 1.0, atol=1e-6)
    ops = nets.Ops("float32")
    whole = seq_nets.experts(ops, p, "layer_0/moe", u, weights, slice(0, 16))
    parts = [seq_nets.experts(ops, p, "layer_0/moe", u, weights, slice(2 * j, 2 * j + 2)) for j in range(8)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    assert float(abs(parts[0] - whole).max()) > 1e-3
    # and the masks: a causal mask is the block-causal one at block length 1
    attn = [s for s in seq_nets.spec(z, 128) if s[0].startswith("layer_0/attn/")]
    pa, _ = harness.make_weights(attn, 4)
    h = jax.random.normal(jax.random.key(3), (2, 8, z["hidden"]))
    causal = seq_nets.attention(ops, pa, "layer_0/attn", h, z, fault="causal")
    np.testing.assert_allclose(causal, seq_nets.attention(ops, pa, "layer_0/attn", h, dict(z, block_length=1)),
                               rtol=1e-6)
    full = seq_nets.attention(ops, pa, "layer_0/attn", h, dict(z, block_length=8))
    assert float(abs(causal - full).max()) > 1e-3 and float(abs(causal - full)[:, -1].max()) < 1e-5


def test_the_reference_imports_nothing_of_the_program():
    for name in ("moco_seq.py", "seq_nets.py"):
        with open(os.path.join(ROOT, "perfbench", "reference", name)) as f:
            text = f.read()
        assert "import moco_tpu" not in text and "from moco_tpu" not in text


# -- the command end to end on a tiny token cell -------------------------------------


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """The benchmark with a tiny token cell: its configuration, traffic and limits
    are files of the tree; the entries are added to a copy of the tiny manifest."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("seq_cell")))
    extra = os.path.join(root, "tests", "perfbench", "extra")
    for sub in ("layer_metrics", "limits", "traffic", "configs"):
        os.makedirs(os.path.join(extra, sub), exist_ok=True)
    path = os.path.join(extra, "tiny_manifest.json")
    with open(path) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        top = json.load(f)
    m["configs"].append({"name": "sdar-tiny", "source": "test", "reduced": [], "why": "test",
                         "file": "tests/perfbench/extra/configs/sdar-tiny.json"})
    m["workloads"].append({"name": TINY_CELL, "config": "sdar-tiny", "traffic": "tiny_tokens",
                           "chips": 1, "why": "test"})
    m["per_layer"] += [dict(p, workloads=[TINY_CELL]) for p in top["per_layer"] if p["name"] in NEW_METRICS]
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def test_tiny_token_cell_runs_traced_and_reports_its_counters(extended):
    rc, result, _ = run_cell(TINY_CELL, trace=1, root=extended, seconds=2, seed=2 ** 31 + 27)
    assert rc == 0 and list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True and result["attempted"] >= 2 and result["failed"] == 0
    for k, limit in TIGHT.items():
        assert result["compared"][k]["value"] <= limit, k
    metrics = result["metrics"]
    assert 1.0 <= metrics["expert_load_max_over_mean"]["value"] < 4.0
    # a CPU trace carries no scope and no program line: nothing under a device metric's name
    assert not {"seq_step_mfu_pct", "moe_experts_roofline", "attn_device_ms", "moe_experts_device_ms",
                "step_mfu_pct", "blur_roofline", "fused_step_device_ms"} & set(metrics)
    assert {"pre_step_s", "host_ms_per_step", "step_ms_p90", "data_wait_pct"} <= set(metrics)
    events = os.path.join(extended, "perfbench", "_work", "run-" + TINY_CELL, "telemetry", "events.jsonl")
    with open(events) as f:
        steps = [r for r in map(json.loads, f) if r.get("kind") == "step"]
    sampled = [r["health"] for r in steps if "moe_assign_per_token" in r.get("health", {})]
    assert sampled and all(0.5 < h["moe_assign_per_token"] < 1.7 for h in sampled)


def key_unchanged(real):
    """The momentum update left out: the step hands back the key encoder it got."""
    import jax

    def step(state, rows, lengths, n):
        kept = jax.tree.map(lambda x: x.copy(), state.params_k)
        state, metrics = real(state, rows, lengths, n)
        return state.replace(params_k=kept), metrics
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "key_unchanged"])
def test_a_broken_timed_token_path_is_not_correct(extended, fault):
    wrap = key_unchanged if fault == "key_unchanged" else getattr(pb_helpers, fault)
    rc, result, _ = run_cell(TINY_CELL, root=extended, seed=12, wrap_step=wrap)
    assert rc == 0 and result["correct"] is False
    over = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    want = {"state_unchanged": {"dq3_med", "dk3_med"}, "key_unchanged": {"dk3_med"},
            "half_batch": {"keys_max", "keys_med", "grad1"}}[fault]
    assert want <= over if fault != "half_batch" else over & want
    if fault == "key_unchanged":     # and nothing else sees it
        assert result["compared"]["dk3_med"]["value"] > 0.9      # 1 but for rounding
        assert not over - {"dk3", "dk3_med"}


def test_the_upper_readings_come_from_the_reference_alone(extended, tmp_path, capsys):
    """`calibrate_reference.py`: controls and planted faults against the float32
    reference from the cell's own traffic, no trainer in the process."""
    from perfbench import calibrate_reference

    rc = calibrate_reference.main(
        ["--workload", TINY_CELL, "--seeds", "2", "--first-seed", "40", "--variants",
         "float8,half,fault_causal,routing", "--root", extended, "--manifest",
         "tests/perfbench/extra/tiny_manifest.json", "--out", str(tmp_path), "--platform", "cpu"])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert [ln["seed"] for ln in lines[:2]] == [40, 41] and lines[-1]["seeds"] == 2
    for ln in lines[:2]:
        assert ln["half"]["keys_max"] == pytest.approx(2 ** 0.5)     # the rows left out
        assert ln["routing_set_share"] == 0.0      # in float32 the program picks the reference's sets
        for name in ("float8", "half", "fault_causal"):
            assert any(ln[name][k] > limit for k, limit in TIGHT.items()), (name, ln[name])
    with open(tmp_path / f"calibrate-reference-{TINY_CELL}.json") as f:
        assert len(json.load(f)) == 2
