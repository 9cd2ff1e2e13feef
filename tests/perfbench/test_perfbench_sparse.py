"""ISSUE 33, the benchmark's side: the long-document configuration's files, its
plain reference against the program at a small size on the CPU (hidden 64, 2
layers, 16 experts of which 4 held, an indexer of 4 heads of 8 that picks 16 keys
a query, views of 64 tokens), the command end to end on a tiny cell with the six
planted faults under the timed path, and the new readers."""

import json
import os
import unittest.mock as mock
from types import SimpleNamespace

import numpy as np
import pytest

import pb_helpers
from pb_helpers import CONTRACT_KEYS, ROOT, copy_benchmark, run_cell

CELL = "keye-vl2-30b-a3b-ep8.tokens8k"
CONFIG = "keye-vl2-30b-a3b-ep8"
TINY_CELL = "keye-tiny.tiny_tokens_l160"
NEW_METRICS = ("sparse_step_mfu_pct", "index_device_ms", "select_device_ms", "sparse_attn_roofline",
               "index_scores_roofline", "select_top_k_roofline", "sel_live_tile_share")
SHARED_METRICS = ("aug_device_ms", "k_fwd_device_ms", "q_fwd_bwd_device_ms", "loss_queue_device_ms",
                  "opt_ema_device_ms", "async_copy_wait_ms", "loop_unspanned_ms_per_step",
                  "h2d_mb_per_step", "compile_s", "compiles_in_window", "model_init_s",
                  "attn_device_ms", "embed_pool_device_ms", "moe_router_device_ms",
                  "moe_dispatch_device_ms", "moe_experts_device_ms", "moe_experts_roofline",
                  "expert_load_max_over_mean")
TIGHT = {"loss1": 5e-5, "loss2": 5e-5, "loss3": 5e-5, "grad1": 5e-3, "grad1_med": 5e-4,
         "dq3": 5e-2, "dk3": 5e-2, "keys_max": 1e-4}


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


@pytest.fixture(scope="module")
def config_file(manifest):
    return manifest.config(CONFIG)


# -- the configuration's file -----------------------------------------------------


def test_the_file_holds_every_published_width_and_lists_its_cuts(config_file, manifest):
    """The catalog row's `config`, key for key (`sa_config` and `rope_scaling`
    whole); the three cuts with the published numbers beside them."""
    published = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
                 "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
                 "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
                 "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
                 "rms_norm_eps": 1e-06,
                 "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
                 "rope_theta": 10000000,
                 "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                               "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
                 "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
                 "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if config_file[k] != v)
    assert differs == sorted(config_file["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert config_file["published"] == {k: published[k] for k in differs}
    assert {k: config_file[k] for k in differs} == {"num_hidden_layers": 4, "num_experts": 16,
                                                    "vocab_size": 18992}
    assert all(config_file["trainer"][k] == config_file[k] for k in differs)
    assert config_file["num_experts"] * 8 == published["num_experts"] == config_file["num_router_outputs"]
    assert set(config_file["reduced_why"]) == set(config_file["reduced"]) and "8" in config_file["deployment"]
    assert (config_file["trainer"]["seq_len"], config_file["trainer"]["batch_size"]) == (8192, 2)
    listed = next(c for c in manifest.data["configs"] if c["name"] == CONFIG)
    assert listed["source"].split(";")[0] in config_file["source"] and len(listed["source"]) <= 200
    assert listed["reduced"] == config_file["reduced"]
    for key in ("indexer_equations", "indexer_rotary", "tie_rule", "chunk_sizes", "mrope_section",
                "qk_norm", "indexer_precision", "frozen_indexer", "left_out", "pooling_and_head",
                "recipe", "masking", "weights", "remat", "health_stride"):
        assert config_file["assumed"][key]
    assert "no effect on the result" in config_file["assumed"]["chunk_sizes"]


def test_the_programs_table_is_the_files_and_the_references(config_file):
    from moco_tpu.config import get_preset
    from moco_tpu.models.keye import KEYE_SIZES
    from perfbench.reference import sparse_nets

    z = dict(KEYE_SIZES["keye_vl2_30b_a3b"])
    assert z.pop("block_length") == 1      # the program's name for a causal mask
    assert z == sparse_nets.SIZES["keye_vl2_30b_a3b"]
    tiny = dict(KEYE_SIZES["keye_tiny"], block_length=None)
    assert tiny == dict(sparse_nets.SIZES["keye_tiny"], block_length=None)
    pairs = {"hidden": "hidden_size", "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
             "head_dim": "head_dim", "top_k": "num_experts_per_tok", "expert_width": "moe_intermediate_size",
             "rope_theta": "rope_theta", "eps": "rms_norm_eps", "experts": "num_router_outputs"}
    assert all(z[a] == config_file[b] for a, b in pairs.items())
    sa = config_file["sa_config"]
    assert (z["index_heads"], z["index_dim"], z["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert {k: z[k] for k in ("layers", "experts", "vocab")} == {
        "layers": 48, "experts": 128, "vocab": 151936}
    preset = get_preset(config_file["preset"])
    assert (preset.arch, preset.seq_len, preset.batch_size) == ("keye_vl2_30b_a3b", 8192, 2)


def test_the_cell_stands_in_the_manifest_with_its_metrics(manifest):
    cell = manifest.workload(CELL)      # (a later PR appends after it: no count, no "last" here)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert "2 documents" in cell["why"] and "8 192" in cell["why"] and len(cell["why"]) <= 200
    entries = {p["name"]: p for p in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL] and entries[name]["moves"] == "train_imgs_per_s_per_chip"
        assert manifest.find("layer_metrics", name + ".py")
    names = [p["name"] for p in manifest.data["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first: first + len(NEW_METRICS)] == list(NEW_METRICS) and first > names.index("ut_pass_delta")
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"], name
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= reported
    # its count is a block-causal density; its reader finds nothing to read here
    assert not {"seq_step_mfu_pct", "unscoped_device_pct", "step_mfu_pct", "blur_roofline",
                "looped_step_mfu_pct", "mlp_device_ms"} & reported
    mix = manifest.load_json("traffic", cell["traffic"] + ".json")
    assert (mix["generator"], mix["distinct"], mix["entries"], mix["length"], mix["vocab"]) == (
        "memory_tokens", 1024, 65536, 16384, 18992)
    limits = manifest.load_json("limits", CELL + ".json")
    assert set(limits["limits"]) <= set(limits["set_from"]) and limits["not_compared"] is not None
    for name, reading in limits["set_from"].items():
        assert reading["lower"] < limits["limits"][name] < reading["upper"], name


def test_the_parameters_of_the_cell_are_431_million_and_20_bytes_each(config_file):
    from perfbench.reference import sparse_nets

    spec = sparse_nets.spec(sparse_nets.sizes_for(config_file["trainer"]), 128)
    n = sum(int(np.prod(shape)) for _, shape, _, _ in spec)
    assert n == pytest.approx(431e6, rel=0.002) and 20 * n == pytest.approx(8.62e9, rel=0.002)
    layer = sum(int(np.prod(s)) for p, s, _, _ in spec if p.startswith("layer_0/"))
    assert layer == pytest.approx(96.9e6, rel=0.002)
    indexer = [p for p, _, _, _ in spec if p.startswith("layer_0/indexer/")]
    assert len(indexer) == 5
    assert sum(int(np.prod(s)) for p, s, _, _ in spec if p in indexer) == pytest.approx(2.26e6, rel=0.01)


# -- arithmetic --------------------------------------------------------------------


def test_sparse_flops_and_the_kernels_work_against_hand_counts(config_file):
    from perfbench import flops_sparse, harness
    from perfbench.kernels import sparse_attn, sparse_index

    assert flops_sparse.selected_pairs(8192, 2048) / 8192 == pytest.approx(1792.125)
    assert flops_sparse.selected_pairs(64, 2048) == flops_sparse.causal_pairs(64) == 64 * 65 / 2
    # a token of a layer by hand (ISSUE 33): projections 37.7 MFLOP, attention at the
    # selected pairs 29.4, router 0.5, experts 9.4; the indexer's 4.5 + 8.4 forward only
    trained, constant = flops_sparse.view_forward(config_file, 8192, 1.0)
    head = 2 * 2048 * 2048 + 2 * 2048 * 128
    assert (trained - head) / (4 * 8192) == pytest.approx(77.0e6, rel=0.005)
    assert constant / (4 * 8192) == pytest.approx(12.9e6, rel=0.01)
    config = SimpleNamespace(batch_size=2, seq_len=8192, compute_dtype="bfloat16")
    step = flops_sparse.step_flops(config, config_file, 1.0)
    assert step == pytest.approx(21.9e12, rel=0.005)       # ISSUE 33 reckoned 21.9 TFLOP a step
    assert flops_sparse.step_flops(config, config_file, 2.0) > step
    one = sparse_attn.work(8192, 2048, 32, 4, 128, 2)
    assert one["fwd"]["flops"] == 4 * 1792.125 * 8192 * 4096 and one["bwd"]["flops"] == 2.5 * one["fwd"]["flops"]
    assert one["fwd"]["bytes"] == 2 * 8192 * 4096 * 2 + 2 * 8192 * 512 * 2 + 8192 * 8192
    s = sparse_attn.step_work(config_file, 2, 8192, 2)
    assert s["flops"] == 2 * 4 * 4.5 * one["fwd"]["flops"]
    # all causal pairs would be 67.1 MFLOP a token: the masked product cannot read over 44 %
    assert flops_sparse.selected_pairs(8192, 2048) / flops_sparse.causal_pairs(8192) == pytest.approx(0.4375, rel=0.001)
    i = sparse_index.step_work(config_file, 2, 8192, 2)
    assert i["scores"]["flops"] == 2 * 2 * 4 * 2 * (8192 * 8193 / 2) * 16 * 64
    assert i["selection"]["flops"] == 0 and i["selection"]["bytes"] == 16 * (4 * 8192 * 8193 / 2 + 8192 ** 2)
    # the trainer's own count differs by the indexer's two passes that it counts as four
    from moco_tpu.telemetry.mfu import train_step_flops

    trainer = train_step_flops(harness.trainer_config(config_file, ""))
    assert trainer == pytest.approx(step + 2 * 2 * constant, rel=1e-6)


def test_the_sparse_readers_names_are_the_programs_and_reduce_by_innermost_name():
    from moco_tpu.telemetry import scopes
    from perfbench import nested_spans, sparse_spans

    assert sparse_spans.SPARSE == scopes.SPARSE_SCOPES
    assert not set(sparse_spans.SPARSE) & set(nested_spans.NESTED)
    base = "jit(fused_step)/jit(train_step)/shard_map/"
    layer = "q_fwd_bwd/jvp(SDAREncoder)/checkpoint/layer_0/"
    events = [
        ("fusion.1", 0, 1000, {"tf_op": base + layer + "indexer/index/q/dot_general"}),
        ("%index_scores.3 = f32[2,8192,8192]{2,1,0} custom-call(...)", 1000, 500,
         {"tf_op": base + "k_fwd/SDAREncoder/layer_1/indexer/index/jit(_index_scores)/index_scores/pallas_call"}),
        ("custom-call.7", 1500, 250,
         {"tf_op": base + layer + "indexer/select/jit(_select)/select_top_k/pallas_call"}),
        ("fusion.4", 1750, 125, {"tf_op": base + layer + "indexer/select/reduce_sum"}),
        ("custom-call.8", 1875, 60,
         {"tf_op": base + layer + "attn/attn/jit(_tiled_forward)/masked_attention_fwd/pallas_call"}),
        ("custom-call.9", 1935, 40, {"tf_op": base + "q_fwd_bwd/transpose(jvp(SDAREncoder))/checkpoint/layer_0/"
                                              "attn/attn/jit(_tiled_backward)/masked_attention_bwd/pallas_call"}),
        ("fusion.5", 1975, 5, {"tf_op": base + "loss_queue/dot_general"}),
    ]
    planes = [{"name": "/device:TPU:0", "lines": {"XLA Ops": events}}]
    red = sparse_spans.reduce_planes(planes, "tpu")
    assert red == {"scope_ps": {"index": 1500, "select": 375},
                   "kernel_ps": {"index_scores": 500, "select_top_k": 250,
                                 "masked_attention_fwd": 60, "masked_attention_bwd": 40},
                   "device_planes": 1}
    # and the accepted reader of `attn` sees the attention kernels alone
    assert nested_spans.reduce_planes(planes, "tpu")["scope_ps"] == {"attn": 100}
    # a program without the names (the parent of the PR that added them)
    assert sparse_spans.reduce_planes([{"name": "/device:TPU:0", "lines": {"XLA Ops": events[-1:]}}],
                                      "tpu")["scope_ps"] == {}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(manifest, name, config_file):
    from perfbench import harness

    run = {"records": [], "window_records": [], "trace": {"programs": {}, "ops": {}},
           "config": SimpleNamespace(batch_size=2, seq_len=8192, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2, "chips": 1, "device_kind": "TPU v5 lite"}
    mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "t_" + name)
    assert mod.read(run) is None
    # nor under another configuration's file (a cell the metric does not list)
    other = dict(run, config_file=manifest.config("sdar-30b-a3b-ep8"))
    assert mod.read(other) is None


def test_the_mfu_and_roofline_readers_on_a_made_up_run(manifest, config_file):
    """21.9 TFLOP in 0.8 s is 13.9 % of 197 TFLOP/s; the attention over the selected
    pairs of a step is 8.66 TFLOP, 44 ms at the peak."""
    from perfbench import harness, sparse_spans

    records = [{"step": 16, "health": {"moe_assign_per_token": 1.0, "sel_live_tile_share": 0.75}}]
    run = {"records": records, "window_records": records, "chips": 1, "device_kind": "TPU v5 lite",
           "trace": {"programs": {"jit_fused_step(123)": [0.8, 0.8]}, "ops": {}},
           "config": SimpleNamespace(batch_size=2, seq_len=8192, compute_dtype="bfloat16"),
           "config_file": config_file, "traced_steps": 2}

    def read(name):
        return harness.load_module(manifest.find("layer_metrics", name + ".py"), "m_" + name).read(run)

    assert read("sparse_step_mfu_pct") == pytest.approx(100 * 21.9e12 / 0.8 / 197e12, rel=0.005)
    assert read("sel_live_tile_share") == 0.75
    saved = sparse_spans.reduction
    sparse_spans.reduction = lambda run: {
        "scope_ps": {"index": 2 * 30e9, "select": 2 * 60e9}, "device_planes": 1,
        "kernel_ps": {"masked_attention_fwd": 2 * 150e9, "masked_attention_bwd": 2 * 70e9,
                      "index_scores": 2 * 28e9, "select_top_k": 2 * 57e9}}
    try:
        assert read("index_device_ms") == pytest.approx(30.0) and read("select_device_ms") == pytest.approx(60.0)
        assert read("sparse_attn_roofline") == pytest.approx(100 * 8.659e12 / 197e12 / 0.220, rel=0.001)
        assert read("index_scores_roofline") == pytest.approx(100 * 1.0996e12 / 197e12 / 0.028, rel=0.001)
        assert read("select_top_k_roofline") == pytest.approx(100 * 3.2215e9 / 819e9 / 0.057, rel=0.001)
        assert all(0 < read(n) < 100 for n in NEW_METRICS if n.endswith("_roofline"))
    finally:
        sparse_spans.reduction = saved


# -- the reference against the program, from the same token rows --------------------


def _tiny():
    from perfbench import harness

    manifest = harness.Manifest(ROOT)
    with open(os.path.join(ROOT, "tests/perfbench/extra/configs/keye-tiny.json")) as f:
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
    inputs = [(rng.integers(0, 63, (config.batch_size, 160)).astype(np.int32),
               np.full((config.batch_size, 1), 160, np.int32)) for _ in range(3)]
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
    losses, moment1, keys1 = [], None, None
    for i, (rows_i, lengths) in enumerate(inputs):
        state, metrics = fused(state, jnp.asarray(rows_i), jnp.asarray(lengths), i)
        losses.append(float(metrics["loss"]))
        if i == 0:
            moment1 = jax.device_get(harness.optimizer_moment(state.opt_state, "mu"))
            keys1 = float(metrics["h_sel_keys_per_query"])
    prog = {"losses": losses, "grad1": None, "moment_name": "mu", "moment1": moment1,
            "q3": jax.device_get(harness.flatten(state.params_q)),
            "k3": jax.device_get(harness.flatten(state.params_k)),
            "keys3": jax.device_get(state.queue[: 3 * config.batch_size])}
    numbers = {k: v[0] for k, v in harness.compare(prog, ref_out, weights, hyper).items()}
    picked = np.asarray(ref.picked_pairs(weights, *inputs[0]))          # [layers, B, L, L]
    return numbers, ref_out, {"keys_per_query": keys1, "picked": picked, "weights": weights,
                              "q3": prog["q3"], "k3": prog["k3"], "ref": ref}


@pytest.fixture(scope="module")
def float32_program():
    return _three_steps()


def test_program_in_float32_agrees_with_the_reference_to_rounding(float32_program):
    numbers, ref_out, extra = float32_program
    for k, limit in TIGHT.items():
        assert numbers[k] <= limit, (k, numbers[k])
    assert all(np.isfinite(ref_out["losses"]))
    # the program's counter is the reference's selection: min(16, t + 1) keys a query
    picked = extra["picked"]
    assert picked.shape == (2, 4, 64, 64) and not np.triu(picked, 1).any()
    assert (picked.sum(-1) == np.minimum(16, np.arange(64) + 1)).all()
    assert extra["keys_per_query"] == pytest.approx(picked.sum() / picked.shape[0] / 4 / 64)


def test_the_indexers_and_the_routers_leaves_are_constants_in_both(float32_program):
    """After three steps: in the program and in the reference the same leaves
    stand where the seed put them, every other leaf moved, and the momentum
    copies follow."""
    _, ref_out, extra = float32_program
    ref, weights = extra["ref"], extra["weights"]
    constant = {p for p in weights if "/indexer/" in p or "/router/" in p}
    assert len(constant) == 2 * (5 + 1) and constant == {p for p in weights if not ref.trainable(p)}
    for side, q3, k3 in (("program", extra["q3"], extra["k3"]), ("reference", ref_out["q3"], ref_out["k3"])):
        for p, w in weights.items():
            moved = bool(np.abs(np.asarray(q3[p]) - w).max() > 0)
            assert moved == (p not in constant), (side, p)
            assert bool(np.abs(np.asarray(k3[p]) - w).max() > 0) == moved, (side, p)


@pytest.mark.parametrize("fault", ["float8", "fault_select_all", "fault_topk_half", "fault_recent",
                                   "fault_no_relu", "half"])
def test_the_control_and_every_planted_fault_fail_what_float32_passes(fault):
    numbers, _, _ = (_three_steps(rows=2) if fault == "half" else _three_steps(precision=fault))
    over = {k for k, limit in TIGHT.items() if not numbers.get(k, 0) <= limit}
    assert over & {"grad1", "grad1_med", "keys_max", "loss2", "loss3"}, numbers


def test_the_references_selection_is_exact_and_its_blocks_change_nothing():
    import jax
    import jax.numpy as jnp

    from perfbench.reference import sparse_nets

    scores = jax.random.normal(jax.random.key(0), (1, 8, 40))
    scores = scores.at[0, 5].set(0.0).at[0, 5, ::2].set(-0.0).at[0, 6, jnp.array([1, 4, 30])].set(9.0)
    position = jnp.arange(30, 38)
    picked = np.asarray(sparse_nets.select(scores, position, 6))
    assert (picked.sum(-1) == 6).all() and not picked[0, 0, 31:].any()      # causal: s <= t
    assert (np.flatnonzero(picked[0, 5]) == np.arange(6)).all()             # all equal: the lowest
    assert picked[0, 6, [1, 4, 30]].all()
    early = np.asarray(sparse_nets.select(scores[:, :3], jnp.arange(3), 6))
    assert (early.sum(-1) == [1, 2, 3]).all()                               # fewer keys than topk: all
    assert np.asarray(sparse_nets.select(scores, position, 6, "select_all")).sum() == sum(range(31, 39))
    recent = np.asarray(sparse_nets.select(scores, position, 6, "recent"))
    assert (np.flatnonzero(recent[0, 0]) == np.arange(25, 31)).all()
    assert (np.asarray(sparse_nets.select(scores, position, 6, "topk_half")).sum(-1) == 3).all()
    # blocks of query rows: the same numbers whatever the block
    harness, manifest, config_file = _tiny()
    z = sparse_nets.sizes_for(config_file["trainer"])
    w, _ = harness.make_weights(sparse_nets.spec(z, 128), 5)
    ids = jax.random.randint(jax.random.key(1), (2, 64), 0, 64)
    ops = sparse_nets.Ops("float32")
    whole = sparse_nets.forward(ops, w, ids, z)
    with mock.patch.object(sparse_nets, "BLOCK", 16):
        np.testing.assert_allclose(sparse_nets.forward(ops, w, ids, z), whole, rtol=1e-5, atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("moco_sparse.py", "sparse_nets.py"):
        with open(os.path.join(ROOT, "perfbench", "reference", name)) as f:
            text = f.read()
        assert "import moco_tpu" not in text and "from moco_tpu" not in text


# -- the command end to end on a tiny cell --------------------------------------------


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """The benchmark with a tiny long-view cell: its configuration, traffic and
    limits are files of the tree; the entries are added to a copy of the tiny
    manifest."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("sparse_cell")))
    path = os.path.join(root, "tests", "perfbench", "extra", "tiny_manifest.json")
    with open(path) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        top = json.load(f)
    m["configs"].append({"name": "keye-tiny", "source": "test", "reduced": [], "why": "test",
                         "file": "tests/perfbench/extra/configs/keye-tiny.json"})
    m["workloads"].append({"name": TINY_CELL, "config": "keye-tiny", "traffic": "tiny_tokens_l160",
                           "chips": 1, "why": "test"})
    m["per_layer"] += [dict(p, workloads=[TINY_CELL]) for p in top["per_layer"]
                       if p["name"] in NEW_METRICS + ("expert_load_max_over_mean",)]
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def test_tiny_cell_runs_traced_and_reports_its_counters(extended):
    rc, result, _ = run_cell(TINY_CELL, trace=1, root=extended, seconds=2, seed=2 ** 31 + 33)
    assert rc == 0 and list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True and result["attempted"] >= 2 and result["failed"] == 0
    for k, limit in TIGHT.items():
        assert result["compared"][k]["value"] <= limit, k
    metrics = result["metrics"]
    assert metrics["sel_live_tile_share"]["value"] == 1.0          # a view is one tile here
    # a CPU trace carries no scope and no program line: nothing under a device metric's name
    assert not {"sparse_step_mfu_pct", "sparse_attn_roofline", "index_device_ms", "select_device_ms",
                "index_scores_roofline", "select_top_k_roofline", "fused_step_device_ms"} & set(metrics)
    events = os.path.join(extended, "perfbench", "_work", "run-" + TINY_CELL, "telemetry", "events.jsonl")
    with open(events) as f:
        records = [json.loads(line) for line in f]
    sampled = [r["health"] for r in records if r.get("kind") == "step"
               and "sel_keys_per_query" in r.get("health", {})]
    assert sampled and all(h["sel_keys_per_query"] == pytest.approx(
        np.minimum(16, np.arange(64) + 1).mean()) for h in sampled)
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"]["select"] == {"topk": 16, "path": "xla"} and setup["moe"]["dispatch"] == "xla"


def _planted(fault):
    """A fault under the timed path: the program's own functions, changed."""
    import jax.numpy as jnp

    from moco_tpu.models import keye

    real_select = keye.top_k_selection

    def causal(scores):
        n = scores.shape[-1]
        return jnp.broadcast_to(jnp.tril(jnp.ones((n, n), bool)), scores.shape)

    def recent(scores, topk):
        at = jnp.arange(scores.shape[-1])
        return (causal(scores) & (at[None, :] > at[:, None] - topk)).astype(jnp.int8)

    def no_relu(q, k, w):
        s = jnp.einsum("bthd,bsd->bths", q, k, preferred_element_type=jnp.float32)
        return jnp.sum(s * w[..., None], 2)

    return {"select_all": (keye, "top_k_selection", lambda s, topk: causal(s).astype(jnp.int8)),
            "topk_half": (keye, "top_k_selection", lambda s, topk: real_select(s, topk // 2)),
            "recent": (keye, "top_k_selection", recent),
            "no_relu": (keye, "causal_scores", no_relu)}[fault]


def key_unchanged(real):
    """The momentum update left out: the step hands back the key encoder it got."""
    import jax

    def step(state, rows, lengths, n):
        kept = jax.tree.map(lambda x: x.copy(), state.params_k)
        state, metrics = real(state, rows, lengths, n)
        return state.replace(params_k=kept), metrics
    return step


@pytest.mark.parametrize("fault", ["select_all", "topk_half", "recent", "no_relu", "half_batch",
                                   "key_unchanged"])
def test_a_broken_timed_path_is_not_correct(extended, fault):
    """The six faults the cell's limits must catch, each planted under the timed
    path at test size."""
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
        assert over & {"keys_max", "keys_med", "grad1", "grad1_med"}, result["compared"]


def test_the_upper_readings_come_from_the_reference_alone(extended, tmp_path, capsys):
    """`calibrate_sparse.py`: controls and planted faults against the float32
    reference from the cell's own traffic, no trainer in the process, and the
    share of selected pairs the program picks otherwise."""
    from perfbench import calibrate_sparse

    rc = calibrate_sparse.main(
        ["--workload", TINY_CELL, "--seeds", "1", "--first-seed", "40", "--variants",
         "float8,half,fault_recent,selection", "--root", extended, "--manifest",
         "tests/perfbench/extra/tiny_manifest.json", "--out", str(tmp_path), "--platform", "cpu"])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert lines[0]["seed"] == 40 and lines[-1]["seeds"] == 1
    assert lines[0]["half"]["keys_max"] == pytest.approx(2 ** 0.5)     # the rows left out
    assert lines[0]["select_pair_share"] == 0.0     # in float32 the program picks the reference's pairs
    for name in ("float8", "half", "fault_recent"):
        assert any(lines[0][name][k] > limit for k, limit in TIGHT.items()), (name, lines[0][name])
