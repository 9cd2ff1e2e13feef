"""ISSUE 31, the program's side: the looped, weight-shared token encoder
(`models/ouro.py`) at a small size on the CPU (`ouro_tiny`: hidden 64, 2
layers, 4 heads of 16, MLP width 160, 3 passes, vocabulary 512). The loop
against the same layers written out, the family-neutral door of
`models/__init__.py`, the step's scopes and counters, and the one compiled
pass."""

import json
import re
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import looped_unrolled
from moco_tpu import models
from moco_tpu.models import ouro, sdar
from moco_tpu.telemetry import scopes

Z = ouro.OURO_SIZES["ouro_tiny"]


def tiny_config(**over):
    from moco_tpu.config import get_preset

    return get_preset("text-moco-v2-ouro").replace(
        arch="ouro_tiny", seq_len=16, batch_size=8, num_negatives=256,
        compute_dtype="float32", health_stride=1, **over)


def build_fused(config, devices):
    """The fused step as `train.py` builds it, its state, and one batch."""
    from moco_tpu.data import build_token_views_sharded, token_view_config_for
    from moco_tpu.data.datasets import SyntheticTokenDataset
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import (build_encoder, build_fused_step, build_optimizer,
                                     build_train_step)

    mesh = create_mesh(devices=devices)
    model = build_encoder(config)
    tx, sched = build_optimizer(config, 64)
    state = create_train_state(
        jax.random.key(0), model, tx, (config.batch_size // mesh.size, config.seq_len),
        config.num_negatives, config.embed_dim, input_dtype=jnp.int32)
    step_fn = build_train_step(config, model, tx, mesh, 64, sched)
    fused = build_fused_step(
        step_fn, build_token_views_sharded(token_view_config_for(config), mesh),
        jax.random.key(1))
    rows, _, lengths = SyntheticTokenDataset(16, 2 * config.seq_len, Z["vocab"]).get_batch(
        np.arange(config.batch_size))
    return fused, state, jnp.asarray(rows), jnp.asarray(lengths)


# -- the loop against the same layers written out -------------------------------------


def _encoders():
    ids = jax.random.randint(jax.random.key(3), (4, 16), 0, Z["vocab"])
    loop = ouro.build("ouro_tiny", num_classes=128, remat=True)
    params = loop.init(jax.random.key(1), ids)["params"]
    # make every scale and kernel its own number: at 1.0 a norm left out can hide
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(tree, [x + 0.1 * jax.random.normal(k, x.shape) * (x.ndim == 1)
                                       for x, k in zip(leaves, keys)])
    return ids, loop, params


def _loss(model, params, ids):
    return jnp.sum(jnp.square(model.apply({"params": params}, ids)))


def test_the_loop_is_the_stack_written_out_with_the_parameters_repeated():
    """Outputs equal, and the SHARED kernel's gradient is the sum over its
    `passes` copies in the stack written out `passes x L` deep."""
    ids, loop, params = _encoders()
    out_loop = loop.apply({"params": params}, ids)
    with mock.patch.object(ouro, "looped", looped_unrolled.written_out):
        deep = ouro.build("ouro_tiny", num_classes=128)
        repeated = dict(params, loop={
            (f"pass_{t}_" + name): leaf for t in range(Z["ut_steps"])
            for name, leaf in params["loop"].items()})
        assert set(repeated["loop"]) == set(deep.init(jax.random.key(0), ids)["params"]["loop"])
        assert len(repeated["loop"]) == Z["ut_steps"] * (Z["layers"] + 1)
        np.testing.assert_allclose(deep.apply({"params": repeated}, ids), out_loop,
                                   rtol=2e-5, atol=2e-6)
        g_deep = jax.grad(lambda p: _loss(deep, p, ids))(repeated)
    g_loop = jax.grad(lambda p: _loss(loop, p, ids))(params)
    flat_loop = dict(jax.tree_util.tree_flatten_with_path(g_loop["loop"])[0])
    for path, shared in flat_loop.items():
        name, rest = path[0].key, path[1:]
        copies = []
        for t in range(Z["ut_steps"]):
            leaf = g_deep["loop"][f"pass_{t}_{name}"]
            for k in rest:
                leaf = leaf[k.key]
            copies.append(np.asarray(leaf, np.float64))
        total = sum(copies)
        np.testing.assert_allclose(shared, total, rtol=2e-4, atol=1e-6 * np.abs(total).max(),
                                   err_msg=str(path))
        # and no single copy is the sum: every pass hands the weight a cotangent
        assert all(np.abs(c).max() > 0 for c in copies)
        assert np.abs(np.asarray(shared) - copies[-1]).max() > 1e-3 * np.abs(total).max(), path
    for name in ("embed", "fc", "fc_hidden"):
        for a, b in zip(jax.tree.leaves(g_loop[name]), jax.tree.leaves(g_deep[name])):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5 * float(np.abs(b).max()))


@pytest.mark.parametrize("fault", [None, "pass_short", "norm_once", "last_pass_grad"])
def test_the_unrolled_loop_with_shared_parameters_and_what_each_fault_moves(fault):
    """The helper the benchmark's tests plant faults with: without a fault it is
    the loop (same tree, same numbers); each fault moves the output or, for the
    gradient of the last pass alone, the gradient only."""
    ids, loop, params = _encoders()
    with mock.patch.object(ouro, "looped", looped_unrolled.unrolled(fault)):
        other = ouro.build("ouro_tiny", num_classes=128)
        out, grad = other.apply({"params": params}, ids), jax.grad(lambda p: _loss(other, p, ids))(params)
    want, g_want = loop.apply({"params": params}, ids), jax.grad(lambda p: _loss(loop, p, ids))(params)
    out_gap = float(jnp.abs(out - want).max() / jnp.abs(want).max())
    q, q_want = (g["loop"]["layer_0"]["attn"]["q"]["kernel"] for g in (grad, g_want))
    grad_gap = float(jnp.abs(q - q_want).max() / jnp.abs(q_want).max())
    if fault is None:
        assert out_gap < 2e-5 and grad_gap < 2e-4
    elif fault == "last_pass_grad":
        assert out_gap < 2e-5 and grad_gap > 0.05
    else:
        assert out_gap > 0.01


def test_remat_changes_no_number_and_the_counter_is_the_last_passes_move():
    ids, loop, params = _encoders()
    plain = ouro.build("ouro_tiny", num_classes=128, remat=False)
    np.testing.assert_array_equal(loop.apply({"params": params}, ids),
                                  plain.apply({"params": params}, ids))
    _, taps = loop.apply({"params": params}, ids, mutable=[ouro.LOOP_STATS])
    delta = np.asarray(taps[ouro.LOOP_STATS]["loop"]["pass_delta"])
    assert delta.shape == (Z["ut_steps"],) and (delta > 0).all() and np.isfinite(delta).all()
    # by hand: the states before and after the last pass, from a loop one pass shorter
    with mock.patch.dict(ouro.OURO_SIZES["ouro_tiny"], ut_steps=Z["ut_steps"] - 1):
        feat_short = ouro.build("ouro_tiny").apply({"params": params}, ids)
    feat = ouro.build("ouro_tiny").apply({"params": params}, ids)
    assert feat.shape == (4, Z["hidden"]) and float(jnp.abs(feat - feat_short).max()) > 1e-3


# -- the door ----------------------------------------------------------------------------


def test_the_door_answers_for_both_families_and_for_no_other():
    assert models.is_token_encoder("ouro_2p6b") and models.is_token_encoder("sdar_tiny")
    assert not models.is_token_encoder("resnet50") and not models.is_token_encoder("vit_small")
    assert models.has_router("sdar_30b_a3b") and not models.has_router("ouro_2p6b")
    assert models.held_vocab("ouro_2p6b") == 49152 and models.held_vocab("ouro_2p6b", 100) == 100
    assert models.held_vocab("sdar_30b_a3b", 18992) == 18992
    assert models.token_counters("ouro_tiny") == ((ouro.LOOP_STATS,), ouro.health)
    assert models.token_counters("sdar_tiny") == ((sdar.MOE_STATS,), sdar.health)
    assert isinstance(models.build_token_encoder("ouro_tiny", 8, layers=1), ouro.OuroEncoder)
    assert isinstance(models.build_token_encoder("sdar_tiny", 8, held=4), sdar.SDAREncoder)
    assert isinstance(models.build_backbone("ouro_tiny"), ouro.OuroEncoder)
    with pytest.raises(ValueError):
        models.token_sizes("ouro_9000")
    with pytest.raises(ValueError):
        models.build_token_encoder("ouro_tiny", 8, held=4)       # a dense stack holds no experts
    z = models.token_sizes("ouro_2p6b")
    assert (z["hidden"], z["heads"], z["kv_heads"], z["head_dim"], z["width"], z["vocab"],
            z["layers"], z["ut_steps"], z["rope_theta"], z["eps"]) == (
        2048, 16, 16, 128, 5632, 49152, 48, 4, 1e6, 1e-6)


def test_the_attention_path_names_rotary_alone_where_the_kernels_run(monkeypatch):
    assert models.attention_path("ouro_2p6b", 512) == {
        "path": "einsum", "tiles": 16, "tiles_skipped": 0, "qk_prep": "xla"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert models.attention_path("ouro_2p6b", 512) == {
        "path": "fused", "tiles": 16, "tiles_skipped": 6, "qk_prep": "rotary"}
    assert models.attention_path("ouro_tiny", 16)["path"] == "einsum"    # heads of 16 lanes
    # no per-head norm: no scale in the tree, on either path
    tree = jax.eval_shape(lambda: ouro.build("ouro_2p6b", 8, layers=1).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert set(tree["loop"]["layer_0"]["attn"]) == {"q", "k", "v", "o"}
    assert set(tree["loop"]["layer_0"]) == {"attn", "mlp", "norm1", "norm2", "norm3", "norm4"}
    assert set(tree["loop"]) == {"layer_0", "norm"}


def test_the_trainers_mfu_counts_a_layer_once_a_pass():
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry.mfu import model_fwd_flops, train_step_flops

    whole = get_preset("text-moco-v2-ouro")
    cut = whole.replace(num_hidden_layers=6)
    per_view = model_fwd_flops("ouro_2p6b", 0, embed_dim=128, mlp_head=True, seq_len=512,
                               num_hidden_layers=6)
    # ISSUE 31: a layer application is 104.9 MFLOP a token, 24 of them, 512 tokens a view
    assert per_view == pytest.approx(24 * 512 * 104.9e6, rel=0.002)
    assert train_step_flops(cut) == pytest.approx(4 * 16 * per_view)
    assert train_step_flops(cut) == pytest.approx(82.5e12, rel=0.003)
    assert train_step_flops(whole) == pytest.approx(8 * train_step_flops(cut), rel=0.01)


# -- the step: scopes, counters, one compiled pass --------------------------------------

_STEP: dict = {}


def fused_two_devices():
    if not _STEP:
        fused, state, rows, lengths = build_fused(tiny_config(remat=True), jax.devices()[:2])
        text = fused.lower(state, rows, lengths, 0).compile().as_text()
        _STEP.update(fused=fused, state=state, rows=rows, lengths=lengths,
                     names=re.findall(r'op_name="([^"]*)"', text))
    return _STEP


def components(op_name):
    return re.findall(r"[A-Za-z0-9_]+", op_name)


def test_looped_scope_names_are_distinct_plain_and_share_two_with_the_routed_encoder():
    names = scopes.LOOPED_SCOPES
    assert len(set(names)) == 4 and not set(names) & set(scopes.STEP_SCOPES + scopes.COLLECTIVE_SCOPES)
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names)
    assert set(names) & set(scopes.ENCODER_SCOPES) == {scopes.ATTN, scopes.EMBED_POOL}
    assert scopes.ENCODER_SCOPES == ("attn", "moe_router", "moe_dispatch", "moe_experts", "embed_pool")


@pytest.mark.parametrize("parent", [scopes.K_FWD, scopes.Q_FWD_BWD])
@pytest.mark.parametrize("scope", scopes.LOOPED_SCOPES)
def test_looped_scopes_nest_beneath_both_encoder_passes(scope, parent):
    own = [components(n) for n in fused_two_devices()["names"] if n.startswith("jit(fused_step)")]
    hits = [p for p in own if scope in p and parent in p]
    assert hits and all(p.index(parent) < p.index(scope) for p in hits)
    if parent == scopes.Q_FWD_BWD and scope != scopes.EMBED_POOL:
        backward = [n for n in fused_two_devices()["names"] if "transpose(" in n
                    and scope in components(n)]
        assert backward, scope


def test_every_instruction_of_a_layer_is_under_a_nested_scope_and_mlp_and_norm_are_siblings_of_attn():
    bare, nested = [], []
    for name in fused_two_devices()["names"]:
        parts = components(name)
        if "OuroEncoder" not in parts or scopes.LOSS_QUEUE in parts:
            continue
        inside = [p for p in parts if p in scopes.LOOPED_SCOPES]
        if not inside:
            bare.append(parts)
        # `attn`, `mlp` and `norm` never nest in one another
        if len(set(inside) & {scopes.ATTN, scopes.MLP, scopes.NORM}) > 1:
            nested.append(name)
    # under none of the four: the loop's own hand-over alone (the scan's counter,
    # its stack of layer inputs, the sums of cotangents where a layer's input
    # forks), nothing of a layer's or the closing norm's arithmetic
    assert bare and all("while" in p and not any(c.startswith(("layer_", "norm")) for c in p)
                        for p in bare), bare[:8]
    assert {p[-1] for p in bare} <= {"while", "add", "sub", "lt", "closed_call", "remat2", "add_any",
                                     "broadcast_in_dim",
                                     "dynamic_slice", "dynamic_update_slice"}
    assert not nested, nested[:8]


def _leaf(tree, path):
    for part in path:
        tree = tree[part]
    return np.asarray(tree)


MOVED = (("embed", "embedding"), ("loop", "layer_1", "mlp", "gate", "kernel"),
         ("loop", "layer_0", "attn", "q", "kernel"), ("loop", "layer_0", "norm2", "scale"),
         ("loop", "norm", "scale"))


def test_the_step_counts_its_passes_and_the_ema_moves_every_leaf():
    s = fused_two_devices()
    before = jax.device_get(s["state"].params_k)
    state, metrics = s["fused"](jax.tree.map(jnp.copy, s["state"]), s["rows"], s["lengths"], 0)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["h_ut_passes"]) == Z["ut_steps"]
    assert 0 < float(metrics["h_ut_pass_delta"]) < 10
    assert not [k for k in metrics if k.startswith("h_moe")]
    state, _ = s["fused"](state, s["rows"], s["lengths"], 1)   # q moved: now k follows
    after = jax.device_get(state.params_k)
    for path in MOVED:
        assert np.abs(_leaf(before, path) - _leaf(after, path)).max() > 0, path
    assert int(state.queue_ptr) == 16 and not state.batch_stats_q


def test_the_counters_are_off_with_the_health_stride():
    fused, state, rows, lengths = build_fused(tiny_config(remat=False).replace(health_stride=0),
                                              jax.devices()[:1])
    _, metrics = fused(state, rows, lengths, 0)
    assert not [k for k in metrics if k.startswith("h_")]


def _dot_generals(ut_steps):
    with mock.patch.dict(ouro.OURO_SIZES["ouro_tiny"], ut_steps=ut_steps):
        fused, state, rows, lengths = build_fused(tiny_config(remat=True), jax.devices()[:1])
        text = fused.lower(state, rows, lengths, 0).as_text()
    return text.count("dot_general"), text.count("stablehlo.while")


def test_the_step_program_holds_one_compiled_pass_whatever_the_number_of_passes():
    """The lowered step's count of `dot_general` does not grow with the passes:
    the loop is a `while` over one traced body, not `passes x L` layer bodies."""
    two, four = _dot_generals(2), _dot_generals(4)
    assert two == four and two[0] > 0 and two[1] >= 2      # the scan and its transpose at least
    with mock.patch.object(ouro, "looped", looped_unrolled.unrolled()):
        fused, state, rows, lengths = build_fused(tiny_config(remat=True), jax.devices()[:1])
        written = fused.lower(state, rows, lengths, 0).as_text().count("dot_general")
    assert written > 2 * two[0]                            # three passes written out


def test_the_cli_runs_the_text_preset_and_the_report_prints_the_loop(tmp_path):
    from moco_tpu import train

    train.main(["--preset", "text-moco-v2-ouro", "--arch", "ouro_tiny", "--seq-len", "16",
                "--batch-size", "8", "--num-negatives", "64", "--compute-dtype", "float32",
                "--epochs", "1", "--steps-per-epoch", "3", "--ckpt-dir", "", "--fake-devices", "1",
                "--health-stride", "1", "--telemetry-dir", str(tmp_path)])
    records = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 3 and steps[0]["health"]["ut_passes"] == Z["ut_steps"]
    assert steps[0]["health"]["ut_pass_delta"] > 0 and all(r.get("mfu", 1) != 0 for r in steps)
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"] == {"path": "einsum", "tiles": 1, "tiles_skipped": 0, "qk_prep": "xla"}
    import subprocess
    import sys

    out = subprocess.run([sys.executable, "tools/telemetry_report.py", str(tmp_path / "events.jsonl")],
                         capture_output=True, text=True, check=True).stdout
    assert f"loop: {Z['ut_steps']} pass(es) over the shared stack" in out
    assert "attention einsum" in out and "q/k prep xla" in out


@pytest.mark.parametrize("name, devices", [
    ("text-moco-v2-ouro", 1),            # the preset, at its configuration's 6 layers
    ("cell:ouro-2.6b-l6", 1),            # the benchmark's own configuration
    ("cell:ouro-2.6b-l6", 8),            # the key gather and gradient sync across devices
])
def test_step_program_lowers_for_tpu(name, devices, mesh8):
    """The looped program at the published widths exports for the TPU platform
    from the CPU, its kernels inside the scan's body: in each of the 6 layers of
    the ONE traced pass the attention kernel in the key forward, the query
    forward, its rematerialised twin and the backward, and the rotary kernel
    (no norm: `qk_rotary`) before each of them for q and for k. The counts are
    those of 6 layer bodies, not of 24 layer applications."""
    from step_lowering import cell_config, census_for_tpu

    layers = cell_config("ouro-2.6b-l6").num_hidden_layers
    assert layers == 6
    assert census_for_tpu(name, devices, mesh8, batch_size=8, num_hidden_layers=layers) == {
        "_fwd_kernel": 18, "_bwd_kernel": 6, "qk_rotary": 36, "qk_rotary_bwd": 12}
