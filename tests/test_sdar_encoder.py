"""ISSUE 27, the program's side: the routed token encoder (`models/sdar.py`)
at a small size on the CPU. The mask, the share of an expert layer against
the whole layer, the passes a skewed router takes, token rows through the
feed, the token views across meshes, the step's scopes and counters."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu import models
from moco_tpu.models import sdar
from moco_tpu.telemetry import scopes

Z = sdar.SDAR_SIZES["sdar_tiny"]


def tiny_config(**over):
    from moco_tpu.config import get_preset

    return get_preset("text-moco-v2-sdar").replace(
        arch="sdar_tiny", num_experts=4, vocab_size=64, seq_len=16, batch_size=8,
        num_negatives=256, compute_dtype="float32", health_stride=1, **over)


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
    rows, _, lengths = SyntheticTokenDataset(16, 2 * config.seq_len, 64).get_batch(
        np.arange(config.batch_size))
    return fused, state, jnp.asarray(rows), jnp.asarray(lengths)


@pytest.mark.parametrize("length", [4, 12])
def test_block_causal_is_causal_at_block_one_and_full_at_block_length(length):
    causal = np.tril(np.ones((length, length), bool))
    assert (np.asarray(sdar.block_causal_mask(length, 1)) == causal).all()
    assert np.asarray(sdar.block_causal_mask(length, length)).all()
    two = np.asarray(sdar.block_causal_mask(length, 2))
    assert two[0, 1] and not two[1, 2] and (two >= causal).all()


def _experts(held, dtype=jnp.float32, z=Z):
    return sdar.Experts(z["experts"], held, z["top_k"], z["expert_width"], dtype)


def _layer_params(seed=0, z=Z):
    """A whole expert layer's parameters (all 16 experts held) and tokens."""
    u = jax.random.normal(jax.random.key(seed), (96, z["hidden"]))
    params = _experts(z["experts"], z=z).init(jax.random.key(seed + 1), u)["params"]
    return params, u


def _share(params, j, held, z=Z):
    """The layer as the chip that holds experts `j*held .. (j+1)*held` sees it:
    the program holds the FIRST experts, so that chip's experts are renumbered
    to the front, the router's columns with them."""
    n = z["experts"]
    mine = np.arange(j * held, (j + 1) * held)
    perm = np.concatenate([mine, np.setdiff1d(np.arange(n), mine)])
    return {"router": {"kernel": params["router"]["kernel"][:, perm]},
            **{k: params[k][mine] for k in ("gate", "up", "down")}}


@pytest.mark.parametrize("arch, held", [("sdar_tiny", 2), ("sdar_tiny", 4), ("keye_tiny", 2)])
def test_the_shares_expert_outputs_add_up_to_the_uncut_layers(arch, held):
    """16 experts over 8 (or 4) chips: what every chip's share gives, added up,
    is the whole layer's result; and a share is smaller than the whole. Every
    routed family's sizes (`model-configs` section 4): the layer is one module."""
    z = models.token_sizes(arch)
    params, u = _layer_params(z=z)
    whole = _experts(z["experts"], z=z).apply({"params": params}, u)
    parts = [_experts(held, z=z).apply({"params": _share(params, j, held, z)}, u)
             for j in range(z["experts"] // held)]
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(parts[0]).max()) > 0
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-3


def test_a_skewed_router_takes_more_passes_and_drops_nothing():
    """Every token sent to the four held experts: 4 assignments a token where
    the buffer of one pass holds 2, so the second pass has to run, forward and
    backward; the result is the plain sum over the chosen experts."""
    params, u = _layer_params(3)
    held = 4
    skew = params["router"]["kernel"].at[:, :held].add(50.0 * jnp.sign(u.mean(0))[:, None])
    u = u + 2.0 * jnp.sign(u.mean(0))        # every token leans the same way
    p = dict(_share(params, 0, held), router={"kernel": skew})
    assert sdar.held_rows(96, Z["top_k"], Z["experts"], held) == 2 * 96

    def plain(p, u):
        # a share's router is a constant of the step; the logits still carry u's gradient
        r = jax.nn.softmax(u @ jax.lax.stop_gradient(p["router"]["kernel"]), -1)
        w, e = jax.lax.top_k(r, Z["top_k"])
        w = w / w.sum(-1, keepdims=True)
        y = jnp.einsum("etf,efd->etd",
                       jax.nn.silu(jnp.einsum("td,edf->etf", u, p["gate"]))
                       * jnp.einsum("td,edf->etf", u, p["up"]), p["down"])
        full = jnp.sum(jax.nn.one_hot(e, Z["experts"]) * w[..., None], 1)[:, :held]
        return jnp.einsum("etd,te->td", y, full)

    out, stats = _experts(held).apply({"params": p}, u, mutable=[sdar.MOE_STATS])
    counts = stats[sdar.MOE_STATS]["held_counts"]
    assert int(counts.sum()) == 4 * 96        # all four choices of every token are held here
    np.testing.assert_allclose(out, plain(p, u), rtol=2e-4, atol=2e-5)
    grad = jax.grad(lambda p, u: jnp.sum(_experts(held).apply({"params": p}, u) ** 2),
                    argnums=(0, 1))(p, u)
    want = jax.grad(lambda p, u: jnp.sum(plain(p, u) ** 2), argnums=(0, 1))(p, u)
    assert not np.any(grad[0]["router"]["kernel"]) and np.any(grad[1])
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4 * float(jnp.abs(b).max()))


def test_build_backbone_gives_the_third_family():
    from moco_tpu.models import build_backbone

    model = build_backbone("sdar_tiny")
    ids = jnp.zeros((2, 8), jnp.int32)
    feat = model.apply(model.init(jax.random.key(0), ids), ids)
    assert feat.shape == (2, Z["hidden"]) and isinstance(model, sdar.SDAREncoder)
    with pytest.raises(ValueError):
        sdar.build_sdar("sdar_tiny", held=17)


def test_token_rows_ride_the_feed_with_its_workers():
    """int32 rows and `[n, 1]` lengths through `Prefetcher`'s canvas pool (the
    multi-worker path) arrive as the data set gave them."""
    from moco_tpu.data import epoch_loader, epoch_permutation
    from moco_tpu.data.datasets import SyntheticTokenDataset
    from moco_tpu.data.stats import InputPipelineStats
    from moco_tpu.parallel.mesh import create_mesh

    ds = SyntheticTokenDataset(64, 24, 50, seed=2)
    mesh = create_mesh(devices=jax.devices()[:2])
    stats = InputPipelineStats()
    loader = epoch_loader(ds, 0, 7, 8, mesh, workers=4, stats=stats)
    order = epoch_permutation(len(ds), 0, 7, 8)
    try:
        for b, (rows, _labels, lengths) in enumerate(loader):
            assert rows.dtype == jnp.int32 and rows.shape == (8, 24) and lengths.shape == (8, 1)
            want = ds.get_batch(order[b * 8:(b + 1) * 8])
            assert (np.asarray(rows) == want[0]).all() and (np.asarray(lengths) == 24).all()
    finally:
        loader.close_quietly()
    assert b == 7 and stats.snapshot()["staged_bytes"] == 8 * 8 * (24 * 4 + 4 + 4)
    assert ds.rows.max() < 49                      # the last id is the views' mask id


def test_token_views_do_not_depend_on_the_mesh_and_crop_inside_the_document():
    from moco_tpu.data.augment import TokenViewConfig, build_token_views_sharded
    from moco_tpu.parallel.mesh import create_mesh

    cfg = TokenViewConfig(seq_len=16, mask_id=999)
    rows = jnp.asarray(np.arange(8 * 48).reshape(8, 48) % 900, jnp.int32)
    lengths = jnp.asarray([[48], [16], [20], [48], [32], [48], [17], [48]], jnp.int32)
    key = jax.random.key(5)
    one = build_token_views_sharded(cfg, create_mesh(devices=jax.devices()[:1]))(rows, key, lengths)
    two = build_token_views_sharded(cfg, create_mesh(devices=jax.devices()[:2]))(rows, key, lengths)
    for a, b in zip(one, two):
        assert (np.asarray(a) == np.asarray(b)).all()
    q, k = (np.asarray(v) for v in one)
    assert q.shape == k.shape == (8, 16) and not (q == k).all()
    masked = q == 999
    assert 0 < masked.mean() < 0.3
    for i in range(8):                             # a contiguous run of the document, inside its length
        seen = q[i][~masked[i]] - np.asarray(rows)[i, 0]
        start = seen[0] - np.flatnonzero(~masked[i])[0]
        assert (q[i][~masked[i]] == (np.asarray(rows)[i, 0] + start + np.flatnonzero(~masked[i])) % 900).all()
        assert 0 <= start <= int(lengths[i, 0]) - 16
    assert (q[1][~masked[1]] == np.asarray(rows)[1, :16][~masked[1]]).all()   # a 16-token document: the whole of it


# -- the step: scopes, counters, EMA ---------------------------------------------

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


def test_encoder_scope_names_are_distinct_plain_and_no_step_scope():
    names = scopes.ENCODER_SCOPES
    assert len(set(names)) == 5 and not set(names) & set(scopes.STEP_SCOPES + scopes.COLLECTIVE_SCOPES)
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names)


@pytest.mark.parametrize("parent", [scopes.K_FWD, scopes.Q_FWD_BWD])
@pytest.mark.parametrize("scope", scopes.ENCODER_SCOPES)
def test_encoder_scopes_nest_beneath_both_encoder_passes(scope, parent):
    own = [components(n) for n in fused_two_devices()["names"] if n.startswith("jit(fused_step)")]
    hits = [p for p in own if scope in p and parent in p]
    assert hits and all(p.index(parent) < p.index(scope) for p in hits)
    if parent == scopes.Q_FWD_BWD and scope != scopes.EMBED_POOL:
        backward = [n for n in fused_two_devices()["names"] if "transpose(" in n
                    and scope in components(n)]
        assert backward, scope


def test_every_instruction_of_the_encoder_is_under_a_nested_scope():
    """What lies under `k_fwd` or `q_fwd_bwd` (and not under `loss_queue`) and
    inside the encoder's module is under one of the five."""
    bare = []
    for name in fused_two_devices()["names"]:
        parts = components(name)
        if "SDAREncoder" not in parts or scopes.LOSS_QUEUE in parts:
            continue
        if not set(parts) & set(scopes.ENCODER_SCOPES):
            bare.append(name)
    assert not bare, bare[:8]
    assert not [n for n in fused_two_devices()["names"] if scopes.SHUFFLE_BN in components(n)]


def _leaf(tree, path):
    for part in path:
        tree = tree[part]
    return np.asarray(tree)


ROUTER, MOVED = ("layer_0", "moe", "router", "kernel"), (("embed", "embedding"),
                                                         ("layer_1", "moe", "gate"),
                                                         ("layer_0", "attn", "q", "kernel"))


def test_the_step_counts_its_routing_and_the_ema_moves_what_a_share_trains():
    s = fused_two_devices()
    before = jax.device_get(s["state"].params_k)
    state, metrics = s["fused"](jax.tree.map(jnp.copy, s["state"]), s["rows"], s["lengths"], 0)
    assert np.isfinite(float(metrics["loss"]))
    # 16 experts, top-4, 4 held: one assignment a token where routing is uniform
    assert 0.6 < float(metrics["h_moe_assign_per_token"]) < 1.6
    assert 1.0 <= float(metrics["h_moe_load_max_over_mean"]) < 4.0
    state, metrics2 = s["fused"](state, s["rows"], s["lengths"], 1)   # q moved: now k follows
    after, q = jax.device_get(state.params_k), jax.device_get(state.params_q)
    for path in MOVED:
        assert np.abs(_leaf(before, path) - _leaf(after, path)).max() > 0, path
    # a share (4 of 16 held) does not update its router: not by the gradient, not
    # by the decay, and so not by the momentum update either
    assert np.array_equal(_leaf(before, ROUTER), _leaf(q, ROUTER))
    assert np.array_equal(_leaf(before, ROUTER), _leaf(after, ROUTER))
    assert int(state.queue_ptr) == 16 and not state.batch_stats_q


def test_the_whole_layer_trains_its_router_and_the_ema_moves_it():
    fused, state, rows, lengths = build_fused(tiny_config(remat=False).replace(num_experts=16),
                                              jax.devices()[:1])
    before = jax.device_get(state.params_k)
    state, metrics = fused(state, rows, lengths, 0)
    assert float(metrics["h_moe_assign_per_token"]) == pytest.approx(4.0)   # every choice is held
    state, _ = fused(state, rows, lengths, 1)
    after = jax.device_get(state.params_k)
    for path in (ROUTER,) + MOVED:
        assert np.abs(_leaf(before, path) - _leaf(after, path)).max() > 0, path


def test_the_counters_are_off_with_the_health_stride():
    fused, state, rows, lengths = build_fused(tiny_config(remat=False).replace(health_stride=0),
                                              jax.devices()[:1])
    _, metrics = fused(state, rows, lengths, 0)
    assert not [k for k in metrics if k.startswith("h_")]


def test_the_trainers_mfu_counts_the_new_family():
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry.mfu import model_fwd_flops, train_step_flops

    whole = get_preset("text-moco-v2-sdar")
    cut = whole.replace(num_hidden_layers=4, num_experts=16, vocab_size=18992)
    # one view through four layers: projections 2 * 512 * 18.9e6 a layer, and so on
    per_view = model_fwd_flops("sdar_30b_a3b", 0, embed_dim=128, mlp_head=True, seq_len=512,
                               num_hidden_layers=4, num_experts=16)
    assert per_view == pytest.approx(0.106e12, rel=0.03)
    assert train_step_flops(cut) == pytest.approx(4 * 32 * per_view)
    assert train_step_flops(whole) > 20 * train_step_flops(cut)


def test_the_cli_runs_the_text_preset(tmp_path):
    from moco_tpu import train

    train.main(["--preset", "text-moco-v2-sdar", "--arch", "sdar_tiny", "--num-experts", "4",
                "--vocab-size", "64", "--seq-len", "16", "--batch-size", "8",
                "--num-negatives", "64", "--compute-dtype", "float32", "--epochs", "1",
                "--steps-per-epoch", "3", "--ckpt-dir", "", "--fake-devices", "1",
                "--telemetry-dir", str(tmp_path)])
    import json

    records = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 3 and "moe_assign_per_token" in steps[0]["health"]
    assert all(r.get("mfu", 1) != 0 for r in steps)


@pytest.mark.parametrize("name, devices", [
    ("text-moco-v2-sdar", 1),            # the preset, at its configuration's 4 layers
    ("cell:sdar-30b-a3b-ep8", 1),        # the benchmark's own configuration
    ("cell:sdar-30b-a3b-ep8", 8),        # the key gather and gradient sync across devices
])
def test_step_program_lowers_for_tpu(name, devices, mesh8):
    """The token program at the published widths exports for the TPU platform
    from the CPU and reaches `attn`'s Mosaic kernels 48 times: in each of 4
    layers the attention kernel in the key forward, the query forward, its
    rematerialised twin and the backward (16), and `norm_rotary` before each of
    them for q and for k (24 forward, 8 backward: ISSUE 30). And the routed
    layer's row movers (ISSUE 32), each at its three passes' call sites (the
    first pass, the small spill pass under `cond`, a whole pass under `scan`):
    `dispatch` in the three forwards (36), `combine` in the two whose result is
    used (24: the rematerialised one's is dead), their transposes in the
    backward (12 and 12). Since ISSUE 34 also the control of `sdar.KEPT`: the
    whole-row kernel names nothing, so the layer's `nn.remat` keeps what it
    kept and the rematerialised twin stays (the tiled pair's is gone:
    `test_keye_encoder.py`)."""
    import unittest.mock as mock

    from step_lowering import cell_config, census_for_tpu, named_config

    layers = cell_config("sdar-30b-a3b-ep8").num_hidden_layers
    config = named_config(name, batch_size=8, num_hidden_layers=layers)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        moe = models.dispatch_path(config.arch, 8 // devices, config.seq_len, config.num_experts)
    # the preset holds every expert: one pass
    sites = {"kernels": 1 + (moe["spill_rows"] > 0) + (moe["passes"] > 0), "xla": 0}[
        moe["dispatch"]]
    assert (name, devices, sites) in {("text-moco-v2-sdar", 1, 1), ("cell:sdar-30b-a3b-ep8", 1, 3),
                                      ("cell:sdar-30b-a3b-ep8", 8, 3)}
    movers = {"moe_gather": 12 * sites, "moe_combine": 8 * sites,
              "moe_gather_weighted": 4 * sites, "moe_gather_transpose": 4 * sites}
    assert census_for_tpu(name, devices, mesh8, batch_size=8, num_hidden_layers=layers) == {
        "_fwd_kernel": 12, "_bwd_kernel": 4, "qk_norm_rotary": 24, "qk_norm_rotary_bwd": 8,
        **{kernel: n for kernel, n in movers.items() if n}}
