"""FSDP sharding for the v3 step (ISSUE 15, parallel/fsdp.py).

Parity gates on the tiny-ViT CPU proxy over a 4-device single-process
mesh (the pod-math stand-in — the 2-proc multihost harness is dead at
seed in this container):

- `sharding=fsdp` with `grad_sync=fused|bucketed` is BITWISE-pinned
  against plain dp: the all-gather-on-use reconstructs the exact bits,
  the reduce is the same psum over the same device order, and the
  elementwise optimizer computes each shard identically;
- quantized (incl. the fsdp_tp multi-hop reduce) and demo extend their
  ISSUE-6 bounded-divergence gates to fsdp;
- per-device param+optimizer bytes measure ~1/N of dp (the acceptance
  inventory);
- dp→fsdp and 4→2-device restores land params exactly and gradsync EF
  state fresh-zero through the dialect-3 path (no silent slices).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import PretrainConfig
from moco_tpu.models.vit import ViT
from moco_tpu.parallel import fsdp
from moco_tpu.parallel.gradsync import GradSync
from moco_tpu.parallel.mesh import (
    FSDP_AXIS,
    create_mesh,
    create_mesh_2d,
    default_fsdp_size,
    mesh_for_config,
)
from moco_tpu.train_step import build_optimizer, build_train_step
from moco_tpu.v3_step import V3Model, create_v3_train_state

IMG, B = 16, 16
N_STEPS = 3


def tiny_config(**kw):
    base = dict(
        variant="v3", arch="vit_small", embed_dim=16, momentum_ema=0.99,
        momentum_ramp=True, temperature=0.2, optimizer="adamw", lr=1e-3,
        weight_decay=0.1, batch_size=B, epochs=2, warmup_epochs=0,
    )
    base.update(kw)
    return PretrainConfig(**base)


def _build(config, mesh):
    model = V3Model(
        ViT(patch_size=8, width=32, depth=2, num_heads=2, num_classes=None),
        embed_dim=16, hidden_dim=32,
    )
    tx, sched = build_optimizer(config, 4)
    state = create_v3_train_state(
        jax.random.key(0), model, tx, (B // mesh.size, IMG, IMG, 3)
    )
    state = GradSync(config, mesh.size).attach(state, mesh)
    state = fsdp.place_state(state, mesh, config)
    step = build_train_step(config, model, tx, mesh, 4, sched, state=state)
    return state, step


def _run(config, steps=N_STEPS):
    mesh = mesh_for_config(config, create_mesh(4))
    state, step = _build(config, mesh)
    losses = []
    for i in range(steps):
        x1 = jax.random.normal(jax.random.key(100 + i), (B, IMG, IMG, 3))
        x2 = jax.random.normal(jax.random.key(200 + i), (B, IMG, IMG, 3))
        state, m = step(state, x1, x2)
        losses.append(float(m["loss"]))
    return state, losses


@pytest.fixture(scope="module")
def dp_run():
    return _run(tiny_config())


@pytest.fixture(scope="module")
def fsdp_run():
    return _run(tiny_config(sharding="fsdp"))


# ---------------------------------------------------------------------------
# mesh / config surface
# ---------------------------------------------------------------------------


def test_mesh_for_config_shapes():
    m_dp = mesh_for_config(tiny_config(), create_mesh(4))
    assert tuple(m_dp.axis_names) == ("data",)
    m_f = mesh_for_config(tiny_config(sharding="fsdp"), create_mesh(4))
    assert tuple(m_f.axis_names) == ("data", FSDP_AXIS)
    assert m_f.devices.shape == (1, 4)
    m_t = mesh_for_config(tiny_config(sharding="fsdp_tp"), create_mesh(4))
    assert m_t.devices.shape == (2, 2)
    m_t3 = mesh_for_config(
        tiny_config(sharding="fsdp_tp", sharding_axis_size=4), create_mesh(8))
    assert m_t3.devices.shape == (2, 4)
    # device ORDER is preserved (the bitwise-parity anchor)
    assert list(m_f.devices.flat) == list(create_mesh(4).devices.flat)
    assert default_fsdp_size("fsdp", 8) == 8
    assert default_fsdp_size("fsdp_tp", 8) == 4


def test_config_rejects_bad_sharding():
    with pytest.raises(ValueError, match="sharding"):
        tiny_config(sharding="zero3")
    with pytest.raises(ValueError, match="variant"):
        PretrainConfig(variant="v2", sharding="fsdp")
    with pytest.raises(ValueError, match="collective_chunks"):
        tiny_config(collective_chunks=0)
    with pytest.raises(ValueError, match="zero_sharding"):
        tiny_config(sharding="fsdp", zero_sharding=True)
    with pytest.raises(ValueError, match="divide"):
        mesh_for_config(tiny_config(sharding="fsdp_tp", sharding_axis_size=3),
                        create_mesh(4))


# ---------------------------------------------------------------------------
# parity: fused/bucketed bitwise, quantized/demo bounded
# ---------------------------------------------------------------------------


def test_fsdp_fused_bitwise_parity_with_dp(dp_run, fsdp_run):
    sd, ld = dp_run
    sf, lf = fsdp_run
    assert ld == lf
    for a, b in zip(jax.tree.leaves(sd.params_q), jax.tree.leaves(sf.params_q),
                    strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(sd.opt_state), jax.tree.leaves(sf.opt_state),
                    strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fsdp_params_actually_sharded(fsdp_run):
    sf, _ = fsdp_run
    sharded = [
        leaf for leaf in jax.tree.leaves(sf.params_q)
        if hasattr(leaf, "sharding") and FSDP_AXIS in
        jax.tree.leaves(tuple(leaf.sharding.spec))
    ]
    assert sharded, "no param leaf is sharded over the fsdp axis"
    # a sharded leaf's per-device shard really is 1/4 of the logical array
    leaf = sharded[0]
    shard = leaf.addressable_shards[0]
    assert np.prod(shard.data.shape) == np.prod(leaf.shape) // 4


def test_fsdp_state_bytes_quarter_of_dp(dp_run, fsdp_run):
    sd, _ = dp_run
    sf, _ = fsdp_run
    inv_d = fsdp.state_bytes_per_device(sd)
    inv_f = fsdp.state_bytes_per_device(sf)
    # ~1/N with slack only for the replicated small leaves (biases, LN
    # scales, cls token, opt scalars)
    ratio = inv_f["state_bytes_per_device"] / inv_d["state_bytes_per_device"]
    assert ratio < 0.35, (inv_d, inv_f)
    assert inv_f["param_bytes_per_device"] < 0.35 * inv_d["param_bytes_per_device"]


def test_fsdp_bucketed_bitwise_parity_with_dp(dp_run):
    _, ld = dp_run
    sb, lb = _run(tiny_config(sharding="fsdp", grad_sync="bucketed",
                              grad_sync_bucket_mb=0.05))
    assert ld == lb


def test_fsdp_tp_fused_bitwise_parity_with_dp(dp_run):
    _, ld = dp_run
    st, lt = _run(tiny_config(sharding="fsdp_tp"))
    assert ld == lt
    # the hybrid 2x2 mesh shards params over fsdp=2 only
    inv = fsdp.state_bytes_per_device(st)
    assert inv["param_bytes_per_device"] > 0


def test_fsdp_quantized_bounded_divergence(dp_run):
    _, ld = dp_run
    sq, lq = _run(tiny_config(sharding="fsdp", grad_sync="quantized",
                              grad_sync_bucket_mb=0.05))
    assert all(np.isfinite(lq))
    for a, b in zip(ld, lq):
        assert abs(a - b) <= 0.05 * max(abs(a), 1.0), (ld, lq)
    # error feedback lives: [n_dev, ...] leading axis, nonzero residual
    acc = jax.tree.leaves(sq.gradsync["acc"])
    assert acc and all(a.shape[0] == 4 for a in acc)
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in acc)


def test_fsdp_tp_multihop_quantized_bounded_divergence(dp_run):
    """fsdp_tp + quantized = the DynamiQ-style two-hop reduce (exact
    intra-axis psum, int8 inter-axis hop): still inside the single-hop
    quantized band vs exact DP."""
    _, ld = dp_run
    _, lq = _run(tiny_config(sharding="fsdp_tp", grad_sync="quantized",
                             grad_sync_bucket_mb=0.05))
    assert all(np.isfinite(lq))
    for a, b in zip(ld, lq):
        assert abs(a - b) <= 0.05 * max(abs(a), 1.0), (ld, lq)


def test_fsdp_demo_bounded_divergence(dp_run):
    _, ld = dp_run
    sd_, ldm = _run(tiny_config(sharding="fsdp", grad_sync="demo",
                                grad_sync_topk=0.25))
    assert all(np.isfinite(ldm))
    for a, b in zip(ld, ldm):
        assert abs(a - b) <= 0.5 * max(abs(a), 1.0), (ld, ldm)
    acc = jax.tree.leaves(sd_.gradsync["acc"])
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in acc)


@pytest.mark.slow
def test_fsdp_chunked_gather_bitwise(dp_run):
    """FAST-style chunked key-gather scheduling is pure scheduling: the
    fsdp+chunks program reproduces the dp trajectory bit-for-bit. (The
    collective-level bitwise restitch pin is tier-1 in
    tests/test_collectives.py; this whole-step soak rides the slow
    suite for the tier-1 budget.)"""
    _, ld = dp_run
    _, lc = _run(tiny_config(sharding="fsdp", collective_chunks=2))
    assert ld == lc


# ---------------------------------------------------------------------------
# multihop reduce unit (region-level)
# ---------------------------------------------------------------------------


def test_gradsync_for_mesh_reports_multihop_bytes(mesh8):
    """GradSync.for_mesh binds the strategy to the mesh's own axes: on a
    2-D mesh with both axes > 1, quantized describe() carries the
    multihop block and counts BOTH hops — a hand-rolled
    GradSync(config, mesh.size) would under-report the wire bytes ~5x
    (the drift the driver's telemetry emits to BENCH)."""
    params = {"w": jnp.zeros((256,), jnp.float32)}
    config = tiny_config(sharding="fsdp_tp", grad_sync="quantized")
    mesh2d = create_mesh_2d(4, devices=list(mesh8.devices.flat))
    gs = GradSync.for_mesh(config, mesh2d)
    assert gs.multihop
    info = gs.describe(params)
    assert info["multihop"]["intra_size"] == 4
    assert info["multihop"]["inter_size"] == 2
    # int8 inter payload + f32 intra hop + one scale
    assert info["sync_bytes_per_step"] == 256 * 1 + 256 * 4 + 4
    assert info["multihop"]["intra_bytes_per_step"] == 256 * 4
    assert info["multihop"]["inter_bytes_per_step"] == 256 * 1 + 4
    # the (1, N) fsdp mesh has a size-1 outer axis: single-hop, same
    # accounting as plain dp quantized
    mesh_f = mesh_for_config(tiny_config(sharding="fsdp"), create_mesh(4))
    gs_f = GradSync.for_mesh(tiny_config(sharding="fsdp",
                                         grad_sync="quantized"), mesh_f)
    assert not gs_f.multihop
    assert gs_f.describe(params)["sync_bytes_per_step"] == 256 * 1 + 4


def test_multihop_reduce_means_match_single_hop(mesh8):
    """The two-hop quantized mean equals the single-hop quantized mean to
    int8 tolerance, and the per-device EF residuals reassemble to the full
    group residual exactly once (the /n_intra bookkeeping)."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.collectives import (
        multihop_quantized_psum_mean,
        quantized_psum_mean,
    )
    from jax import shard_map

    mesh2d = create_mesh_2d(4, devices=list(mesh8.devices.flat))
    x = jax.random.normal(jax.random.key(0), (8, 64))

    def multi(v):
        means, errs = multihop_quantized_psum_mean(
            [v.reshape(-1)], "data", "fsdp", 2, 4, "int8")
        return means[0], errs[0]

    def single(v):
        means, errs = quantized_psum_mean(
            [v.reshape(-1)], ("data", "fsdp"), 8, "int8")
        return means[0]

    fm = jax.jit(shard_map(
        multi, mesh=mesh2d,
        in_specs=(P(("data", "fsdp")),),
        out_specs=(P(), P(("data", "fsdp"))),
    ))
    fs = jax.jit(shard_map(
        single, mesh=mesh2d,
        in_specs=(P(("data", "fsdp")),), out_specs=P(),
    ))
    mean_m, errs = fm(x)
    mean_s = fs(x)
    true_mean = np.asarray(x).reshape(8, -1).mean(axis=0)
    # the multihop quantum is scale(intra SUM)/127/n_intra ≈ 0.006 on this
    # draw — both reduces must land within one quantum of the true mean
    np.testing.assert_allclose(np.asarray(mean_m), true_mean,
                               rtol=0.2, atol=0.01)
    np.testing.assert_allclose(np.asarray(mean_s), true_mean,
                               rtol=0.2, atol=0.01)
    # EF bookkeeping: summing every device's stored residual over an
    # intra group recovers the group residual once (stored as /n_intra)
    errs = np.asarray(errs)  # [8, 64] — one row per device
    group_sum = np.asarray(x).reshape(2, 4, -1).sum(axis=1)
    per_group_err = errs.reshape(2, 4, -1).sum(axis=1)
    # residual == intra_sum - dequantized wire value; bounded by one
    # quantum of the shared scale
    scale = np.abs(group_sum).max() / 127.0
    assert np.abs(per_group_err).max() <= scale * 1.01


# ---------------------------------------------------------------------------
# checkpoint: dp→fsdp, fsdp→dp, 4→2 — dialect 3
# ---------------------------------------------------------------------------


def test_dp_to_fsdp_restore_lands_sharded(tmp_path, dp_run):
    """A dp checkpoint restores straight into the fsdp placement (same
    logical tree, different NamedShardings): params bitwise, leaves
    sharded."""
    from moco_tpu.checkpoint import (
        checkpoint_manager,
        restore_checkpoint,
        save_checkpoint,
    )

    sd, _ = dp_run
    mgr = checkpoint_manager(str(tmp_path / "ckpt"))
    save_checkpoint(mgr, sd, 3, position=(0, 3), devices=4, sharding="dp")
    config = tiny_config(sharding="fsdp")
    mesh = mesh_for_config(config, create_mesh(4))
    fresh, _ = _build(config, mesh)
    target = fsdp.state_shardings(fresh, mesh, config)
    restored = restore_checkpoint(mgr, fresh, 3, sharding=target)
    assert int(restored.step) == int(sd.step)
    for a, b in zip(jax.tree.leaves(restored.params_q),
                    jax.tree.leaves(sd.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sharded = [
        leaf for leaf in jax.tree.leaves(restored.params_q)
        if hasattr(leaf, "sharding") and FSDP_AXIS in
        jax.tree.leaves(tuple(leaf.sharding.spec))
    ]
    assert sharded, "restore dropped the fsdp placement"
    from moco_tpu.checkpoint import read_recorded_sharding

    assert read_recorded_sharding(str(tmp_path / "ckpt"), 3) == "dp"


def test_fsdp_4_to_2_restore_rebuilds_ef_fresh_zero(tmp_path):
    """The elastic 4→2 leg under sharding=fsdp: a quantized 4-device fsdp
    checkpoint restored by a 2-device fsdp run — params exact, the
    [4, ...] accumulators rebuilt fresh-zero on the new mesh (the PR 11
    silent-slice guard, now exercised with the sharded layout)."""
    from moco_tpu.checkpoint import (
        checkpoint_manager,
        maybe_resume,
        save_checkpoint,
    )

    config = tiny_config(sharding="fsdp", grad_sync="quantized",
                         grad_sync_bucket_mb=0.05)
    mesh4 = mesh_for_config(config, create_mesh(4))
    state4, _ = _build(config, mesh4)
    # non-zero accumulators: the restore must DISCARD them, not slice them
    state4 = state4.replace(
        gradsync=jax.tree.map(jnp.ones_like, state4.gradsync))
    mgr = checkpoint_manager(str(tmp_path / "ckpt"))
    save_checkpoint(mgr, state4, 5, position=(0, 5), devices=4,
                    sharding="fsdp")
    mesh2 = mesh_for_config(config, create_mesh(2))
    fresh2, _ = _build(config, mesh2)
    target = fsdp.state_shardings(fresh2, mesh2, config)
    restored = maybe_resume(mgr, fresh2, "auto", sharding=target)
    assert int(restored.step) == int(state4.step)
    for a, b in zip(jax.tree.leaves(restored.params_q),
                    jax.tree.leaves(state4.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for leaf in jax.tree.leaves(restored.gradsync["acc"]):
        assert leaf.shape[0] == 2              # the NEW mesh's accumulator
        assert float(jnp.max(jnp.abs(leaf))) == 0.0  # fresh zeros, no slice


# ---------------------------------------------------------------------------
# telemetry: the sharding event renders, MFU is labeled per mode
# ---------------------------------------------------------------------------


def test_report_renders_sharding_line_and_mfu_label(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "telemetry_report.py"),
    )
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    records = [
        {"kind": "run_start", "name": "t", "variant": "v3", "arch": "vit_s",
         "batch_size": 256, "n_chips": 8, "n_procs": 1, "sharding": "fsdp"},
        {"kind": "event", "event": "sharding", "mode": "fsdp",
         "mesh_shape": {"data": 1, "fsdp": 8},
         "param_bytes_per_device": 4 * 2**20,
         "opt_bytes_per_device": 8 * 2**20,
         "state_bytes_per_device": 12 * 2**20},
    ]
    for s in range(1, 5):
        records.append({"kind": "step", "step": s, "step_s": 0.1,
                        "data_s": 0.01, "host_s": 0.005, "mfu": 0.3})
    summary = report.summarize(records)
    assert summary["sharding"]["mode"] == "fsdp"
    assert summary["sharding"]["param_bytes_per_device"] == 4 * 2**20
    text = report.render(summary)
    assert "sharding: fsdp" in text
    assert "params 4.00 MiB/device" in text
    assert "MFU [fsdp]:" in text
    # sharding is a routine event, not an incident (the grad_sync rule)
    assert summary["incidents_total"] == 0


def test_mfu_estimator_carries_sharding_mode():
    from moco_tpu.telemetry.mfu import MFUEstimator

    est = MFUEstimator.for_config(tiny_config(sharding="fsdp"), 8, "v5e")
    assert est.sharding == "fsdp"
    est_dp = MFUEstimator.for_config(tiny_config(), 8, "v5e")
    assert est_dp.sharding == "dp"
    # the analytic FLOPs basis is layout-invariant
    assert est.flops_per_step == est_dp.flops_per_step


# ---------------------------------------------------------------------------
# driver: fsdp through train(), elastic resize drill with sharding=fsdp
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fsdp_through_driver_and_resume(mesh8, tmp_path):
    """End-to-end: a short fsdp driver run lands the `sharding` telemetry
    event + sidecar stamp, and `--resume auto` restores into the sharded
    placement (dialect 3) bit-faithfully."""
    import json

    from moco_tpu.config import get_preset
    from moco_tpu.train import train

    tel = str(tmp_path / "tel")
    os.makedirs(tel, exist_ok=True)
    cfg = get_preset("imagenet-moco-v3-vits").replace(
        arch="vit_tiny", compute_dtype="float32", image_size=32,
        batch_size=16, embed_dim=16, dataset="synthetic", warmup_epochs=0,
        lr=1e-3, base_lr=0.0, epochs=2, steps_per_epoch=3, sharding="fsdp",
        knn_monitor=False, ckpt_dir=str(tmp_path / "ckpt"), print_freq=2,
        telemetry_dir=tel, telemetry_stride=2, telemetry_flush_steps=2,
    )
    state_a, _ = train(cfg.replace(ckpt_dir=""), mesh8)       # 6 straight
    state_mid, _ = train(cfg, mesh8, max_steps=3)             # 3 + save
    assert int(state_mid.step) == 3
    state_b, _ = train(cfg.replace(resume="auto"), mesh8)     # resume to 6
    assert int(state_a.step) == int(state_b.step) == 6
    for a, b in zip(jax.tree.leaves(state_a.params_q),
                    jax.tree.leaves(state_b.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    events = [json.loads(line) for line in
              open(os.path.join(tel, "events.jsonl"))]
    sh = [e for e in events if e.get("event") == "sharding"]
    assert sh and sh[0]["mode"] == "fsdp"
    assert sh[0]["param_bytes_per_device"] > 0
    gs = [e for e in events if e.get("event") == "grad_sync"]
    assert gs and gs[0]["sharding"] == "fsdp"
    from moco_tpu.checkpoint import read_recorded_sharding

    assert read_recorded_sharding(cfg.ckpt_dir, 3) == "fsdp"


def _fsdp_drill_argv(tdir, ckpt_dir, devices):
    import sys

    return [
        sys.executable, "-m", "moco_tpu.train",
        "--preset", "imagenet-moco-v3-vits", "--fake-devices", str(devices),
        "--arch", "vit_tiny", "--dataset", "synthetic",
        "--compute-dtype", "float32", "--image-size", "32",
        "--batch-size", "16", "--embed-dim", "16", "--lr", "1e-3",
        "--base-lr", "0", "--warmup-epochs", "0",
        "--epochs", "4", "--steps-per-epoch", "4", "--print-freq", "1",
        "--knn-monitor", "false", "--watchdog-secs", "0",
        "--sharding", "fsdp", "--grad-sync", "quantized",
        "--telemetry-dir", str(tdir), "--telemetry-flush-steps", "4",
        "--heartbeat-secs", "0.05", "--ckpt-dir", str(ckpt_dir),
    ]


@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_resize_drill_4_to_2_with_fsdp(tmp_path):
    """The PR 11 resize drill under sharding=fsdp: a supervised 4-device
    fsdp run resizes to 2 devices mid-run (chaos `resize_at_step`) with
    zero manual steps — the relaunch restores the SHARDED state onto the
    new mesh through the dialect-3 tree restore, quantized EF restarts
    fresh-zero, and the final loss matches an uninterrupted 4-device run
    within the gradsync shim's bounded-divergence tolerance (the v3 step
    math is mesh-size-invariant at fixed global batch)."""
    import json
    import subprocess

    from moco_tpu.resilience.supervisor import (
        CLASS_CLEAN,
        CLASS_RESIZE,
        RestartPolicy,
        Supervisor,
        read_events_tail,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MOCO_TPU_NO_CACHE"] = "1"
    env.pop("MOCO_TPU_CHAOS", None)
    env.pop("MOCO_TPU_CHAOS_STATE", None)

    def losses_of(events_path):
        out = {}
        for rec in read_events_tail(events_path, max_bytes=1 << 22):
            if rec.get("kind") == "step" and "loss" in rec:
                out[int(rec["step"])] = float(rec["loss"])
        return out

    # uninterrupted 4-device reference
    ref_t, ref_ckpt = tmp_path / "ref_t", tmp_path / "ref_ckpt"
    proc = subprocess.run(
        _fsdp_drill_argv(ref_t, ref_ckpt, 4), env=env,
        capture_output=True, text=True, timeout=900, cwd=repo,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    ref_losses = losses_of(os.path.join(str(ref_t), "events.jsonl"))
    assert 16 in ref_losses

    sup_t, sup_ckpt = tmp_path / "sup_t", tmp_path / "sup_ckpt"
    sup_t.mkdir()
    chaos_env = dict(env, MOCO_TPU_CHAOS="resize_at_step=5,devices=2",
                     MOCO_TPU_CHAOS_STATE=str(tmp_path / "chaos_state"))
    sup = Supervisor(
        _fsdp_drill_argv(sup_t, sup_ckpt, 4),
        telemetry_dir=str(sup_t), ckpt_dir=str(sup_ckpt), env=chaos_env,
        policy=RestartPolicy(
            max_restarts=3, heartbeat_stale_secs=60.0,
            startup_grace_secs=600.0, term_grace_secs=3.0,
            backoff_base_secs=0.1, backoff_max_secs=1.0, poll_secs=0.25,
        ),
        seed=0,
    )
    result = sup.run()
    assert result.final_class == CLASS_CLEAN, result
    assert result.classifications == [CLASS_RESIZE, CLASS_CLEAN], result
    relaunches = [r for r in sup.incidents if r["event"] == "resize_relaunch"]
    assert [(r["devices_from"], r["devices_to"]) for r in relaunches] == \
        [(4, 2)]
    events_path = os.path.join(str(sup_t), "events.jsonl")
    records = read_events_tail(events_path, max_bytes=1 << 22)
    # the EF state restarted fresh-zero at the mesh hop
    dialect = [r for r in records if r.get("kind") == "event"
               and r.get("event") == "ckpt-dialect"]
    assert dialect, "no ckpt-dialect event at the mesh hop"
    sup_losses = losses_of(events_path)
    assert 16 in sup_losses, sorted(sup_losses)
    # pre-resize leg: same program, same data — bitwise
    for step in range(1, 5):
        assert sup_losses[step] == ref_losses[step], step
    final_ref, final_sup = ref_losses[16], sup_losses[16]
    assert abs(final_sup - final_ref) <= 0.05 * max(abs(final_ref), 1.0), (
        f"final loss diverged past the shim tolerance: "
        f"ref={final_ref} resized={final_sup}"
    )
    # the resized leg really ran fsdp on the 2-device mesh
    sh_events = [r for r in records if r.get("event") == "sharding"]
    assert sh_events[-1]["mode"] == "fsdp"
    assert sh_events[-1]["mesh_shape"] == {"data": 1, "fsdp": 2}
    with open(os.path.join(str(sup_t), "heartbeat.json")) as f:
        assert json.load(f)["phase"] == "run_end"


@pytest.mark.chaos
def test_driver_chaos_resize_with_fsdp(mesh8, tmp_path):
    """The PR 11 resize drill under sharding=fsdp: a chaos resize request
    mid-run writes the elastic checkpoint with the sharding stamp and
    exits through the resized path."""
    import json

    from moco_tpu.config import get_preset
    from moco_tpu.resilience.chaos import ChaosPlan, chaos_context
    from moco_tpu.resilience.resize import consume_resize_request
    from moco_tpu.train import train

    tdir = tmp_path / "telemetry"
    cfg = get_preset("imagenet-moco-v3-vits").replace(
        arch="vit_tiny", compute_dtype="float32", image_size=32,
        batch_size=16, embed_dim=16, dataset="synthetic", warmup_epochs=0,
        lr=1e-3, base_lr=0.0, epochs=3, steps_per_epoch=3, sharding="fsdp",
        knn_monitor=False, ckpt_dir=str(tmp_path / "ckpt"), print_freq=1000,
        telemetry_dir=str(tdir), heartbeat_secs=0.0,
    )
    with chaos_context(ChaosPlan(resize_at_step=4, resize_devices=2)):
        _state, metrics = train(cfg, mesh8)
    assert metrics.get("resized") is True
    from moco_tpu.checkpoint import read_recorded_sharding
    from moco_tpu.resilience.resize import read_recorded_devices

    assert read_recorded_devices(cfg.ckpt_dir) == (4, 8)
    assert read_recorded_sharding(cfg.ckpt_dir, 4) == "fsdp"
    req = consume_resize_request(str(tdir))
    assert req is not None and req.devices == 2
    with open(tdir / "heartbeat.json") as f:
        hb = json.load(f)
    assert hb["phase"] == "resize_exit" and hb["step"] == 4
