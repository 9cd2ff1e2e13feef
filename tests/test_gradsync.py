"""Communication-efficient gradient sync (ISSUE 6, parallel/gradsync.py).

Parity gates on the tiny CPU proxy, over the 8-fake-device mesh (the
single-process stand-in for pod math — the 2-proc multihost harness is dead
at seed in this container):

- `bucketed` is BITWISE-pinned against the fused exact-DP reduce (same adds
  in the same element order; only the issue schedule differs);
- `quantized` and `demo` pass bounded loss-divergence gates over N steps —
  compressed DP is approximate by design, so the gate is a band, not
  equality;
- the per-leaf dtype policy handles integer and None leaves (the
  `_pmean_grads` regression the ISSUE calls out);
- the per-device accumulators checkpoint and resume exactly, and a
  dialect-1 checkpoint (no gradsync leaves) restores with fresh zeros.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from moco_tpu.config import PretrainConfig
from moco_tpu.parallel.gradsync import GradSync, leaf_wire_dtype
from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.train_state import create_train_state
from moco_tpu.train_step import build_encoder, build_optimizer, build_train_step
from jax import shard_map

B, IMG, DIM, K = 16, 16, 16, 64


def _config(**kw):
    base = dict(
        variant="v1", arch="resnet_tiny", cifar_stem=True, num_negatives=K,
        embed_dim=DIM, batch_size=B, epochs=2, lr=0.1,
    )
    base.update(kw)
    return PretrainConfig(**base)


def _build(mesh, config):
    model = build_encoder(config)
    tx, sched = build_optimizer(config, 8)
    state = create_train_state(
        jax.random.key(0), model, tx, (B // mesh.size, IMG, IMG, 3), K, DIM
    )
    state = GradSync(config, mesh.size).attach(state, mesh)
    step = build_train_step(config, model, tx, mesh, 8, sched)
    return state, step


def _run(mesh, config, steps=1):
    state, step = _build(mesh, config)
    losses = []
    for i in range(steps):
        im_q = jax.random.normal(jax.random.key(100 + i), (B, IMG, IMG, 3))
        im_k = jax.random.normal(jax.random.key(200 + i), (B, IMG, IMG, 3))
        state, metrics = step(state, im_q, im_k)
        losses.append(float(metrics["loss"]))
    return state, losses, metrics


# ---------------------------------------------------------------------------
# bucketed: bitwise parity with exact DP
# ---------------------------------------------------------------------------


def test_bucketed_bitwise_parity_with_fused(mesh8):
    sf, lf, mf = _run(mesh8, _config(grad_sync="fused"), steps=2)
    sb, lb, mb = _run(
        mesh8, _config(grad_sync="bucketed", grad_sync_bucket_mb=0.05), steps=2
    )
    assert lf == lb
    for a, b in zip(jax.tree.leaves(sf.params_q), jax.tree.leaves(sb.params_q),
                    strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(sf.queue), np.asarray(sb.queue))


def test_bucketed_bf16_matches_fused_bf16(mesh8):
    """The legacy grad_allreduce_dtype policy rides through both dense
    modes identically (wire casts happen per leaf, before concatenation)."""
    sf, lf, _ = _run(
        mesh8, _config(grad_sync="fused", grad_allreduce_dtype="bfloat16"),
        steps=2,
    )
    sb, lb, _ = _run(
        mesh8,
        _config(grad_sync="bucketed", grad_allreduce_dtype="bfloat16",
                grad_sync_bucket_mb=0.05),
        steps=2,
    )
    assert lf == lb
    for a, b in zip(jax.tree.leaves(sf.params_q), jax.tree.leaves(sb.params_q),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_bucket_plan_respects_budget_and_covers_all_leaves(mesh8):
    config = _config(grad_sync="bucketed", grad_sync_bucket_mb=0.01)
    gs = GradSync(config, mesh8.size)
    model = build_encoder(config)
    variables = model.init(jax.random.key(0), jnp.zeros((1, IMG, IMG, 3)),
                           train=False)
    gs.plan(variables["params"])
    buckets = gs._buckets()
    planned = sorted(p.index for b in buckets for p in b)
    assert planned == list(range(len(jax.tree.leaves(variables["params"]))))
    budget = 0.01 * 2**20
    for b in buckets:
        nbytes = sum(p.size * 4 for p in b)
        # a single oversized leaf may exceed the budget alone; multi-leaf
        # buckets must not
        assert len(b) == 1 or nbytes <= budget


# ---------------------------------------------------------------------------
# quantized: bounded divergence + error feedback
# ---------------------------------------------------------------------------

N_DIVERGENCE_STEPS = 5


def test_quantized_int8_bounded_divergence(mesh8):
    sf, lf, _ = _run(mesh8, _config(grad_sync="fused"),
                     steps=N_DIVERGENCE_STEPS)
    sq, lq, _ = _run(
        mesh8,
        _config(grad_sync="quantized", grad_sync_bucket_mb=0.05),
        steps=N_DIVERGENCE_STEPS,
    )
    assert all(np.isfinite(lq))
    # loss curves track exact DP within a band (int8 + shared scale + EF)
    for a, b in zip(lf, lq):
        assert abs(a - b) <= 0.05 * max(abs(a), 1.0), (lf, lq)
    # ...but the compression really happened: params are NOT bitwise equal
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(sf.params_q),
                        jax.tree.leaves(sq.params_q))
    )
    # and the error-feedback accumulator carries a nonzero residual with the
    # per-device leading axis
    acc = jax.tree.leaves(sq.gradsync["acc"])
    assert all(a.shape[0] == mesh8.size for a in acc)
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in acc)


def test_quantized_per_leaf_scales_avoid_starvation(mesh8):
    """Leaves whose gradients are orders of magnitude below the bucket's
    absmax must still transmit: scales are per LEAF (pmax-shared), not per
    bucket — a bucket-wide scale would round the small leaf to all-zeros on
    the wire every step."""
    config = _config(grad_sync="quantized", grad_sync_bucket_mb=64.0)
    gs = GradSync(config, mesh8.size)
    tree = {"big": jnp.full((64,), 0.1, jnp.float32),
            "small": jnp.full((64,), 1e-5, jnp.float32)}
    acc = {"acc": jax.tree.map(
        lambda x: jnp.zeros((mesh8.size,) + x.shape, jnp.float32), tree)}

    def region(t, a, step):
        payload, new_acc, _ = gs.region_reduce(t, a, step)
        return payload

    fn = shard_map(region, mesh=mesh8,
                   in_specs=(P(), P(DATA_AXIS), P()), out_specs=P())
    out = jax.jit(fn)(tree, acc, jnp.int32(0))
    # both leaves share one bucket (64 MiB budget), yet the small leaf's
    # reduced value is nonzero and within int8 tolerance of its true mean
    np.testing.assert_allclose(np.asarray(out["small"]), 1e-5, rtol=0.02)
    np.testing.assert_allclose(np.asarray(out["big"]), 0.1, rtol=0.02)


def test_quantized_bf16_bounded_divergence(mesh8):
    _, lf, _ = _run(mesh8, _config(grad_sync="fused"), steps=3)
    _, lq, _ = _run(
        mesh8,
        _config(grad_sync="quantized", grad_sync_quant_dtype="bfloat16"),
        steps=3,
    )
    assert all(np.isfinite(lq))
    for a, b in zip(lf, lq):
        assert abs(a - b) <= 0.02 * max(abs(a), 1.0), (lf, lq)


# ---------------------------------------------------------------------------
# demo: decoupled momentum, sparse sync, cadence
# ---------------------------------------------------------------------------


def test_demo_bounded_divergence(mesh8):
    _, lf, _ = _run(mesh8, _config(grad_sync="fused"),
                    steps=N_DIVERGENCE_STEPS)
    sd, ld, _ = _run(
        mesh8,
        _config(grad_sync="demo", grad_sync_topk=0.25,
                grad_sync_demo_beta=0.9),
        steps=N_DIVERGENCE_STEPS,
    )
    assert all(np.isfinite(ld))
    # demo is NOT an approximation of SGD — the gate is a band around the
    # exact-DP curve wide enough for the decoupled update, tight enough to
    # catch a frozen or exploding encoder
    for a, b in zip(lf, ld):
        assert abs(a - b) <= 0.5 * max(abs(a), 1.0), (lf, ld)
    # the local momentum carries the untransmitted residue
    acc = jax.tree.leaves(sd.gradsync["acc"])
    assert any(float(jnp.max(jnp.abs(a))) > 0 for a in acc)


def test_demo_cadence_skips_sync_on_off_steps(mesh8):
    """With cadence=2 and a memoryless optimizer the off-step hands the
    optimizer an all-zero delta: params must not move, while the sync step
    must move them — pinned this way because byte savings are invisible on
    the CPU backend but a zero update is not."""
    config = _config(
        grad_sync="demo", grad_sync_cadence=2, grad_sync_topk=0.25,
        sgd_momentum=0.0, weight_decay=0.0,
    )
    state, step = _build(mesh8, config)
    im = lambda k: jax.random.normal(jax.random.key(k), (B, IMG, IMG, 3))
    s1, _ = step(state, im(1), im(2))        # step 0: sync
    p0 = [np.asarray(x) for x in jax.tree.leaves(s1.params_q)]
    s2, _ = step(s1, im(3), im(4))           # step 1: off — no sync, no move
    p1 = [np.asarray(x) for x in jax.tree.leaves(s2.params_q)]
    for a, b in zip(p0, p1, strict=True):
        np.testing.assert_array_equal(a, b)
    s3, _ = step(s2, im(5), im(6))           # step 2: sync again
    assert any(
        not np.array_equal(np.asarray(a), b)
        for a, b in zip(jax.tree.leaves(s3.params_q), p1)
    )


def test_demo_params_stay_replicated_consistent(mesh8):
    """The DP-safety invariant: after sparse merges every device applies
    the identical update (the merge is an outer-level replicated
    computation), so a fully-addressable param leaf has identical shards."""
    sd, _, _ = _run(mesh8, _config(grad_sync="demo", grad_sync_topk=0.25),
                    steps=2)
    leaf = jax.tree.leaves(sd.params_q)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


# ---------------------------------------------------------------------------
# per-leaf dtype policy (the `_pmean_grads` regression)
# ---------------------------------------------------------------------------


def test_wire_dtype_policy():
    assert leaf_wire_dtype(jnp.dtype(jnp.float32), "float32") == jnp.float32
    assert leaf_wire_dtype(jnp.dtype(jnp.bfloat16), "float32") == jnp.bfloat16
    assert leaf_wire_dtype(jnp.dtype(jnp.float32), "bfloat16") == jnp.bfloat16
    assert leaf_wire_dtype(jnp.dtype(jnp.int32), "bfloat16") == jnp.int32
    with pytest.raises(ValueError, match="grad_allreduce_dtype"):
        leaf_wire_dtype(jnp.dtype(jnp.float32), "float16")


@pytest.mark.parametrize("mode", ["fused", "bucketed"])
@pytest.mark.parametrize("allreduce_dtype", ["float32", "bfloat16"])
def test_integer_and_none_leaves_reduce_exactly(mesh8, mode, allreduce_dtype):
    """Integer leaves are SUMMED exactly (never averaged, never cast) and
    None leaves pass through structurally; a bf16 float leaf keeps its own
    dtype after the reduce (the old code silently widened it to f32)."""
    config = _config(grad_sync=mode, grad_allreduce_dtype=allreduce_dtype,
                     grad_sync_bucket_mb=0.001)
    gs = GradSync(config, mesh8.size)

    def region(tree, step):
        payload, state, probe = gs.region_reduce(tree, {}, step)
        return payload

    fn = shard_map(
        region, mesh=mesh8,
        in_specs=(P(), P()), out_specs=P(),
    )
    tree = {
        "w": jnp.full((8, 3), 2.0, jnp.float32),
        "h": jnp.full((4,), 1.5, jnp.bfloat16),
        "count": jnp.asarray([3, 7], jnp.int32),
        "none": None,
    }
    out = jax.jit(fn)(tree, jnp.int32(0))
    assert out["none"] is None
    assert out["count"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out["count"]),
                                  np.asarray([24, 56]))  # 8 devices × exact
    assert out["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0, rtol=1e-6)
    assert out["h"].dtype == jnp.bfloat16  # NOT widened to f32
    np.testing.assert_allclose(np.asarray(out["h"], np.float32), 1.5,
                               rtol=1e-2)


# ---------------------------------------------------------------------------
# config validation + byte accounting
# ---------------------------------------------------------------------------


def test_config_rejects_bad_gradsync_knobs():
    with pytest.raises(ValueError, match="grad_sync"):
        _config(grad_sync="turbo")
    with pytest.raises(ValueError, match="grad_sync_quant_dtype"):
        _config(grad_sync_quant_dtype="int4")
    with pytest.raises(ValueError, match="grad_sync_cadence"):
        _config(grad_sync_cadence=0)
    with pytest.raises(ValueError, match="grad_sync_topk"):
        _config(grad_sync_topk=0.0)
    with pytest.raises(ValueError, match="grad_sync_bucket_mb"):
        _config(grad_sync_bucket_mb=0)


def test_sync_bytes_accounting(mesh8):
    params = {"a": jnp.zeros((100,), jnp.float32),
              "b": jnp.zeros((10, 10), jnp.float32)}
    fused = GradSync(_config(grad_sync="fused"), 8).describe(params)
    assert fused["sync_bytes_per_step"] == 200 * 4
    q = GradSync(_config(grad_sync="quantized"), 8).describe(params)
    assert q["sync_bytes_per_step"] == 200 * 1 + 4 * 2  # 1 B/elem + scale/leaf
    demo_cfg = _config(grad_sync="demo", grad_sync_topk=0.05,
                       grad_sync_cadence=4)
    demo = GradSync(demo_cfg, 8).describe(params)
    assert demo["sync_bytes_per_step"] == 2 * int(5 * 8 / 4)  # k=5 per leaf
    # the compressed modes really cut the wire payload
    assert q["sync_bytes_per_step"] < fused["sync_bytes_per_step"] / 3
    assert demo["sync_bytes_per_step"] < q["sync_bytes_per_step"] / 5


# ---------------------------------------------------------------------------
# checkpoint: dialect 2 roundtrip + dialect-1 upgrade
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_gradsync_state_checkpoint_roundtrip(mesh8, tmp_path):
    from moco_tpu.checkpoint import (
        checkpoint_manager,
        restore_checkpoint,
        save_checkpoint,
    )
    from moco_tpu.parallel.mesh import replicated

    config = _config(grad_sync="quantized")
    state, step = _build(mesh8, config)
    im_q = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
    im_k = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
    state, _ = step(state, im_q, im_k)
    mgr = checkpoint_manager(str(tmp_path / "ckpt"))
    save_checkpoint(mgr, state, 1)
    fresh, _ = _build(mesh8, config)
    restored = restore_checkpoint(mgr, fresh, 1, sharding=replicated(mesh8))
    for a, b in zip(jax.tree.leaves(state.gradsync["acc"]),
                    jax.tree.leaves(restored.gradsync["acc"]), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(restored.step) == 1


@pytest.mark.slow
def test_dialect1_checkpoint_restores_with_fresh_accumulators(mesh8, tmp_path):
    """A pre-gradsync (dialect 1) checkpoint — simulated by saving the
    TrainState WITHOUT the gradsync field — restores into a quantized-mode
    target: the shim strips the accumulator leaves, the restore succeeds,
    and the accumulators restart from the caller's fresh zeros."""
    import orbax.checkpoint as ocp

    from moco_tpu.checkpoint import checkpoint_manager, restore_checkpoint
    from moco_tpu.parallel.mesh import replicated

    config = _config(grad_sync="quantized")
    state, _ = _build(mesh8, config)
    old_tree = {
        "step": state.step, "params_q": state.params_q,
        "params_k": state.params_k, "batch_stats_q": state.batch_stats_q,
        "batch_stats_k": state.batch_stats_k, "opt_state": state.opt_state,
        "queue": state.queue, "queue_ptr": state.queue_ptr,
        "rng": jax.random.key_data(state.rng),
    }
    mgr = checkpoint_manager(str(tmp_path / "old"))
    mgr.save(0, args=ocp.args.StandardSave(old_tree))
    mgr.wait_until_finished()
    fresh, _ = _build(mesh8, config)
    restored = restore_checkpoint(mgr, fresh, 0, sharding=replicated(mesh8))
    for a, b in zip(jax.tree.leaves(restored.params_q),
                    jax.tree.leaves(state.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a in jax.tree.leaves(restored.gradsync["acc"]):
        assert float(jnp.max(jnp.abs(a))) == 0.0  # fresh zeros


@pytest.mark.slow
def test_mode_switch_downgrade_drops_accumulators(mesh8, tmp_path):
    """A quantized checkpoint (accumulator leaves on disk) restored by a
    fused-mode run: the shim's stripped retry ignores the on-disk
    accumulators and the run proceeds exact-DP."""
    from moco_tpu.checkpoint import (
        checkpoint_manager,
        restore_checkpoint,
        save_checkpoint,
    )
    from moco_tpu.parallel.mesh import replicated

    state_q, step_q = _build(mesh8, _config(grad_sync="quantized"))
    im_q = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
    im_k = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
    state_q, _ = step_q(state_q, im_q, im_k)
    mgr = checkpoint_manager(str(tmp_path / "ckpt"))
    save_checkpoint(mgr, state_q, 1)
    fresh_fused, step_f = _build(mesh8, _config(grad_sync="fused"))
    restored = restore_checkpoint(mgr, fresh_fused, 1,
                                  sharding=replicated(mesh8))
    assert restored.gradsync == {}
    for a, b in zip(jax.tree.leaves(restored.params_q),
                    jax.tree.leaves(state_q.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    restored, metrics = step_f(restored, im_q, im_k)
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# v3 path + telemetry plumbing
# ---------------------------------------------------------------------------


def test_v3_demo_step_runs(mesh8):
    config = _config(
        variant="v3", grad_sync="demo", grad_sync_topk=0.25,
        optimizer="sgd", num_negatives=K,
    )
    from moco_tpu.v3_step import create_v3_train_state

    model = build_encoder(config)
    tx, sched = build_optimizer(config, 8)
    state = create_v3_train_state(
        jax.random.key(0), model, tx, (B // mesh8.size, IMG, IMG, 3)
    )
    state = GradSync(config, mesh8.size).attach(state, mesh8)
    step = build_train_step(config, model, tx, mesh8, 8, sched)
    x1 = jax.random.normal(jax.random.key(1), (B, IMG, IMG, 3))
    x2 = jax.random.normal(jax.random.key(2), (B, IMG, IMG, 3))
    state, metrics = step(state, x1, x2)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1
    acc = jax.tree.leaves(state.gradsync["acc"])
    assert acc and all(a.shape[0] == mesh8.size for a in acc)


def test_step_emits_comm_probes(mesh8):
    _, _, metrics = _run(mesh8, _config(grad_sync="bucketed"), steps=1)
    assert np.isfinite(float(metrics["gs_comm_pre"]))
    assert np.isfinite(float(metrics["gs_comm_post"]))


def test_timer_comm_phase():
    from moco_tpu.telemetry.timing import StepPhaseTimer

    timer = StepPhaseTimer(stride=2)
    timer.epoch_start()
    with timer.phase("host_s"):
        pass
    # off-stride: no fence, no comm sample
    assert timer.maybe_fence(1, 1.0, comm_pre=0.5, comm_post=0.7) is None
    assert "comm_s" not in timer.finish_step()
    with timer.phase("host_s"):
        pass
    assert timer.maybe_fence(2, 1.0, comm_pre=0.5, comm_post=0.7) is not None
    phases = timer.finish_step()
    assert "comm_s" in phases and phases["comm_s"] >= 0.0
    assert "device_s" in phases
    # probes absent (a non-gradsync caller): fence still works, no comm key
    with timer.phase("host_s"):
        pass
    assert timer.maybe_fence(4, 1.0) is not None
    assert "comm_s" not in timer.finish_step()


def test_report_renders_comm_share_and_sync_bytes(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "telemetry_report.py"),
    )
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    gs = {"mode": "quantized", "sync_bytes_per_step": 5 * 2**20,
          "quant_dtype": "int8", "bucket_mb": 4.0, "buckets": 3}
    records = [
        {"kind": "run_start", "name": "t", "variant": "v2", "arch": "r50",
         "batch_size": 256, "n_chips": 8, "n_procs": 1},
        {"kind": "event", "event": "grad_sync", **gs},
    ]
    for s in range(1, 9):
        rec = {"kind": "step", "step": s, "step_s": 0.1, "data_s": 0.01,
               "host_s": 0.005}
        if s % 4 == 0:
            rec["comm_s"] = 0.02
            rec["grad_sync"] = gs
        records.append(rec)
    summary = report.summarize(records)
    assert summary["comm"]["samples"] == 2
    assert summary["comm"]["share_mean"] == pytest.approx(0.2)
    assert summary["grad_sync"]["mode"] == "quantized"
    text = report.render(summary)
    assert "grad sync: quantized" in text
    assert "5.00 MiB/step/device" in text
    assert "comm phase" in text and "share 20.0%" in text
    # grad_sync is a routine event, not an incident
    assert summary["incidents_total"] == 0


@pytest.mark.slow
def test_driver_emits_grad_sync_records(mesh8, tmp_path):
    """End-to-end: a short quantized driver run lands a `grad_sync` event
    (mode + analytic bytes) and step records at the sampling stride carry
    the grad_sync stamp; the report renders the section."""
    from moco_tpu.config import get_preset
    from moco_tpu.train import train

    tel_dir = str(tmp_path / "tel")
    os.makedirs(tel_dir, exist_ok=True)
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=32,
        num_negatives=64, embed_dim=16, epochs=1, steps_per_epoch=6,
        grad_sync="quantized", knn_monitor=False, ckpt_dir="", print_freq=2,
        telemetry_dir=tel_dir, telemetry_stride=2, telemetry_flush_steps=2,
    )
    state, metrics = train(config, mesh8)
    assert int(state.step) == 6
    assert np.isfinite(metrics["loss"])
    events = [json.loads(line) for line in
              open(os.path.join(tel_dir, "events.jsonl"))]
    gs_events = [e for e in events
                 if e.get("kind") == "event" and e.get("event") == "grad_sync"]
    assert gs_events and gs_events[0]["mode"] == "quantized"
    assert gs_events[0]["sync_bytes_per_step"] > 0
    stamped = [e for e in events
               if e.get("kind") == "step" and "grad_sync" in e]
    assert stamped, "no step record carried the grad_sync stamp"
