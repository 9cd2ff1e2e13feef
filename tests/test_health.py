"""Learning-health observability suite (ISSUE 13).

Layers, bottom-up:

  - unit: the in-graph diagnostic math (telemetry/health.py) —
    embedding std / participation ratio on known distributions, the
    neg-sim/logit-margin fold over both logit layouts, the chaos
    key-encoder crush really degenerating features;
  - sentinel: CollapseSentinel window semantics (full-window violation,
    one incident per excursion, clean-window re-arm, min_step, opt-in
    rollback raising CollapseError);
  - step level (8 fake devices): neg_sim/logit_margin as standard
    metrics in both step builders; health_stride gating (real values
    on-stride, exact zeros off); THE contract — the parameter/queue/
    optimizer trajectory with diagnostics on is BITWISE the trajectory
    with them off;
  - serve: the reload drift guard refusing a collapsed checkpoint
    (CollapsedCheckpointError), recording probe drift on good reloads;
  - acceptance (chaos drill): 30-step CPU train with collapse_at_step=20
    → the stride-sampled emb-std pins the injected collapse, the
    sentinel fires EXACTLY one `health` incident, obsd's shipped
    learning-health rules alert then recover over the run's own records,
    telemetry_report renders the `health:` section, and the collapsed
    final checkpoint is refused by the reload guard.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import PretrainConfig, get_preset
from moco_tpu.resilience import (
    ChaosPlan,
    CollapseError,
    CollapseSentinel,
    NonFiniteLossError,
    chaos_context,
)
from moco_tpu.telemetry import health

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RULES_PATH = os.path.join(REPO, "tools", "slo_rules",
                          "learning_health.json")

GLOBAL_B, IMG, DIM, K = 16, 8, 16, 64


# ---------------------------------------------------------------------------
# unit: diagnostic math
# ---------------------------------------------------------------------------


def test_embedding_stats_isotropic_vs_collapsed():
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(256, 16)).astype(np.float32))
    std, pr = health.embedding_stats(z)
    # isotropic gaussian: per-dim std ~1, participation ratio ~D
    assert 0.8 < float(std) < 1.2
    assert 12.0 < float(pr) <= 16.0
    # rank-one collapse: every row on ONE direction (varying magnitude)
    mags = rng.normal(size=(256, 1)).astype(np.float32)
    direction = rng.normal(size=(1, 16)).astype(np.float32)
    _, pr1 = health.embedding_stats(jnp.asarray(mags * direction))
    assert float(pr1) == pytest.approx(1.0, abs=1e-3)
    # rank-zero (constant batch): std exactly 0, pr degrades to 0
    stdc, prc = health.embedding_stats(jnp.ones((64, 16)))
    assert float(stdc) == 0.0 and float(prc) == 0.0


def test_neg_sim_mean_both_logit_layouts():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))
    # v1/v2 layout: positive at column 0
    labels = jnp.zeros((8,), jnp.int32)
    expected = float(np.mean(np.asarray(logits)[:, 1:])) * 0.07
    got = float(health.neg_sim_mean(logits, labels, 0.07))
    assert got == pytest.approx(expected, rel=1e-5)
    # v3 layout: positive on a (shifted) diagonal
    sq = jnp.asarray(rng.normal(size=(6, 6)).astype(np.float32))
    diag = jnp.arange(6, dtype=jnp.int32)
    m = np.asarray(sq)
    expected = float((m.sum() - np.trace(m)) / (6 * 5))
    assert float(health.neg_sim_mean(sq, diag, 1.0)) == pytest.approx(
        expected, rel=1e-5)


def test_grad_group_norms_first_and_last_group():
    grads = {
        "a_stem": {"w": jnp.full((3,), 2.0)},
        "z_head": {"w": jnp.full((4,), 1.0)},
    }
    out = health.grad_group_norms(grads)
    assert float(out["h_gnorm_first"]) == pytest.approx(np.sqrt(12.0))
    assert float(out["h_gnorm_last"]) == pytest.approx(2.0)
    assert float(out["h_gnorm"]) == pytest.approx(np.sqrt(16.0))


def test_crush_key_params_makes_features_input_independent():
    from moco_tpu.models import build_backbone

    model = build_backbone("resnet_tiny", cifar_stem=True)
    variables = model.init(jax.random.key(0), jnp.zeros((1, IMG, IMG, 3)),
                           train=False)
    crushed = health.crush_key_params(variables["params"])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, IMG, IMG, 3)).astype(np.float32))
    out = model.apply(
        {"params": crushed,
         "batch_stats": variables.get("batch_stats", {})},
        x, train=False)
    # every input maps to ONE constant feature vector
    assert np.allclose(np.asarray(out), np.asarray(out)[0], atol=1e-6)
    std, _ = health.embedding_stats(out)
    assert float(std) < 1e-6


# ---------------------------------------------------------------------------
# CollapseSentinel window semantics
# ---------------------------------------------------------------------------


def _feed(sentinel, values, key="logit_margin", start=1):
    for i, v in enumerate(values):
        sentinel.observe(start + i, {key: v})
    sentinel.flush()


def test_sentinel_fires_once_per_excursion_and_rearms():
    s = CollapseSentinel(3, margin_eps=0.01)
    assert s.armed
    _feed(s, [1.0, 1.0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    assert len(s.fired) == 1
    (incident,) = s.fired
    assert incident["predicate"] == "margin"
    assert incident["step"] == 6  # the step completing the first bad window
    # a clean window re-arms; a second excursion fires a SECOND incident
    _feed(s, [1.0, 1.0, 1.0, 0.0, 0.0, 0.0], start=9)
    assert len(s.fired) == 2


def test_sentinel_one_healthy_sample_inside_window_rearms():
    s = CollapseSentinel(3, margin_eps=0.01)
    _feed(s, [0.0, 0.0, 1.0, 0.0, 0.0, 1.0, 0.0, 0.0])
    assert s.fired == []


def test_sentinel_min_step_suppresses_warmup():
    s = CollapseSentinel(2, acc1_floor=5.0, min_step=10)
    _feed(s, [0.1, 0.1, 0.1, 0.1], key="acc1", start=1)
    assert s.fired == []  # all inside warmup
    _feed(s, [0.1, 0.1, 0.1], key="acc1", start=11)
    assert len(s.fired) == 1


def test_sentinel_warmup_values_never_fill_the_window():
    """Grace-period observations are DISCARDED, not just muted: warmup
    violations plus ONE bad post-min_step value must not complete a
    window (the window starts filling only after min_step)."""
    s = CollapseSentinel(3, acc1_floor=5.0, min_step=10)
    _feed(s, [0.1] * 8, key="acc1", start=2)   # warmup-era "violations"
    _feed(s, [0.1], key="acc1", start=11)      # first real observation
    assert s.fired == []                       # window 1/3 full, no page


def test_sentinel_emb_std_takes_min_of_q_and_k():
    s = CollapseSentinel(2, emb_std_eps=1e-3)
    # query side healthy, key side collapsed: still collapse
    for i in range(4):
        s.observe(i + 1, {"h_emb_std_q": 0.5, "h_emb_std_k": 0.0})
    s.flush()
    assert len(s.fired) == 1 and s.fired[0]["predicate"] == "emb_std"


def test_sentinel_rollback_raises_collapse_error():
    s = CollapseSentinel(2, margin_eps=0.01, rollback=True)
    with pytest.raises(CollapseError) as e:
        _feed(s, [0.0, 0.0, 0.0])
    assert isinstance(e.value, NonFiniteLossError)  # rides the driver's
    assert e.value.predicate == "margin"            # bounded-rollback path


def test_sentinel_unarmed_when_no_thresholds():
    s = CollapseSentinel(5)
    assert not s.armed
    _feed(s, [0.0] * 20)
    assert s.fired == []


# ---------------------------------------------------------------------------
# step level: standard metrics, stride gating, bitwise trajectory
# ---------------------------------------------------------------------------


def _tiny_v1_config(**overrides):
    base = dict(variant="v1", num_negatives=K, embed_dim=DIM,
                temperature=0.07, lr=0.05, batch_size=GLOBAL_B, epochs=4,
                schedule=(2, 3))
    base.update(overrides)
    return PretrainConfig(**base)


def _build_v1(config, mesh):
    from moco_tpu.models.resnet import BasicBlock, ResNet
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_optimizer, build_train_step

    model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, width=8,
                   cifar_stem=True, num_classes=DIM)
    tx, _ = build_optimizer(config, steps_per_epoch=4)
    state = create_train_state(
        jax.random.key(0), model, tx, (GLOBAL_B // 8, IMG, IMG, 3), K, DIM)
    raw = build_train_step(config, model, tx, mesh, steps_per_epoch=4)

    def step_fn(s, im_q, im_k):
        # the step donates its state; feed a copy, keep the original
        return raw(jax.tree.map(jnp.copy, s), im_q, im_k)

    return state, step_fn


def _batches(n):
    return [
        (jax.random.normal(jax.random.key(10 + i), (GLOBAL_B, IMG, IMG, 3)),
         jax.random.normal(jax.random.key(20 + i), (GLOBAL_B, IMG, IMG, 3)))
        for i in range(n)
    ]


def test_standard_metrics_present_and_consistent_v1(mesh8):
    config = _tiny_v1_config()  # health_stride=0: diagnostics OFF
    state, step_fn, = _build_v1(config, mesh8)
    _, metrics = step_fn(state, *_batches(1)[0])
    assert "neg_sim" in metrics and "logit_margin" in metrics
    assert float(metrics["logit_margin"]) == pytest.approx(
        float(metrics["pos_sim"]) - float(metrics["neg_sim"]), abs=1e-5)
    # diagnostics off: NO h_* keys in the step program's outputs
    assert not any(k.startswith("h_") for k in metrics)


def test_health_stride_gates_and_trajectory_bitwise_v1(mesh8):
    """THE contract: diagnostics are observational — the state trajectory
    with health_stride on is BITWISE the trajectory with it off; h_*
    scalars carry real values exactly on stride steps, zeros off."""
    batches = _batches(4)
    state_off, step_off = _build_v1(_tiny_v1_config(), mesh8)
    state_on, step_on = _build_v1(_tiny_v1_config(health_stride=2), mesh8)

    s_off, s_on = state_off, state_on
    for i, (im_q, im_k) in enumerate(batches):
        s_off, m_off = step_off(s_off, im_q, im_k)
        s_on, m_on = step_on(s_on, im_q, im_k)
        on_stride = i % 2 == 0  # the cond keys on state.step (starts 0)
        if on_stride:
            assert float(m_on["h_emb_std_q"]) > 1e-3
            assert float(m_on["h_emb_std_k"]) > 1e-3
            # the 2-row per-device shard is rank-1 by construction, so
            # the PR bottoms at exactly 1 here; real shards spread it
            assert float(m_on["h_emb_pr_q"]) >= 1.0
            assert float(m_on["h_gnorm"]) > 0.0
            assert float(m_on["h_qnorm_mean"]) >= 0.0
            assert float(m_on["h_pdrift"]) >= 0.0
        else:
            for key in ("h_emb_std_q", "h_emb_std_k", "h_emb_pr_q",
                        "h_gnorm", "h_qnorm_mean", "h_pdrift"):
                assert float(m_on[key]) == 0.0, key
        # identical losses step by step...
        assert float(m_on["loss"]) == float(m_off["loss"])
    # ...and a bitwise-identical final state (params, queue, optimizer)
    for a, b in zip(
            jax.tree.leaves(s_on.replace(rng=jax.random.key_data(s_on.rng))),
            jax.tree.leaves(s_off.replace(rng=jax.random.key_data(s_off.rng)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_v3_standard_metrics_and_stride(mesh8):
    from moco_tpu.v3_step import build_v3_train_step, create_v3_train_state

    config = PretrainConfig(
        variant="v3", arch="vit_tiny", embed_dim=DIM, batch_size=GLOBAL_B,
        epochs=4, lr=1e-3, image_size=16, health_stride=2,
    )
    from moco_tpu.train_step import build_encoder, build_optimizer

    model = build_encoder(config)
    tx, sched = build_optimizer(config, steps_per_epoch=4)
    state = create_v3_train_state(
        jax.random.key(0), model, tx, (GLOBAL_B // 8, 16, 16, 3))
    raw = build_v3_train_step(config, model, tx, mesh8, 4, sched)

    def step_fn(s, a, b):
        return raw(jax.tree.map(jnp.copy, s), a, b)

    im = [(jax.random.normal(jax.random.key(30 + i), (GLOBAL_B, 16, 16, 3)),
           jax.random.normal(jax.random.key(40 + i), (GLOBAL_B, 16, 16, 3)))
          for i in range(2)]
    s = state
    s, m0 = step_fn(s, *im[0])  # state.step 0: on-stride
    assert "neg_sim" in m0 and "logit_margin" in m0
    assert float(m0["h_emb_std_q"]) > 0.0
    assert float(m0["h_pdrift"]) >= 0.0
    # v3 is queue-free: no queue diagnostics
    assert "h_qnorm_mean" not in m0
    s, m1 = step_fn(s, *im[1])  # state.step 1: off-stride
    assert float(m1["h_emb_std_q"]) == 0.0


# ---------------------------------------------------------------------------
# serve: the reload drift guard
# ---------------------------------------------------------------------------


def _engine_from_params(model, params, stats, buckets=(1, 4, 8)):
    from moco_tpu.serve import EmbeddingEngine

    return EmbeddingEngine(model, params, stats, image_size=IMG,
                           buckets=buckets)


@pytest.fixture(scope="module")
def tiny_backbone():
    from moco_tpu.models import build_backbone

    model = build_backbone("resnet_tiny", cifar_stem=True)
    variables = {
        seed: model.init(jax.random.key(seed),
                         jnp.zeros((1, IMG, IMG, 3)), train=False)
        for seed in (0, 1)
    }
    return model, variables


def test_reload_guard_refuses_collapsed_checkpoint(tiny_backbone):
    from moco_tpu.serve import CollapsedCheckpointError, EmbedService

    model, variables = tiny_backbone
    v0 = variables[0]
    service = EmbedService(
        _engine_from_params(model, v0["params"],
                            v0.get("batch_stats", {})),
        flush_ms=2.0, max_queue=32, request_deadline_ms=10_000.0)
    crushed = health.crush_key_params(v0["params"])
    service.set_engine_factory(
        lambda path: _engine_from_params(model, crushed,
                                         v0.get("batch_stats", {})))
    try:
        with pytest.raises(CollapsedCheckpointError) as e:
            service.reload("collapsed.npz", step=7)
        assert "degenerate" in str(e.value)
        assert service.reloads == 0  # never promoted
        # the OLD engine keeps serving
        img = np.random.RandomState(0).randint(
            0, 256, (IMG, IMG, 3)).astype(np.uint8)
        row, _ = service.embed(img)
        assert np.isfinite(row).all()
    finally:
        service.drain(timeout_s=10.0)


def test_reload_guard_records_drift_on_good_reload(tiny_backbone):
    from moco_tpu.serve import EmbedService

    model, variables = tiny_backbone
    v0, v1 = variables[0], variables[1]
    service = EmbedService(
        _engine_from_params(model, v0["params"],
                            v0.get("batch_stats", {})),
        flush_ms=2.0, max_queue=32, request_deadline_ms=10_000.0)
    service.set_engine_factory(
        lambda path: _engine_from_params(model, v1["params"],
                                         v1.get("batch_stats", {})))
    try:
        entry = service.reload("other.npz", step=8)
        assert service.reloads == 1
        # different weights: the space moved, and the probe says by how
        # much; dispersion stayed healthy
        assert entry["probe_drift"] > 0.0
        assert entry["probe_spread"] > service.reload_min_spread
    finally:
        service.drain(timeout_s=10.0)


def test_reload_guard_disabled_with_probe_zero(tiny_backbone):
    from moco_tpu.serve import EmbedService

    model, variables = tiny_backbone
    v0 = variables[0]
    service = EmbedService(
        _engine_from_params(model, v0["params"],
                            v0.get("batch_stats", {})),
        flush_ms=2.0, max_queue=32, request_deadline_ms=10_000.0,
        reload_probe=0)
    crushed = health.crush_key_params(v0["params"])
    service.set_engine_factory(
        lambda path: _engine_from_params(model, crushed,
                                         v0.get("batch_stats", {})))
    try:
        entry = service.reload("collapsed.npz")  # guard off: promoted
        assert service.reloads == 1
        assert "probe_spread" not in entry
    finally:
        service.drain(timeout_s=10.0)


def test_watcher_public_quarantine_moves_step_dir(tmp_path):
    from moco_tpu.serve import CheckpointWatcher

    watch = tmp_path / "watch"
    (watch / "5").mkdir(parents=True)
    (watch / "5" / "encoder.npz").write_bytes(b"payload")
    events = []
    w = CheckpointWatcher(str(watch),
                          emit=lambda ev, **f: events.append((ev, f)))
    w.quarantine(5, "reload drift guard: collapsed")
    assert not (watch / "5").exists()
    assert (watch / ".quarantine" / "5").exists()
    assert events and events[0][0] == "reload_quarantine"
    assert "drift guard" in events[0][1]["reason"]


# ---------------------------------------------------------------------------
# acceptance: the chaos collapse drill, end to end
# ---------------------------------------------------------------------------


def _drill_config(tmp_path, **overrides):
    base = dict(
        arch="resnet_tiny", dataset="synthetic", image_size=16,
        batch_size=16, num_negatives=64, embed_dim=32, lr=0.1, epochs=3,
        steps_per_epoch=10, ckpt_dir="", tb_dir="", print_freq=1000,
        num_classes=10, knn_monitor=False,
        telemetry_dir=str(tmp_path / "telemetry"),
        telemetry_flush_steps=10_000, heartbeat_secs=0.0,
        health_stride=2, collapse_window=3, collapse_emb_std=1e-4,
        collapse_min_step=4,
    )
    base.update(overrides)
    return get_preset("cifar10-moco-v1").replace(**base)


@pytest.mark.chaos
def test_collapse_drill_e2e(mesh8, tmp_path):
    """ISSUE 13 acceptance: 30-step CPU train with `collapse_at_step=20`
    — the in-graph diagnostics catch the injected collapse, the sentinel
    fires exactly ONE `health` incident, obsd's shipped learning-health
    rules alert then recover over the run's own records, the report
    renders `health:`, and the collapsed checkpoint is refused by the
    serve reload guard."""
    from moco_tpu.telemetry.aggregate import Aggregator, load_rules
    from moco_tpu.train import train
    from tools.telemetry_report import load_events, render, summarize

    config = _drill_config(tmp_path)
    with chaos_context(ChaosPlan(collapse_at_step=20)):
        state, _ = train(config, mesh8)
    assert int(state.step) == 30

    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    records, skipped = load_events(events_path)
    assert skipped == 0

    # (1) the stride-sampled diagnostics separate healthy from collapsed
    blocks = [(r["step"], r["health"]) for r in records
              if r.get("kind") == "step" and "health" in r]
    healthy = [h["emb_std_k"] for s, h in blocks if s <= 20]
    crushed = [h["emb_std_k"] for s, h in blocks if s > 22]
    assert healthy and min(healthy) > 1e-3
    assert crushed and max(crushed) <= 1e-4

    # (2) the sentinel fired exactly one health incident, on emb_std
    incidents = [r for r in records if r.get("kind") == "event"
                 and r.get("event") == "health"]
    assert len(incidents) == 1
    assert incidents[0]["predicate"] == "emb_std"
    assert incidents[0]["step"] > 20

    # (3) obsd with the SHIPPED rule file over the run's own records:
    # replay them time-compressed into a live stream (records that exist
    # before the tailer is created are catch-up by design), healthy
    # phase first, collapsed phase after both burn windows aged out
    replay = tmp_path / "replay"
    replay.mkdir()
    replay_events = str(replay / "events.jsonl")
    agg = Aggregator([str(replay)], rules=load_rules(RULES_PATH))
    assert agg.poll_once(now=900.0) == []

    def append(recs):
        with open(replay_events, "a", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")

    pre = [r for r in records if r not in incidents
           and (r.get("kind") != "step" or r.get("step", 0) <= 20)]
    post = [r for r in records
            if r.get("kind") == "step" and r.get("step", 0) > 20] \
        + incidents
    append(pre)
    transitions = agg.poll_once(now=1000.0)
    assert transitions == []  # healthy phase: nothing fires
    append(post)
    transitions = agg.poll_once(now=1400.0)
    fired = {t["rule"] for t in transitions}
    assert "collapse_emb_std" in fired  # the learning-health SLO alerts
    assert all(t["action"] == "alert" for t in transitions)
    # the stream drains -> the alert recovers (clear_s hysteresis)
    assert agg.poll_once(now=1500.0) == []
    recovered = agg.poll_once(now=1505.0)
    assert {t["rule"] for t in recovered} >= {"collapse_emb_std"}
    assert all(t["action"] == "recover" for t in recovered)

    # (4) the report renders the learning-health story — incl. the slo
    # transitions obsd appended into the replay stream
    replay_records, _ = load_events(replay_events)
    summary = summarize(replay_records)
    assert summary["health"]["incidents"]["fired"] == 1
    assert summary["health"]["min"]["emb_std_k"] <= 1e-4
    assert summary["slo"]["alerts"] >= 1 and summary["slo"]["recoveries"] >= 1
    text = render(summary)
    assert "health:" in text and "collapse incidents: 1 fired" in text

    # (5) the collapsed checkpoint is refused by the serve reload guard:
    # a healthy engine is live, the drilled run's final (crushed) key
    # encoder arrives as the reload candidate
    from moco_tpu.serve import CollapsedCheckpointError, EmbedService
    from moco_tpu.train_step import build_encoder

    model = build_encoder(config)
    healthy_vars = model.init(jax.random.key(3),
                              jnp.zeros((1, 16, 16, 3)), train=False)
    from moco_tpu.serve import EmbeddingEngine

    def engine(params, stats):
        return EmbeddingEngine(model, params, stats, image_size=16,
                               buckets=(1, 4, 8))

    service = EmbedService(
        engine(healthy_vars["params"],
               healthy_vars.get("batch_stats", {})),
        flush_ms=2.0, max_queue=32, request_deadline_ms=10_000.0)
    service.set_engine_factory(
        lambda path: engine(state.params_k, state.batch_stats_k))
    try:
        with pytest.raises(CollapsedCheckpointError):
            service.reload("collapsed-step-30.npz", step=30)
        assert service.reloads == 0
    finally:
        service.drain(timeout_s=10.0)


@pytest.mark.chaos
def test_collapse_rollback_soak_exhausts_budget(mesh8, tmp_path):
    """The opt-in rollback path under a PERSISTENT collapse: every
    rollback restores a pre-collapse checkpoint, the wedged-momentum
    chaos re-crushes the key encoder, the sentinel fires again — the
    bounded budget must exhaust and abort for a human instead of
    rollback-looping forever (the NaN-rollback semantics, inherited by
    construction)."""
    from moco_tpu.resilience import RollbackExhaustedError
    from moco_tpu.train import train

    config = _drill_config(
        tmp_path, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every_epochs=1,
        collapse_rollback=True, max_rollbacks=1,
    )
    with chaos_context(ChaosPlan(collapse_at_step=12)):
        with pytest.raises(RollbackExhaustedError):
            train(config, mesh8)
    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    from tools.telemetry_report import load_events

    records, _ = load_events(events_path)
    # each attempt's stream carries the sentinel firing with rollback
    # requested, and the data-window advance the restore performed
    incidents = [r for r in records if r.get("kind") == "event"
                 and r.get("event") == "health"]
    assert incidents and incidents[0]["predicate"] == "emb_std"
    assert "requesting rollback" in incidents[0]["msg"]
    rollbacks = [r for r in records if r.get("kind") == "event"
                 and r.get("event") == "rollback"]
    assert rollbacks  # the bounded restore actually ran before giving up
