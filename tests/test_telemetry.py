"""Telemetry subsystem suite (ISSUE 2): schema round-trip through the real
offline report, MFU against hand-computed ResNet-18 FLOPs, phase-timer
monotonicity + stride fencing, the 30-step acceptance smoke through the
real train() driver, and a chaos scenario asserting a rollback lands a
structured incident in events.jsonl."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from moco_tpu.config import get_preset
from moco_tpu.telemetry import (
    SCHEMA_VERSION,
    Heartbeat,
    MetricsRegistry,
    MFUEstimator,
    StepPhaseTimer,
    detect_peak_flops,
    model_fwd_flops,
    percentiles_ms,
    resnet_fwd_flops,
    train_step_flops,
    vit_fwd_flops,
)
from moco_tpu.utils import logging as mlog
from moco_tpu.utils.meters import Throughput

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = os.path.join(REPO, "tools", "telemetry_report.py")

_spec = importlib.util.spec_from_file_location("telemetry_report", REPORT)
report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(report)


# ---------------------------------------------------------------------------
# registry / sink
# ---------------------------------------------------------------------------


def test_registry_instruments_typed(tmp_path):
    reg = MetricsRegistry(str(tmp_path / "events.jsonl"))
    c = reg.counter("incidents")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("incidents") is c  # get-or-create
    g = reg.gauge("hbm")
    g.set(10)
    g.set(4)
    assert g.value == 4.0 and g.high_water == 10.0
    h = reg.histogram("step_s")
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        h.observe(v)
    assert h.count == 5 and h.max == 5.0 and h.mean == 3.0
    assert h.percentile(0) == 1.0 and h.percentile(50) == 3.0
    assert h.percentile(100) == 5.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("incidents")
    reg.close()


def test_jsonl_roundtrip_through_report(tmp_path):
    """write → flush → tools/telemetry_report parse: the full schema loop."""
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=3)
    reg.emit("run_start", name="t", variant="v2", arch="resnet18",
             batch_size=32, n_chips=8, n_procs=1,
             peak_flops_per_chip=1e12, flops_per_step=1e9)
    for step in range(1, 11):
        reg.emit("step", step=step, step_s=0.1 * step, data_s=0.01,
                 host_s=0.02, imgs_per_sec=100.0, mfu=0.5)
    reg.emit("event", event="rollback", msg="injected")
    reg.close()

    # a torn tail (SIGKILL mid-flush) must be skipped, not fatal
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "step", "trunc')

    records, skipped = report.load_events(path)
    assert skipped == 1
    assert all(r["v"] == SCHEMA_VERSION for r in records)
    summary = report.summarize(records, skipped)
    assert summary["steps"] == 10
    assert summary["incidents"] == {"rollback": 1}
    # nearest-rank over 0.1..1.0
    assert summary["step_time_ms"]["p50"] == pytest.approx(500.0)
    assert summary["step_time_ms"]["p99"] == pytest.approx(1000.0)
    assert summary["mfu"]["mean"] == pytest.approx(0.5)
    rendered = report.render(summary)
    assert "p50" in rendered and "MFU" in rendered and "rollback" in rendered


def test_registry_flush_cadence(tmp_path):
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=4)
    flushes = [reg.emit("step", step=i) for i in range(6)]
    # 4th record flushes; the 2 after it sit in the buffer until close
    assert flushes == [False, False, False, True, False, False]
    records, _ = report.load_events(path)
    assert len(records) == 4
    reg.close()
    records, _ = report.load_events(path)
    assert len(records) == 6


def test_null_sink_registry_aggregates_without_writing(tmp_path):
    """Non-main pod hosts: instruments work, nothing lands on disk, and the
    record buffer stays bounded (dropped at the flush cadence)."""
    reg = MetricsRegistry(None, flush_every=2)
    for i in range(100):
        reg.emit("step", step=i)
    reg.histogram("step_s").observe(1.0)
    assert len(reg._buffer) < 2
    reg.close()


def test_reopen_after_torn_tail_starts_fresh_line(tmp_path):
    """A resumed run appending to an events.jsonl whose last line was torn
    by a SIGKILL mid-flush must not weld its run_start onto the fragment —
    only the torn fragment may be lost, never the new record."""
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=1)
    reg.emit("step", step=1)
    reg.close()
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "step", "tor')  # no trailing newline

    resumed = MetricsRegistry(path, flush_every=1)
    resumed.emit("run_start", name="resumed")
    resumed.close()
    records, skipped = report.load_events(path)
    assert skipped == 1  # the fragment, and ONLY the fragment
    assert [r["kind"] for r in records] == ["step", "run_start"]


def test_nonfinite_and_foreign_scalars_stay_valid_json(tmp_path):
    """A diverged loss (the record that documents an incident!) must not
    produce a bare `NaN` line that RFC-8259 consumers reject; numpy
    scalars (not `float` subclasses) go through the same check."""
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=1)
    reg.emit("step", step=1, loss=float("nan"), lr=np.float32("inf"),
             n=np.int64(7), nested={"x": [float("-inf"), 2.0]})
    reg.close()
    with open(path) as f:
        line = f.read().strip()
    rec = json.loads(line)  # strict json: parse must succeed
    assert "NaN" not in line and "Infinity" not in line
    assert rec["loss"] == "nan" and rec["lr"] == "inf" and rec["n"] == 7
    assert rec["nested"]["x"] == ["-inf", 2.0]


def test_registry_emit_is_thread_safe(tmp_path):
    """log_event sinks fire from the watchdog/prefetcher threads while the
    step loop emits: no record may be lost or torn across a flush race."""
    import threading

    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=3)  # frequent buffer swaps

    def spam(tid):
        for i in range(200):
            reg.emit("event", event="stress", tid=tid, i=i)

    threads = [threading.Thread(target=spam, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reg.close()
    records, skipped = report.load_events(path)
    assert skipped == 0
    assert len(records) == 800
    seen = {(r["tid"], r["i"]) for r in records}
    assert len(seen) == 800  # nothing lost, nothing duplicated


def test_heartbeat_atomic_and_parseable(tmp_path):
    hb = Heartbeat(str(tmp_path / "telemetry" / "heartbeat.json"))
    hb.beat(7, phase="run_start")
    with open(hb.path) as f:
        payload = json.load(f)
    assert payload["step"] == 7 and payload["pid"] == os.getpid()
    assert payload["v"] == SCHEMA_VERSION
    t_first = payload["t"]
    hb.beat(9)
    with open(hb.path) as f:
        payload = json.load(f)
    assert payload["step"] == 9 and payload["t"] >= t_first
    assert not os.path.exists(hb.path + ".tmp")


def test_heartbeat_maybe_beat_time_gated(tmp_path):
    """maybe_beat honors min_interval_secs (the per-step call must not pay
    an atomic replace per 100 ms step); beat() always writes (lifecycle
    transitions are never elided)."""
    hb = Heartbeat(str(tmp_path / "heartbeat.json"), min_interval_secs=60.0)
    assert hb.maybe_beat(1, phase="step")      # first write always lands
    assert not hb.maybe_beat(2, phase="step")  # gated: way inside 60 s
    with open(hb.path) as f:
        assert json.load(f)["step"] == 1
    hb.beat(3, phase="run_end")                # forced lifecycle write
    with open(hb.path) as f:
        assert json.load(f)["step"] == 3
    ungated = Heartbeat(str(tmp_path / "hb2.json"), min_interval_secs=0.0)
    assert ungated.maybe_beat(1) and ungated.maybe_beat(2)


def test_on_step_beats_every_step_decoupled_from_flush(tmp_path, mesh8):
    """ISSUE 4 satellite: the heartbeat used to advance only when the sink
    flushed, making hang-detection granularity an accident of
    telemetry_flush_steps. It now beats every step (time-gated), with the
    step + phase fields the supervisor's progress check reads."""
    from moco_tpu.telemetry import RunTelemetry

    config = get_preset("cifar10-moco-v1").replace(
        telemetry_dir=str(tmp_path), telemetry_flush_steps=10_000,
        heartbeat_secs=0.0, telemetry_stride=0,
    )
    tel = RunTelemetry(config, n_chips=8, n_procs=1, process_index=0,
                       steps_per_epoch=4)
    try:
        thr = Throughput(8, window=4)
        thr.update(16)
        phases = {"step_s": 0.01, "data_s": 0.001, "host_s": 0.001}
        hb_path = os.path.join(str(tmp_path), "heartbeat.json")
        for step in (1, 2, 3):
            flushed = tel.on_step(step, dict(phases), thr)
            assert not flushed  # flush cadence never reached …
            with open(hb_path) as f:
                payload = json.load(f)
            assert payload["step"] == step  # … yet every step beat
            assert payload["phase"] == "step"
            assert payload["pid"] == os.getpid()
    finally:
        tel.close(last_step=3)
    with open(hb_path) as f:
        final = json.load(f)
    assert final["phase"] == "run_end" and final["step"] == 3


def test_close_preempted_marks_heartbeat_phase(tmp_path, mesh8):
    """The emergency-exit path stamps phase=preempt_exit with the last
    completed step + pid, so the supervisor can tell 'relaunch me' from a
    natural end without scraping logs (ISSUE 4 satellite)."""
    from moco_tpu.telemetry import RunTelemetry

    config = get_preset("cifar10-moco-v1").replace(
        telemetry_dir=str(tmp_path), heartbeat_secs=0.0)
    tel = RunTelemetry(config, n_chips=8, n_procs=1, process_index=0,
                       steps_per_epoch=4)
    tel.close(last_step=7, preempted=True)
    with open(os.path.join(str(tmp_path), "heartbeat.json")) as f:
        payload = json.load(f)
    assert payload["phase"] == "preempt_exit"
    assert payload["step"] == 7 and payload["pid"] == os.getpid()


ATTN_PLANS = {"fused": {"path": "fused", "tiles": 16, "tiles_skipped": 6, "qk_prep": "fused"},
              "einsum": {"path": "einsum", "tiles": 1, "tiles_skipped": 0, "qk_prep": "xla"},
              # ISSUE 34: what a rematerialised layer keeps rides the same block
              "tiled": {"path": "tiled", "tiles": 4096, "tiles_skipped": 1920, "qk_prep": "fused",
                        "select": {"topk": 2048, "path": "kernels"},
                        "kept": {"names": ["masked_attention_o", "masked_attention_lse",
                                           "masked_attention_live"],
                                 "bytes_per_layer": 270532608}}}


@pytest.mark.parametrize("path", [None, "fused", "einsum", "tiled"])
def test_the_setup_event_carries_the_attn_block_beside_its_spans(tmp_path, mesh8, path):
    """ISSUE 28: how a token encoder's attention was built rides the one `setup`
    event (static per program: no step record pays for it); a run without a
    token encoder has no such block."""
    from moco_tpu.telemetry import RunTelemetry

    config = get_preset("cifar10-moco-v1").replace(
        telemetry_dir=str(tmp_path), heartbeat_secs=0.0, telemetry_stride=0)
    tel = RunTelemetry(config, n_chips=8, n_procs=1, process_index=0, steps_per_epoch=4)
    try:
        with tel.setup_span("build_step"):
            pass
        if path:
            tel.set_attn(ATTN_PLANS[path])
        thr = Throughput(8, window=4)
        thr.update(16)
        for step in (1, 2):
            tel.on_step(step, {"step_s": 0.01, "data_s": 0.001, "host_s": 0.001}, thr)
    finally:
        tel.close(last_step=2)
    with open(os.path.join(str(tmp_path), "events.jsonl")) as f:
        setups = [r for r in map(json.loads, f) if r.get("event") == "setup"]
    assert len(setups) == 1 and "build_step" in setups[0]["spans"]
    assert setups[0].get("attn") == (ATTN_PLANS[path] if path else None)


@pytest.mark.parametrize("path, holds", [
    ("fused", "attention fused, 6 of 16 score tiles skipped, q/k prep fused"),
    ("einsum", "attention einsum, 0 of 1 score tiles skipped, q/k prep xla"),
    # a record from before ISSUE 30 has no `qk_prep`: XLA prepared q and k then
    ("fused_without_qk_prep", "attention fused, 6 of 16 score tiles skipped, q/k prep xla"),
    ("tiled", "attention tiled, 1920 of 4096 score tiles skipped, q/k prep fused, top-2048 "
              "selection by kernels, remat keeps 3 named values, 270.5 MB a layer"),
    (None, None),
])
def test_the_report_prints_the_attention_path_on_its_setup_line(path, holds):
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..", "tools",
                                         "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    setup = {"kind": "event", "event": "setup", "spans": {"model_init": 3.5, "build_step": 0.25}}
    if path:
        setup["attn"] = {k: v for k, v in ATTN_PLANS[path.split("_")[0]].items()
                         if k != "qk_prep" or "without" not in path}
    records = [setup, {"kind": "step", "step": 1, "step_s": 0.5}]
    lines = [t for t in report.render(report.summarize(records)).splitlines() if "set-up:" in t]
    assert len(lines) == 1 and "model_init 3.50 s" in lines[0]
    assert ("attention" not in lines[0]) if holds is None else lines[0].endswith(holds)


MOE_PATHS = {"kernels": {"dispatch": "kernels", "rows": 32768, "spill_rows": 4096, "passes": 3,
                         "tokens": 16384},
             "xla": {"dispatch": "xla", "rows": 256, "spill_rows": 32, "passes": 1, "tokens": 128}}


@pytest.mark.parametrize("path", [None, "kernels", "xla"])
def test_the_setup_event_carries_the_moe_block_beside_the_attn_block(tmp_path, mesh8, path):
    """ISSUE 32: how a routed encoder moves its expert rows rides the same
    `setup` event (static per program); an encoder without a router has no such
    block, and the `attn` block stands as it did."""
    from moco_tpu.telemetry import RunTelemetry

    config = get_preset("cifar10-moco-v1").replace(
        telemetry_dir=str(tmp_path), heartbeat_secs=0.0, telemetry_stride=0)
    tel = RunTelemetry(config, n_chips=8, n_procs=1, process_index=0, steps_per_epoch=4)
    try:
        with tel.setup_span("build_step"):
            pass
        tel.set_attn(ATTN_PLANS["fused"])
        if path:
            tel.set_moe(MOE_PATHS[path])
        thr = Throughput(8, window=4)
        thr.update(16)
        for step in (1, 2):
            tel.on_step(step, {"step_s": 0.01, "data_s": 0.001, "host_s": 0.001}, thr)
    finally:
        tel.close(last_step=2)
    with open(os.path.join(str(tmp_path), "events.jsonl")) as f:
        setups = [r for r in map(json.loads, f) if r.get("event") == "setup"]
    assert len(setups) == 1 and setups[0]["attn"] == ATTN_PLANS["fused"]
    assert setups[0].get("moe") == (MOE_PATHS[path] if path else None)


@pytest.mark.parametrize("path, holds", [
    ("kernels", "q/k prep fused · expert rows by kernels, 32768 a pass, 4096 in the small spill "
                "pass, 3 whole passes after it"),
    ("xla", "q/k prep fused · expert rows by xla, 256 a pass, 32 in the small spill pass, "
            "1 whole passes after it"),
    (None, "q/k prep fused"),
])
def test_the_report_prints_the_moe_block_at_the_end_of_its_setup_line(path, holds):
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..", "tools",
                                         "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    setup = {"kind": "event", "event": "setup", "spans": {"model_init": 3.5, "build_step": 0.25},
             "attn": ATTN_PLANS["fused"]}
    if path:
        setup["moe"] = MOE_PATHS[path]
    records = [setup, {"kind": "step", "step": 1, "step_s": 0.5}]
    lines = [t for t in report.render(report.summarize(records)).splitlines() if "set-up:" in t]
    assert len(lines) == 1 and lines[0].endswith(holds)
    assert ("expert rows" in lines[0]) == (path is not None)


@pytest.mark.parametrize("moe, assign, holds", [
    # the routed cell's block: 32 768 buffer rows for 16 384 tokens a call
    ("kernels", 0.97, " · 48.5 % of the first pass's 32768 buffer rows assigned"),
    ("kernels", 2.5, " · 125.0 % of the first pass's 32768 buffer rows assigned, the rest "
                     "spills into the passes after it"),
    # a record from before the `moe` block carried its tokens: no share to print
    ("without_tokens", 0.97, "fullest over mean load 11.000"),
    (None, 0.97, "fullest over mean load 11.000"),
])
def test_the_report_prints_the_first_passs_fill_on_its_experts_line(moe, assign, holds):
    """The grouped products stop at the last assignment, so the share of the
    first pass's buffer the router filled is the share they work on: the
    step records' `moe_assign_per_token` times the `moe` block's `tokens` over
    its `rows`."""
    setup = {"kind": "event", "event": "setup", "spans": {"model_init": 3.5}}
    if moe:
        setup["moe"] = {k: v for k, v in MOE_PATHS["kernels"].items()
                        if k != "tokens" or moe == "kernels"}
    records = [setup, {"kind": "step", "step": 1, "step_s": 0.5,
                       "health": {"moe_assign_per_token": assign,
                                  "moe_load_max_over_mean": 11.0}}]
    lines = [t for t in report.render(report.summarize(records)).splitlines() if "experts:" in t]
    assert len(lines) == 1 and lines[0].endswith(holds)
    assert lines[0].startswith(f"  experts: {assign:.3f} assignment(s) a token")


# ---------------------------------------------------------------------------
# MFU / analytic FLOPs
# ---------------------------------------------------------------------------


def test_resnet18_flops_hand_computed():
    """Independent layer-by-layer arithmetic for ResNet-18 @224 (torch
    BasicBlock structure), down to the exact FLOP."""
    def conv(hw, k, cin, cout):
        return 2 * hw * hw * k * k * cin * cout

    expected = conv(112, 7, 3, 64)            # stem 7x7/2: 224 -> 112
    # stage 1 @56 (after 3x3/2 maxpool), 64ch, 2 blocks, no downsample
    expected += 4 * conv(56, 3, 64, 64)
    # stage 2 @28, 64 -> 128, downsample 1x1 in block 0
    expected += conv(28, 3, 64, 128) + conv(28, 3, 128, 128) + conv(28, 1, 64, 128)
    expected += 2 * conv(28, 3, 128, 128)
    # stage 3 @14, 128 -> 256
    expected += conv(14, 3, 128, 256) + conv(14, 3, 256, 256) + conv(14, 1, 128, 256)
    expected += 2 * conv(14, 3, 256, 256)
    # stage 4 @7, 256 -> 512
    expected += conv(7, 3, 256, 512) + conv(7, 3, 512, 512) + conv(7, 1, 256, 512)
    expected += 2 * conv(7, 3, 512, 512)

    assert resnet_fwd_flops("resnet18", 224) == expected
    # cross-check vs the literature number (1.814 GMACs backbone @224)
    assert expected / 2e9 == pytest.approx(1.814, abs=0.01)
    # head accounting: +2*512*128 for the default fc
    assert model_fwd_flops("resnet18", 224, embed_dim=128) == expected + 2 * 512 * 128


def test_resnet50_and_vit_flops_literature_band():
    assert resnet_fwd_flops("resnet50", 224) / 2e9 == pytest.approx(4.09, abs=0.05)
    # DeiT-S / moco-v3 vit_small: ~4.6 GMACs @224
    assert vit_fwd_flops("vit_small", 224) / 2e9 == pytest.approx(4.6, abs=0.1)


def test_train_step_flops_variant_multipliers():
    v2 = get_preset("imagenet-moco-v2")
    per_image = model_fwd_flops("resnet50", 224, embed_dim=v2.embed_dim,
                                mlp_head=True)
    # v1/v2: query fwd+bwd (3) + key fwd (1)
    assert train_step_flops(v2) == per_image * 4 * v2.batch_size
    v3 = get_preset("imagenet-moco-v3-vits")
    per_image3 = model_fwd_flops("vit_small", 224, embed_dim=v3.embed_dim)
    # v3: both crops through query fwd+bwd (6) + momentum fwd (2)
    assert train_step_flops(v3) == per_image3 * 8 * v3.batch_size


def test_mfu_estimator_arithmetic_and_peak_table():
    est = MFUEstimator(flops_per_step=4e12, n_chips=8, peak_flops_per_chip=1e12)
    # 4e12 FLOPs in 1 s on 8 chips of 1 TFLOP/s = 50%
    assert est.mfu(1.0) == pytest.approx(0.5)
    assert est.mfu(0.0) is None
    assert MFUEstimator(1e9, 1, None).mfu(1.0) is None  # never fabricate
    assert detect_peak_flops("TPU v5e") == 197e12
    assert detect_peak_flops("TPU v5p") == 459e12  # v5p must not match "v5e"
    assert detect_peak_flops("TPU v4") == 275e12
    assert detect_peak_flops("cpu") is None
    config = get_preset("imagenet-moco-v2").replace(peak_flops_per_chip=2e12)
    est2 = MFUEstimator.for_config(config, n_chips=4, device_kind="TPU v4")
    assert est2.peak_flops_per_chip == 2e12  # explicit override wins


# ---------------------------------------------------------------------------
# phase timer
# ---------------------------------------------------------------------------


def test_phase_timer_monotonic_and_stride_fencing():
    import jax.numpy as jnp

    timer = StepPhaseTimer(stride=3)
    sync = jnp.ones(())
    records = []
    timer.epoch_start()
    for step in range(1, 10):
        with timer.phase("data_s"):
            pass
        with timer.phase("host_s"):
            pass
        fenced = timer.maybe_fence(step, sync)
        phases = timer.finish_step()
        records.append((step, fenced, phases))
    # fences land ONLY on stride multiples: 3, 6, 9
    assert [s for s, fenced, _ in records if fenced is not None] == [3, 6, 9]
    assert timer.fences == 3
    for _, fenced, p in records:
        assert p["data_s"] >= 0.0 and p["host_s"] >= 0.0 and p["step_s"] > 0.0
        # phases partition the iteration: the split never exceeds the whole
        assert p["data_s"] + p["host_s"] <= p["step_s"] + 1e-9
        assert ("device_s" in p) == (fenced is not None)
        if fenced is not None:
            assert p["device_s"] == fenced >= 0.0


def test_phase_timer_stride_zero_never_fences():
    timer = StepPhaseTimer(stride=0)
    timer.epoch_start()
    with timer.phase("data_s"):
        pass
    with timer.phase("host_s"):
        pass
    # sync object deliberately un-blockable: stride 0 must never touch it
    assert timer.maybe_fence(1, object()) is None
    assert timer.fences == 0
    assert "device_s" not in timer.finish_step()


# ---------------------------------------------------------------------------
# meters satellite: rolling throughput
# ---------------------------------------------------------------------------


def test_throughput_rolling_window_sheds_compile_stall(monkeypatch):
    from moco_tpu.utils import meters

    clock = {"t": 100.0}
    monkeypatch.setattr(meters.time, "perf_counter", lambda: clock["t"])
    tp = Throughput(num_chips=1, window=4)
    # first step: 10 s compile stall, then steady 0.1 s/step at 32 imgs
    clock["t"] += 10.0
    tp.update(32)
    for _ in range(8):
        clock["t"] += 0.1
        tp.update(32)
    cumulative = tp.imgs_per_sec
    rolling = tp.rolling_imgs_per_sec
    assert cumulative == pytest.approx(9 * 32 / 10.8)   # stall-polluted: ~27
    assert rolling == pytest.approx(32 / 0.1)           # steady state: 320
    # window=0 keeps the old cumulative-only behavior
    tp0 = Throughput(num_chips=1, window=0)
    clock["t"] += 1.0
    tp0.update(10)
    assert tp0.rolling_imgs_per_sec == tp0.imgs_per_sec


# ---------------------------------------------------------------------------
# logging satellites: event sinks + ScalarWriter drops
# ---------------------------------------------------------------------------


def test_log_event_sink_receives_structured_fields(capsys):
    seen = []
    sink = lambda kind, msg, fields: seen.append((kind, msg, fields))  # noqa: E731
    mlog.add_event_sink(sink)
    try:
        mlog.log_event("rollback", "restoring", step=12, rollback=1)
    finally:
        mlog.remove_event_sink(sink)
    assert seen == [("rollback", "restoring", {"step": 12, "rollback": 1})]
    assert "[rollback] restoring" in capsys.readouterr().out
    mlog.log_event("after", "sink removed")  # no sink, no error
    assert seen == [("rollback", "restoring", {"step": 12, "rollback": 1})]


def test_log_event_broken_sink_does_not_raise(capsys):
    def bad_sink(kind, msg, fields):
        raise RuntimeError("sink broke")

    mlog.add_event_sink(bad_sink)
    try:
        mlog.log_event("kind", "msg")
    finally:
        mlog.remove_event_sink(bad_sink)
    out = capsys.readouterr().out
    assert "[kind] msg" in out and "event sink failed" in out


class _FakeTBWriter:
    def __init__(self):
        self.written = []

    def add_scalar(self, name, value, step):
        self.written.append((name, float(value), step))

    def flush(self):
        self.flushed = True

    def close(self):
        pass


def test_scalar_writer_counts_and_surfaces_drops(capsys):
    w = mlog.ScalarWriter("")
    w._writer = _FakeTBWriter()  # bypass the tensorboardX import
    seen = []
    sink = lambda kind, msg, fields: seen.append((kind, fields))  # noqa: E731
    mlog.add_event_sink(sink)
    try:
        w.write(1, {"ok": 1.0, "bad": "not-a-number", "worse": object()})
        w.write(2, {"bad": "still-bad"})
    finally:
        mlog.remove_event_sink(sink)
    assert w.dropped == 3
    assert w._writer.written == [("ok", 1.0, 1)]
    # surfaced ONCE through log_event, not once per drop
    assert len(seen) == 1 and seen[0][0] == "scalar_writer"
    assert seen[0][1]["name"] == "bad"
    w.flush()
    assert w._writer.flushed


def test_scalar_writer_disabled_flush_and_write_noop():
    w = mlog.ScalarWriter("")
    w.write(0, {"x": 1})
    w.flush()
    w.close()
    assert w.dropped == 0


def test_percentiles_ms_shape():
    pct = percentiles_ms([0.001 * (i + 1) for i in range(100)])
    assert set(pct) == {"p50", "p95", "p99"}
    assert pct["p50"] <= pct["p95"] <= pct["p99"] <= 100.0


# ---------------------------------------------------------------------------
# acceptance: 30-step CPU smoke through the real driver
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def telemetry_run(mesh8, tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("telemetry_smoke")
    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=16,
        num_negatives=64, embed_dim=32, lr=0.1, epochs=2, steps_per_epoch=15,
        ckpt_dir="", tb_dir="", print_freq=5, num_classes=10,
        knn_monitor=False,
        telemetry_dir=str(tmp_path / "telemetry"),
        telemetry_flush_steps=8, telemetry_stride=5,
        peak_flops_per_chip=1e12,  # CPU has no table entry; MFU needs a basis
        # ISSUE 3: the smoke also exercises the parallel staging pipeline +
        # decode-once cache, so input metrics land in the same stream
        staging_workers=2, input_cache_mb=64,
    )
    from moco_tpu.train import train

    state, metrics = train(config, mesh8)
    return config, state, metrics


def test_train_30_steps_writes_parseable_events(telemetry_run):
    config, state, metrics = telemetry_run
    assert int(state.step) == 30
    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    records, skipped = report.load_events(events_path)
    assert skipped == 0
    assert all(r["v"] == SCHEMA_VERSION for r in records)

    starts = [r for r in records if r["kind"] == "run_start"]
    assert len(starts) == 1
    assert starts[0]["arch"] == "resnet_tiny"
    assert starts[0]["flops_per_step"] > 0
    assert starts[0]["peak_flops_per_chip"] == 1e12

    steps = [r for r in records if r["kind"] == "step"]
    assert [r["step"] for r in steps] == list(range(1, 31))
    for r in steps:
        assert r["step_s"] > 0 and r["data_s"] >= 0 and r["host_s"] >= 0
        assert r["imgs_per_sec"] >= 0 and r["imgs_per_sec_cum"] >= 0
        assert 0 <= r["mfu"] < 1.0  # tiny model on CPU: tiny but present
    # device fences exactly on the stride (5, 10, ..., 30)
    fenced = [r["step"] for r in steps if "device_s" in r]
    assert fenced == [5, 10, 15, 20, 25, 30]
    # HBM/RSS sampling shares the stride; CPU backends may omit HBM keys
    # but host RSS is always reported
    assert all("host_rss_bytes" in r and r["host_rss_bytes"] > 0
               for r in steps if r["step"] % 5 == 0)
    # loss rides the records where the print cadence synced it anyway
    assert any("loss" in r for r in steps)

    ends = [r for r in records if r["kind"] == "run_end"]
    assert len(ends) == 1
    assert ends[0]["steps"] == 30 and ends[0]["scalar_drops"] == 0
    assert ends[0]["step_s_p50"] > 0


def test_input_pipeline_metrics_in_events(telemetry_run):
    """ISSUE 3 acceptance: queue depth, cache hit rate, and staged-batch
    latency appear in events.jsonl (step records at the sampling stride +
    the run_end summary)."""
    config, _, _ = telemetry_run
    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    records, _ = report.load_events(events_path)
    steps = [r for r in records if r["kind"] == "step"]
    snaps = [r["input"] for r in steps if "input" in r]
    assert snaps, "no step record carried an input snapshot"
    for snap in snaps:
        assert snap["staged_batches"] > 0
        assert snap["workers"] == 2
        assert snap["queue_depth"] >= 0 and snap["queue_depth_mean"] >= 0
        assert snap["staged_batch_s_p95"] >= snap["staged_batch_s_p50"] > 0
        assert 0 <= snap["worker_busy_frac"] <= 1
        assert "cache_hit_rate" in snap  # the cache wrap was active
    end = [r for r in records if r["kind"] == "run_end"][-1]
    assert end["input"]["staged_batches"] >= snaps[-1]["staged_batches"]


def test_report_renders_input_pipeline(telemetry_run):
    config, _, _ = telemetry_run
    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    proc = subprocess.run(
        [sys.executable, REPORT, events_path], capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stderr
    assert "input:" in proc.stdout
    assert "staged-batch latency" in proc.stdout
    assert "decode-once cache" in proc.stdout
    as_json = subprocess.run(
        [sys.executable, REPORT, events_path, "--json"],
        capture_output=True, text=True,
    )
    summary = json.loads(as_json.stdout)
    assert summary["input"]["staged_batches"] > 0
    assert "cache_hit_rate" in summary["input"]


def test_heartbeat_written(telemetry_run):
    config, _, _ = telemetry_run
    hb_path = os.path.join(config.telemetry_dir, "heartbeat.json")
    with open(hb_path) as f:
        payload = json.load(f)
    assert payload["phase"] == "run_end"
    assert payload["pid"] == os.getpid()


def test_report_cli_renders_percentiles_and_mfu(telemetry_run):
    config, _, _ = telemetry_run
    events_path = os.path.join(config.telemetry_dir, "events.jsonl")
    proc = subprocess.run(
        [sys.executable, REPORT, events_path], capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stderr
    assert "p50" in proc.stdout and "p95" in proc.stdout
    assert "MFU: mean" in proc.stdout

    as_json = subprocess.run(
        [sys.executable, REPORT, events_path, "--json"],
        capture_output=True, text=True,
    )
    summary = json.loads(as_json.stdout)
    assert summary["steps"] == 30
    assert summary["step_time_ms"]["p50"] > 0
    assert summary["step_time_ms"]["p95"] >= summary["step_time_ms"]["p50"]
    assert summary["mfu"]["mean"] > 0


# ---------------------------------------------------------------------------
# pod aggregation
# ---------------------------------------------------------------------------


def test_pod_aggregator_folds_gathered_matrix(tmp_path):
    """The exact fold the driver performs on the allgathered per-host
    vectors (the 2-process harness exercises the wire path in
    tests/test_multihost.py where the environment supports multiprocess
    CPU; the fold math is pinned here either way)."""
    from moco_tpu.telemetry import POD_FIELDS, PodAggregator

    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=1)
    agg = PodAggregator(reg, n_procs=2, process_index=0)
    agg.update(step_s=0.2, data_s=0.01, imgs_per_sec=100.0,
               hbm_peak_bytes=1e9, host_rss_bytes=2e9, incidents=1)
    vec = agg.local_vector()
    assert vec.shape == (len(POD_FIELDS),)
    # host 1's vector: slower step, less memory, no incidents
    other = vec.copy()
    other[POD_FIELDS.index("step_s")] = 0.5
    other[POD_FIELDS.index("imgs_per_sec")] = 80.0
    other[POD_FIELDS.index("hbm_peak_bytes")] = 5e8
    other[POD_FIELDS.index("incidents")] = 0
    agg.record(16, np.stack([vec, other]))
    reg.close()

    records, _ = report.load_events(path)
    (pod,) = [r for r in records if r["kind"] == "pod"]
    assert pod["hosts"] == 2 and pod["step"] == 16
    assert pod["step_s_max"] == pytest.approx(0.5)
    assert pod["step_s_min"] == pytest.approx(0.2)
    assert pod["imgs_per_sec_sum"] == pytest.approx(180.0)
    assert pod["hbm_peak_bytes_max"] == int(1e9)
    assert pod["incidents_total"] == 1


def test_pod_aggregator_nonmain_is_silent(tmp_path):
    from moco_tpu.telemetry import PodAggregator

    reg = MetricsRegistry(None)
    agg = PodAggregator(reg, n_procs=2, process_index=1)
    agg.update(step_s=0.1)
    agg.record(4, np.stack([agg.local_vector()] * 2))  # no emit, no error
    assert reg.records_written == 0


# ---------------------------------------------------------------------------
# resilience integration: incidents land in the stream
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_rollback_emits_structured_incident(mesh8, tmp_path):
    """A NaN rollback must be visible to an external monitor: the sentinel
    detection and the retry's data-window advance both land as structured
    `event` records in the SAME events.jsonl the step records go to."""
    from moco_tpu.resilience import ChaosPlan, chaos_context
    from moco_tpu.train import train

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=16,
        num_negatives=64, embed_dim=32, lr=0.1, epochs=3, steps_per_epoch=4,
        ckpt_dir=str(tmp_path / "ckpt"), tb_dir="", print_freq=1000,
        num_classes=10, knn_monitor=False, max_rollbacks=3,
        telemetry_dir=str(tmp_path / "telemetry"),
        telemetry_flush_steps=4, telemetry_stride=0,
    )
    with chaos_context(ChaosPlan(nan_at_step=6)):
        state, metrics = train(config, mesh8)
    assert int(state.step) == 10 and np.isfinite(metrics["loss"])

    records, skipped = report.load_events(
        os.path.join(config.telemetry_dir, "events.jsonl"))
    assert skipped == 0
    incident_kinds = {r["event"] for r in records if r["kind"] == "event"}
    assert "sentinel" in incident_kinds, incident_kinds
    assert "rollback" in incident_kinds, incident_kinds
    # the retry appended to the SAME stream: two run_start records
    assert sum(r["kind"] == "run_start" for r in records) == 2
    summary = report.summarize(records, skipped)
    assert summary["incidents_total"] >= 2
    assert summary["runs"] == 2
