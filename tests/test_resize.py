"""Elastic training suite (ISSUE 11): checkpoint–resize–relaunch.

Layers:
  - unit: resize-request parse/claim, the chaos `resize_at_step` fault,
    exit-49 classification, argv rewrite + recorded-devices sidecar,
    controller arming, R5 coverage of the new exit path, report folds;
  - driver: the real train() honors a chaos resize — elastic checkpoint,
    `resized` metric, devices-stamped position sidecar, `resize_exit`
    heartbeat;
  - dialect shim: a quantized checkpoint saved under a 4-device mesh
    restores onto a 2-device mesh with fresh-zero [2, ...] accumulators —
    the restore every elastic relaunch performs;
  - stub-child e2e: the REAL Supervisor loop resizing stub children
    (request file consumed, SIGUSR2 delivered, argv rewritten, one
    compile cache across the relaunch, mesh_change preflight incident,
    `resize` span under the child span, report fold) in a couple of
    seconds;
  - the slow soak: a supervised real-CPU 1→2→1 device drill with zero
    manual steps, loss-curve continuity pinned against an uninterrupted
    run at the gradsync dialect-shim tolerance.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from moco_tpu.resilience.chaos import ChaosPlan, chaos_context, parse_chaos_spec
from moco_tpu.resilience.exitcodes import EXIT_RESIZE
from moco_tpu.resilience.resize import (
    ResizeController,
    ResizeListener,
    argv_device_count,
    consume_resize_request,
    parse_resize_request,
    pick_device_flag,
    read_honored_request,
    read_recorded_devices,
    write_resize_request,
)
from moco_tpu.resilience.supervisor import (
    CLASS_CLEAN,
    CLASS_RESIZE,
    FATAL_CLASSES,
    RestartPolicy,
    Supervisor,
    classify_exit,
    read_events_tail,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# request file protocol
# ---------------------------------------------------------------------------


def test_parse_resize_request_forms():
    req = parse_resize_request("devices=2 grad_sync_cadence=4")
    assert (req.devices, req.grad_sync_cadence, req.slow) == (2, 4, False)
    assert parse_resize_request("devices=2,slow=1").slow is True
    empty = parse_resize_request("")  # "resize to whatever is visible"
    assert empty.devices is None and empty.grad_sync_cadence is None
    with pytest.raises(ValueError, match="unknown resize request key"):
        parse_resize_request("device=2")  # the typo'd key must be loud
    with pytest.raises(ValueError, match="devices must be >= 1"):
        parse_resize_request("devices=0")
    with pytest.raises(ValueError, match="key=value"):
        parse_resize_request("devices")
    # ISSUE 15: a resize can flip the sharding mode for the relaunch
    assert parse_resize_request("devices=8 sharding=fsdp").sharding == "fsdp"
    assert parse_resize_request("devices=2").sharding is None
    with pytest.raises(ValueError, match="sharding"):
        parse_resize_request("sharding=zero3")


def test_resize_apply_carries_sharding_mode(tmp_path):
    """The relaunch argv carries the requested sharding mode (argparse
    last-wins append, like the device count) — a grow onto a pod can flip
    dp→fsdp in the same resize."""
    d = str(tmp_path)
    ctl = ResizeController(d)
    write_resize_request(d, devices=8, sharding="fsdp")
    req = ctl.poll()
    assert req is not None and req.sharding == "fsdp"
    req = ctl.take()  # the child exited EXIT_RESIZE; claim + disarm
    argv = ["python", "-m", "moco_tpu.train", "--fake-devices", "1"]
    summary = ctl.apply(req, argv)
    assert argv[-4:] == ["--fake-devices", "8", "--sharding", "fsdp"]
    assert summary["sharding"] == "fsdp"
    # a mode-less request appends nothing: the original argv's own
    # --sharding (if any) keeps winning
    write_resize_request(d, devices=2)
    req2 = ctl.poll(now=time.monotonic() + 1.0)  # past the poll gate
    assert req2 is not None
    req2 = ctl.take()
    argv2 = ["python", "-m", "moco_tpu.train", "--sharding", "fsdp",
             "--fake-devices", "8"]
    ctl.apply(req2, argv2)
    assert "--sharding" not in argv2[-2:]
    assert argv2.count("--sharding") == 1


def test_request_claimed_exactly_once(tmp_path):
    d = str(tmp_path)
    write_resize_request(d, devices=2, grad_sync_cadence=4)
    req = consume_resize_request(d)
    assert req.devices == 2 and req.grad_sync_cadence == 4
    # the claim is a rename: a second consumer (or a relaunched child)
    # finds nothing, but the PAYLOAD survives at the honored path for the
    # supervisor's take() fallback
    assert consume_resize_request(d) is None
    honored = read_honored_request(d)
    assert honored is not None and honored.devices == 2
    assert consume_resize_request(str(tmp_path / "empty")) is None


def test_unparseable_request_is_claimed_and_ignored(tmp_path):
    d = str(tmp_path)
    with open(os.path.join(d, "resize.request"), "w") as f:
        f.write("device=2\n")  # typo
    assert consume_resize_request(d) is None
    # claimed anyway: a malformed request must not re-fire every poll
    assert not os.path.exists(os.path.join(d, "resize.request"))


# ---------------------------------------------------------------------------
# chaos fault + classification
# ---------------------------------------------------------------------------


def test_chaos_resize_spec_and_fire_once(tmp_path):
    plan = parse_chaos_spec("resize_at_step=6,devices=2")  # ISSUE 11 spelling
    assert plan.resize_at_step == 6 and plan.resize_devices == 2
    assert parse_chaos_spec("resize_at_step=3,resize_devices=4").resize_devices == 4
    assert plan.maybe_resize(5) is None
    assert plan.maybe_resize(6) == 2
    assert plan.maybe_resize(6) is None  # fire-once in-process
    # marker persistence (MOCO_TPU_CHAOS_STATE): the resized relaunch
    # re-polls every later step and must never be re-poisoned
    state = str(tmp_path / "chaos_state")
    first = ChaosPlan(resize_at_step=4, resize_devices=2, state_dir=state)
    assert first.maybe_resize(4) == 2
    second = ChaosPlan(resize_at_step=4, resize_devices=2, state_dir=state)
    assert second.maybe_resize(4) is None


def test_classify_resize_restartable_without_backoff():
    cls, detail = classify_exit(EXIT_RESIZE)
    assert cls == CLASS_RESIZE
    assert "resize" in detail
    assert CLASS_RESIZE not in FATAL_CLASSES
    policy = RestartPolicy()
    assert CLASS_RESIZE in policy.restart_on
    assert CLASS_RESIZE in policy.no_backoff


# ---------------------------------------------------------------------------
# argv rewrite + recorded-devices sidecar
# ---------------------------------------------------------------------------


def test_argv_device_count_last_wins_both_forms():
    assert argv_device_count(["x", "--num-devices", "4"]) == 4
    assert argv_device_count(["x", "--fake-devices=8"]) == 8
    # argparse last-wins is what the resize append relies on
    assert argv_device_count(["--num-devices", "4", "--num-devices", "2"]) == 2
    assert argv_device_count(["--fake-devices", "0"]) is None  # 0 = off
    assert argv_device_count(["x", "--batch-size", "16"]) is None
    assert pick_device_flag(["--fake-devices", "8"]) == "--fake-devices"
    assert pick_device_flag(["--num-devices=4"]) == "--num-devices"
    assert pick_device_flag(["x"]) == "--num-devices"


def test_read_recorded_devices_newest_stamped_step(tmp_path):
    ckpt = tmp_path / "ckpt"
    pos = ckpt / ".position"
    pos.mkdir(parents=True)
    (ckpt / "4").mkdir()
    (ckpt / "8").mkdir()
    (pos / "4.json").write_text('{"epoch": 1, "batch": 0, "devices": 4}')
    (pos / "8.json").write_text('{"epoch": 2, "batch": 0}')  # pre-elastic
    # newest step (8) has no devices stamp: fall back to the newest that does
    assert read_recorded_devices(str(ckpt)) == (4, 4)
    (pos / "8.json").write_text('{"epoch": 2, "batch": 0, "devices": 2}')
    assert read_recorded_devices(str(ckpt)) == (8, 2)
    assert read_recorded_devices(str(tmp_path / "missing")) is None


def test_controller_arms_once_and_applies(tmp_path):
    d = str(tmp_path)
    ctl = ResizeController(d, slow_cadence=8)
    assert ctl.poll() is None  # nothing pending
    write_resize_request(d, devices=2, slow=True)
    ctl._last_poll = float("-inf")  # bypass the poll gate for the test
    req = ctl.poll()
    assert req is not None and req.devices == 2 and req.slow
    assert ctl.poll() is None  # armed: no re-arm until taken
    taken = ctl.take()
    assert taken is req
    argv = ["python", "-m", "moco_tpu.train", "--fake-devices", "1"]
    summary = ctl.apply(taken, argv)
    # appended, not edited (argparse last-wins): the operator argv stays
    # visible, the new count + the slow-link cadence override ride behind
    assert argv[-4:] == ["--fake-devices", "2", "--grad-sync-cadence", "8"]
    assert summary["devices_from"] == 1 and summary["devices_to"] == 2
    # honored payload deleted after apply: a later payload-less resize
    # must not inherit this one's device count
    assert read_honored_request(d) is None


def test_sigusr2_to_controller_arms_empty_request(tmp_path):
    ctl = ResizeController(str(tmp_path))
    ctl.signal_resize()
    req = ctl.poll()
    assert req is not None and req.source == "sigusr2" and req.devices is None
    assert ctl.poll() is None


def test_sigusr2_recovers_payload_the_child_already_claimed(tmp_path):
    """Operator writes the request, the CHILD's listener claims the file,
    THEN the SIGUSR2 lands: the supervisor must recover the target count
    from the honored payload instead of resizing to 'visible'."""
    d = str(tmp_path)
    write_resize_request(d, devices=3)
    assert consume_resize_request(d) is not None  # the child's claim
    ctl = ResizeController(d)
    ctl.signal_resize()
    req = ctl.poll()
    assert req is not None and req.devices == 3 and req.source == "sigusr2"


def test_listener_file_trigger_and_sigusr2(tmp_path):
    d = str(tmp_path)
    with ResizeListener(d, poll_secs=0.0) as listener:
        assert not listener.poll()
        write_resize_request(d, devices=2)
        assert listener.poll()  # file trigger, consumed on claim
        assert not os.path.exists(os.path.join(d, "resize.request"))
    with ResizeListener("", poll_secs=0.0) as listener:
        assert not listener.poll()
        signal.raise_signal(signal.SIGUSR2)
        assert listener.triggered
    # a TRIGGERED listener leaves SIGUSR2 ignored on exit: the elastic
    # checkpoint is written AFTER the ExitStack closes, and a late
    # supervisor signal restored to the DEFAULT disposition would
    # terminate the child mid-save (the drill caught exactly this)
    assert signal.getsignal(signal.SIGUSR2) is signal.SIG_IGN
    signal.raise_signal(signal.SIGUSR2)  # must be harmless now
    # an UNtriggered listener restores the previous handler
    prev = signal.signal(signal.SIGUSR2, signal.SIG_DFL)
    try:
        with ResizeListener("", poll_secs=0.0):
            pass
        assert signal.getsignal(signal.SIGUSR2) == signal.SIG_DFL
    finally:
        signal.signal(signal.SIGUSR2, prev)


# ---------------------------------------------------------------------------
# guardrails: R5 covers the new exit path
# ---------------------------------------------------------------------------


def test_r5_covers_resize_exit_path(tmp_path):
    """The new exit path speaks the named constant: a literal 49 anywhere
    in the package would silently fork the supervisor's protocol (lint
    rule R5), and train.py's resize exit routes through EXIT_RESIZE."""
    from tools import lint_robustness as lint

    (tmp_path / "bad.py").write_text("import sys\nsys.exit(49)\n")
    found = lint.check_file(str(tmp_path / "bad.py"))
    assert len(found) == 1 and "named constants" in found[0]
    with open(os.path.join(REPO, "moco_tpu", "train.py")) as f:
        source = f.read()
    assert "sys.exit(EXIT_RESIZE)" in source
    assert lint.check_file(os.path.join(REPO, "moco_tpu", "train.py")) == []


# ---------------------------------------------------------------------------
# report folds
# ---------------------------------------------------------------------------


def _sup_record(event, **fields):
    rec = {"v": 1, "t": 0.0, "kind": "supervisor", "event": event}
    rec.update(fields)
    return rec


def test_report_resize_section_and_follow_lines():
    sys.path.insert(0, REPO)
    from tools.telemetry_report import render, render_record, summarize

    records = [
        _sup_record("launch", attempt=0),
        _sup_record("resize_request", source="request", devices=2),
        _sup_record("exit", classification="resize", returncode=49),
        _sup_record("resize_relaunch", source="request", devices_from=1,
                    devices_to=2, step=6, grad_sync_cadence=4),
        _sup_record("mesh_change", ckpt_step=6, devices_from=1,
                    devices_to=2),
        _sup_record("launch", attempt=1),
        _sup_record("exit", classification="clean", returncode=0),
        _sup_record("done", launches=2, restarts=1),
    ]
    summary = summarize(records)
    rsz = summary["resize"]
    assert rsz["requests"] == 1 and rsz["relaunches"] == 1
    assert rsz["mesh_changes"] == 1
    assert rsz["transitions"] == [{
        "devices_from": 1, "devices_to": 2, "step": 6,
        "grad_sync_cadence": 4, "source": "request",
    }]
    text = render(summary)
    assert "resize: 1 relaunch(es)" in text
    assert "1→2@6 (cadence 4)" in text
    assert "mesh changes observed at relaunch preflight: 1" in text
    # --follow: resize transitions get their own prefix, like fleet lines
    line = render_record(records[3])
    assert line.startswith("resize: resize_relaunch")
    assert render_record(records[4]).startswith("resize: mesh_change")
    assert render_record(records[0]).startswith("supervisor: launch")


# ---------------------------------------------------------------------------
# dialect shim: the restore every elastic relaunch performs
# ---------------------------------------------------------------------------


def test_dialect_shim_restores_across_mesh_size_change(tmp_path):
    """A quantized checkpoint saved under a 4-device mesh restored by a
    2-device run (the 1→2→1 drill's legs, one mesh hop): the shim detects
    the [n_dev, ...] accumulator mismatch, restores everything else
    exactly, and rebuilds the accumulators fresh-zero on the NEW mesh —
    with the saved mesh size recorded for the supervisor's preflight."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moco_tpu.checkpoint import (
        checkpoint_manager,
        maybe_resume,
        save_checkpoint,
    )
    from moco_tpu.config import PretrainConfig
    from moco_tpu.parallel.gradsync import GradSync
    from moco_tpu.parallel.mesh import create_mesh, replicated
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_encoder, build_optimizer

    config = PretrainConfig(
        variant="v1", arch="resnet_tiny", cifar_stem=True, num_negatives=64,
        embed_dim=16, batch_size=16, epochs=2, lr=0.1,
        grad_sync="quantized", grad_sync_bucket_mb=0.05,
    )

    def build(mesh):
        model = build_encoder(config)
        tx, _sched = build_optimizer(config, 8)
        state = create_train_state(
            jax.random.key(0), model, tx, (16 // mesh.size, 16, 16, 3),
            64, 16,
        )
        return GradSync(config, mesh.size).attach(state, mesh)

    mesh4 = create_mesh(4)
    state4 = build(mesh4)
    # non-zero accumulators: the restore must DISCARD them, not carry them
    state4 = state4.replace(
        gradsync=jax.tree.map(jnp.ones_like, state4.gradsync))
    for leaf in jax.tree.leaves(state4.gradsync["acc"]):
        assert leaf.shape[0] == 4
    mgr = checkpoint_manager(str(tmp_path / "ckpt"))
    save_checkpoint(mgr, state4, 3, position=(0, 3), devices=mesh4.size)
    assert read_recorded_devices(str(tmp_path / "ckpt")) == (3, 4)

    mesh2 = create_mesh(2)
    fresh2 = build(mesh2)
    restored = maybe_resume(mgr, fresh2, "auto", sharding=replicated(mesh2))
    assert int(restored.step) == int(state4.step)
    for a, b in zip(jax.tree.leaves(restored.params_q),
                    jax.tree.leaves(state4.params_q), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for leaf in jax.tree.leaves(restored.gradsync["acc"]):
        assert leaf.shape[0] == 2          # the NEW mesh's accumulator
        assert float(jnp.max(jnp.abs(leaf))) == 0.0  # fresh zeros


# ---------------------------------------------------------------------------
# driver: the real train() honors a chaos resize
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_driver_chaos_resize_elastic_checkpoint(mesh8, tmp_path):
    from moco_tpu.config import get_preset
    from moco_tpu.train import train

    tdir = tmp_path / "telemetry"
    cfg = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=16,
        num_negatives=64, embed_dim=32, lr=0.1, epochs=3, steps_per_epoch=4,
        ckpt_dir=str(tmp_path / "ckpt"), tb_dir="", print_freq=1000,
        num_classes=10, knn_monitor=False, telemetry_dir=str(tdir),
        heartbeat_secs=0.0,
    )
    with chaos_context(ChaosPlan(resize_at_step=6, resize_devices=2)):
        _state, metrics = train(cfg, mesh8)
    assert metrics.get("resized") is True
    # elastic checkpoint at the fault step, mesh size recorded for the
    # supervisor's preflight
    assert read_recorded_devices(cfg.ckpt_dir) == (6, 8)
    # the chaos drill left the target count where the supervisor looks
    req = consume_resize_request(str(tdir))
    assert req is not None and req.devices == 2
    # the exit heartbeat says a resize relaunch is expected
    with open(tdir / "heartbeat.json") as f:
        hb = json.load(f)
    assert hb["phase"] == "resize_exit" and hb["step"] == 6


# ---------------------------------------------------------------------------
# stub-child e2e: the real Supervisor loop, seconds-cheap children
# ---------------------------------------------------------------------------

_STUB = textwrap.dedent("""\
    import json, os, signal, sys, time
    tdir, state_path, ckpt_dir = sys.argv[1], sys.argv[2], sys.argv[3]
    plan = sys.argv[4].split(",")
    extra = sys.argv[5:]
    n = 0
    if os.path.exists(state_path):
        n = int(open(state_path).read())
    open(state_path, "w").write(str(n + 1))
    with open(os.path.join(tdir, "argv_%d.json" % n), "w") as f:
        json.dump(extra, f)
    with open(os.path.join(tdir, "env_%d.json" % n), "w") as f:
        json.dump({"cache": os.environ.get("JAX_COMPILATION_CACHE_DIR", "")}, f)
    def beat(step, phase="step"):
        p = os.path.join(tdir, "heartbeat.json")
        with open(p + ".tmp", "w") as f:
            json.dump({"v": 1, "t": round(time.time(), 3), "step": step,
                       "pid": os.getpid(), "phase": phase}, f)
        os.replace(p + ".tmp", p)
    def ckpt(step, devices):
        d = os.path.join(ckpt_dir, str(step))
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "payload.bin"), "wb") as f:
            f.write(b"x" * 64)
        pd = os.path.join(ckpt_dir, ".position")
        os.makedirs(pd, exist_ok=True)
        with open(os.path.join(pd, "%d.json" % step), "w") as f:
            json.dump({"epoch": 0, "batch": step, "devices": devices}, f)
    behavior = plan[min(n, len(plan) - 1)]
    kind, _, arg = behavior.partition(":")
    if kind == "resize49":
        # beat, write an "elastic checkpoint" (step/devices from arg),
        # linger so the supervisor's poll can arm + signal, then exit 49
        step, devices = (int(x) for x in arg.split("/"))
        signal.signal(signal.SIGUSR2, signal.SIG_IGN)
        beat(step)
        ckpt(step, devices)
        time.sleep(0.6)
        sys.exit(49)
    elif kind == "usr2exit":
        # honor SIGUSR2 like the real driver's ResizeListener path
        signal.signal(signal.SIGUSR2, lambda *a: sys.exit(49))
        beat(int(arg or 2))
        time.sleep(30)
        sys.exit(1)
    elif kind == "exit":
        beat(2)
        sys.exit(int(arg))
    elif kind == "ok":
        beat(int(arg or 5))
        sys.exit(0)
    else:
        raise SystemExit("unknown stub behavior %r" % behavior)
""")


def _stub_supervisor(tmp_path, plan, argv_extra=(), **sup_kw):
    stub = tmp_path / "stub.py"
    stub.write_text(_STUB)
    tdir = tmp_path / "telemetry"
    tdir.mkdir(exist_ok=True)
    ckpt = tmp_path / "ckpt"
    policy = RestartPolicy(
        max_restarts=3, heartbeat_stale_secs=10.0, startup_grace_secs=10.0,
        term_grace_secs=1.0, backoff_base_secs=0.05, backoff_max_secs=0.2,
        backoff_jitter=0.0, poll_secs=0.1,
    )
    return Supervisor(
        [sys.executable, str(stub), str(tdir), str(tmp_path / "attempts"),
         str(ckpt), plan, *argv_extra],
        telemetry_dir=str(tdir),
        ckpt_dir=str(ckpt),
        policy=policy,
        seed=0,
        **sup_kw,
    ), tdir


def test_e2e_request_file_resize_rewrites_relaunch(tmp_path, monkeypatch):
    """The whole supervisor-side loop on a stub child: a pending
    resize.request is armed and consumed, the child's 49 relaunches with
    the device flag appended and the SAME compile cache (placed from
    outside; a relaunch must find what its predecessor compiled), the
    mesh_change preflight fires (sidecar says 1, argv now says 2), and
    the incident lands as resize events + a `resize` span under the
    child span."""
    cache = tmp_path / "cacheroot"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    sup, tdir = _stub_supervisor(
        tmp_path, "resize49:4/1,ok:8", argv_extra=("--fake-devices", "1"),
    )
    write_resize_request(str(tdir), devices=2)
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.classifications == [CLASS_RESIZE, CLASS_CLEAN]
    assert result.restarts == 1 and not result.gave_up
    # no backoff: a resize exit is voluntary
    assert [r for r in sup.incidents if r["event"] == "backoff"] == []
    requests = [r for r in sup.incidents if r["event"] == "resize_request"]
    assert requests and requests[0]["devices"] == 2
    relaunches = [r for r in sup.incidents
                  if r["event"] == "resize_relaunch"]
    assert len(relaunches) == 1
    assert relaunches[0]["devices_from"] == 1
    assert relaunches[0]["devices_to"] == 2
    # preflight membership check: recorded mesh 1 vs relaunch argv 2
    changes = [r for r in sup.incidents if r["event"] == "mesh_change"]
    assert changes and (changes[0]["devices_from"],
                        changes[0]["devices_to"]) == (1, 2)
    # the relaunch argv carries the new count AND --resume auto
    with open(tdir / "argv_1.json") as f:
        argv1 = json.load(f)
    assert argv1[-4:] == ["--fake-devices", "2", "--resume", "auto"]
    # one cache across the resize: no per-run or per-resize directory
    for n in (0, 1):
        with open(tdir / f"env_{n}.json") as f:
            assert json.load(f)["cache"] == str(cache)
    assert not cache.exists() or os.listdir(cache) == []
    # one traced incident: a `resize` span parented under a child span
    spans = read_events_tail(os.path.join(str(tdir), "spans.jsonl"))
    child_ids = {s["span"] for s in spans if s.get("name") == "child"}
    resize_spans = [s for s in spans if s.get("name") == "resize"]
    assert resize_spans and resize_spans[0]["parent"] in child_ids
    assert resize_spans[0]["attrs"]["devices_to"] == 2
    # the report folds the same stream
    from tools.telemetry_report import summarize

    records = read_events_tail(os.path.join(str(tdir), "events.jsonl"),
                               max_bytes=1 << 20)
    summary = summarize(records)
    assert summary["resize"]["relaunches"] == 1
    assert summary["supervisor"]["classifications"] == ["resize", "clean"]


def test_e2e_sigusr2_resize_without_payload(tmp_path):
    """SIGUSR2 to the SUPERVISOR with no request file: the child is
    signaled (the stub exits 49 from its handler, like the driver's
    listener), and the relaunch keeps the argv's own device flags."""
    sup, tdir = _stub_supervisor(
        tmp_path, "usr2exit:2,ok:9", argv_extra=("--fake-devices", "1"),
    )
    runner = threading.Thread(target=lambda: setattr(
        sup, "_test_result", sup.run()))
    runner.start()
    time.sleep(0.5)  # child up and beating
    sup.resize.signal_resize()  # what the CLI's SIGUSR2 handler calls
    runner.join(timeout=30)
    assert not runner.is_alive()
    result = sup._test_result
    assert result.final_class == CLASS_CLEAN
    assert result.classifications == [CLASS_RESIZE, CLASS_CLEAN]
    relaunches = [r for r in sup.incidents
                  if r["event"] == "resize_relaunch"]
    assert relaunches and relaunches[0]["devices_to"] is None
    assert relaunches[0]["source"] == "sigusr2"
    with open(tdir / "argv_1.json") as f:
        argv1 = json.load(f)
    assert argv1.count("--fake-devices") == 1  # untouched: no target count


def test_e2e_unbootable_resize_reverts_instead_of_dying(tmp_path):
    """A typo'd device count (more devices than the hardware has) makes
    the resized argv exit config_error at boot. The supervisor must
    REVERT the appended flags and finish the run on the old mesh — a bad
    resize request must not take a healthy run down (and must not grind
    the restart budget on a fatal class either)."""
    # launch 0 resizes; launch 1 (the resized argv) dies 45; launch 2
    # (reverted argv) finishes clean
    sup, tdir = _stub_supervisor(
        tmp_path, "resize49:4/1,exit:45,ok:8",
        argv_extra=("--fake-devices", "1"),
    )
    write_resize_request(str(tdir), devices=100)
    base_len = len(sup.child_argv)
    result = sup.run()
    assert result.final_class == CLASS_CLEAN, result
    assert result.classifications == [CLASS_RESIZE, "config_error",
                                      CLASS_CLEAN]
    reverts = [r for r in sup.incidents if r["event"] == "resize_revert"]
    assert reverts and reverts[0]["dropped"] == ["--fake-devices", "100"]
    assert len(sup.child_argv) == base_len  # appended flags gone
    with open(tdir / "argv_2.json") as f:
        argv2 = json.load(f)
    assert "100" not in argv2
    # report folds the revert
    from tools.telemetry_report import render, summarize

    summary = summarize(sup.incidents)
    assert summary["resize"]["reverts"] == 1
    assert "1 reverted (unbootable argv)" in render(summary)


def test_take_path_still_records_the_request(tmp_path):
    """A resize the child honored before the supervisor's poll armed it
    (the chaos drill shape: request written + exit 49 within one poll
    cycle) must still land a resize_request record — a report reading
    'relaunches from 0 requests' looks like resizes nobody asked for."""
    sup, tdir = _stub_supervisor(
        tmp_path, "exit:49,ok:8", argv_extra=("--fake-devices", "1"),
    )
    # freeze the controller's file poll: the monitor never arms, so only
    # take() can claim the request
    sup.resize._last_poll = float("inf")
    write_resize_request(str(tdir), devices=2)
    result = sup.run()
    assert result.classifications == [CLASS_RESIZE, CLASS_CLEAN]
    requests = [r for r in sup.incidents if r["event"] == "resize_request"]
    assert len(requests) == 1 and requests[0]["devices"] == 2
    from tools.telemetry_report import summarize

    summary = summarize(sup.incidents)
    assert summary["resize"]["requests"] == 1
    assert summary["resize"]["relaunches"] == 1


# ---------------------------------------------------------------------------
# the full drill: supervised 1→2→1 on the CPU proxy, zero manual steps
# ---------------------------------------------------------------------------


def _drill_argv(tdir, ckpt_dir):
    return [
        sys.executable, "-m", "moco_tpu.train",
        "--preset", "cifar10-moco-v1", "--fake-devices", "1",
        "--arch", "resnet_tiny", "--dataset", "synthetic",
        "--image-size", "16", "--batch-size", "16",
        "--num-negatives", "64", "--embed-dim", "32", "--lr", "0.1",
        "--epochs", "6", "--steps-per-epoch", "4", "--print-freq", "1",
        "--knn-monitor", "false", "--num-classes", "10",
        "--watchdog-secs", "0",
        # quantized gradsync: per-device error-feedback accumulators — the
        # state the dialect shim rebuilds fresh-zero at each mesh hop (the
        # bounded-divergence contract the continuity pin runs at);
        # sync_bn keeps the BN statistics mesh-size-invariant so the mesh
        # hops themselves are not a second, unbounded divergence source
        "--grad-sync", "quantized", "--sync-bn", "true",
        "--telemetry-dir", str(tdir), "--telemetry-flush-steps", "4",
        "--heartbeat-secs", "0.05", "--ckpt-dir", str(ckpt_dir),
    ]


def _drill_env(chaos="", chaos_state=""):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MOCO_TPU_NO_CACHE"] = "1"  # throwaway drill children
    if chaos:
        env["MOCO_TPU_CHAOS"] = chaos
        env["MOCO_TPU_CHAOS_STATE"] = chaos_state
    else:
        env.pop("MOCO_TPU_CHAOS", None)
        env.pop("MOCO_TPU_CHAOS_STATE", None)
    return env


def _step_losses(events_path):
    losses = {}
    for rec in read_events_tail(events_path, max_bytes=1 << 22):
        if rec.get("kind") == "step" and "loss" in rec:
            losses[int(rec["step"])] = float(rec["loss"])
    return losses


@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_resize_drill_1_2_1_loss_continuity(tmp_path):
    """ISSUE 11 acceptance: a supervised CPU run resizes 1→2 (chaos
    `resize_at_step`, the deterministic drill) and back 2→1 (an operator
    resize.request — the file-trigger path) with ZERO manual steps: the
    supervisor consumes each request, the child exits 49 with a verified
    elastic checkpoint, the relaunch restores onto the new mesh via the
    dialect shim (fresh-zero accumulators, logged `ckpt-dialect` events),
    and the final loss matches an uninterrupted run within the gradsync
    shim's bounded-divergence tolerance (the EF state restarts from
    zeros at each hop). The whole story is one run_id of resize events,
    rendered by telemetry_report."""
    import numpy as np

    # uninterrupted 1-device reference, same subprocess environment
    ref_t = tmp_path / "ref_telemetry"
    ref_ckpt = tmp_path / "ref_ckpt"
    proc = subprocess.run(
        _drill_argv(ref_t, ref_ckpt), env=_drill_env(),
        capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    ref_losses = _step_losses(os.path.join(str(ref_t), "events.jsonl"))
    assert 24 in ref_losses

    # supervised drill: chaos fires the 1→2 resize at step 5; the slow
    # stall at step 9 (fire-once, so only the SECOND child hits it) holds
    # the 2-device leg open while the test drops the operator's 2→1
    # request — the supervisor does everything else
    sup_t = tmp_path / "sup_telemetry"
    sup_ckpt = tmp_path / "sup_ckpt"
    sup_t.mkdir()
    sup = Supervisor(
        _drill_argv(sup_t, sup_ckpt),
        telemetry_dir=str(sup_t),
        ckpt_dir=str(sup_ckpt),
        env=_drill_env(
            chaos="resize_at_step=5,devices=2,slow_at_step=9,slow_ms=8000",
            chaos_state=str(tmp_path / "chaos_state"),
        ),
        policy=RestartPolicy(
            max_restarts=4, heartbeat_stale_secs=60.0,
            startup_grace_secs=600.0, term_grace_secs=3.0,
            backoff_base_secs=0.1, backoff_max_secs=1.0, poll_secs=0.25,
        ),
        seed=0,
    )

    def drop_request_when_second_leg_runs():
        # wait for the 2-device child to be stepping (any beat past the
        # resize step), then file the operator's scale-back request; the
        # 8 s chaos stall at step 9 keeps the child alive while the
        # supervisor consumes the file and SIGUSR2s it
        deadline = time.monotonic() + 600
        hb_path = os.path.join(str(sup_t), "heartbeat.json")
        while time.monotonic() < deadline:
            try:
                with open(hb_path) as f:
                    hb = json.load(f)
                if hb.get("phase") == "step" and int(hb.get("step", 0)) > 5:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.05)
        write_resize_request(str(sup_t), devices=1)

    requester = threading.Thread(target=drop_request_when_second_leg_runs)
    requester.start()
    result = sup.run()
    requester.join(timeout=10)
    assert result.final_class == CLASS_CLEAN, result
    assert not result.gave_up
    assert result.classifications == [CLASS_RESIZE, CLASS_RESIZE,
                                      CLASS_CLEAN], result

    # both relaunches rewrote the argv: 1→2, then 2→1
    relaunches = [r for r in sup.incidents
                  if r["event"] == "resize_relaunch"]
    assert [(r["devices_from"], r["devices_to"]) for r in relaunches] == \
        [(1, 2), (2, 1)]

    events_path = os.path.join(str(sup_t), "events.jsonl")
    records = read_events_tail(events_path, max_bytes=1 << 22)
    # every record of the incident carries ONE run id
    run_ids = {r.get("run_id") for r in records if r.get("run_id")}
    assert run_ids == {sup.run_id}
    # the dialect shim fired at each mesh hop (fresh-zero accumulators)
    dialect = [r for r in records if r.get("kind") == "event"
               and r.get("event") == "ckpt-dialect"]
    assert len(dialect) >= 2, dialect

    # loss-curve continuity: the drill ends where the uninterrupted run
    # ends, within the bounded-divergence tolerance the gradsync dialect
    # shim promises (PR 6 pins quantized-vs-exact at <= 5%; each hop only
    # resets EF state to its cold-start zeros)
    sup_losses = _step_losses(events_path)
    assert 24 in sup_losses, sorted(sup_losses)
    # the 1-device leg before the first resize is the SAME program on the
    # same data: bitwise-equal losses, not merely close
    for step in range(1, 5):
        assert sup_losses[step] == ref_losses[step], step
    final_ref, final_sup = ref_losses[24], sup_losses[24]
    assert abs(final_sup - final_ref) <= 0.05 * abs(final_ref), (
        f"final loss diverged past the shim tolerance: "
        f"ref={final_ref} resized={final_sup}"
    )

    # the whole incident renders as one story
    report = os.path.join(REPO, "tools", "telemetry_report.py")
    out = subprocess.run([sys.executable, report, events_path],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "resize: 2 relaunch(es)" in out.stdout, out.stdout
    as_json = subprocess.run([sys.executable, report, events_path, "--json"],
                             capture_output=True, text=True)
    summary = json.loads(as_json.stdout)
    assert summary["resize"]["relaunches"] == 2
    assert [t["devices_to"] for t in summary["resize"]["transitions"]] == \
        [2, 1]
    np.testing.assert_allclose(final_sup, final_ref, rtol=0.05)
