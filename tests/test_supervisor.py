"""Out-of-process supervisor suite (ISSUE 4).

Three layers:
  - pure unit tests: exit classification, backoff, restart-budget refund,
    events-tail forensics, resume preflight, chaos kill/freeze parsing and
    cross-process fire-once state — no child processes, no jax;
  - stub-child e2e: the REAL Supervisor loop driving tiny python stub
    children (hang → SIGTERM→grace→SIGKILL escalation + restart, crash
    loop → budget exhaustion, fatal classes → no restart, preemption →
    immediate relaunch) in a couple of seconds, tier-1 friendly;
  - the full chaos soak (slow+chaos): a real CPU training run supervised
    through kill@step + freeze@step faults, final state bit-identical to
    an uninterrupted supervised run, incidents rendered by
    tools/telemetry_report.py.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from moco_tpu.resilience.chaos import ChaosPlan, parse_chaos_spec
from moco_tpu.resilience.exitcodes import (
    EXIT_PREEMPTED,
    EXIT_ROLLBACK_EXHAUSTED,
)
from moco_tpu.resilience.supervisor import (
    CLASS_CLEAN,
    CLASS_CRASH,
    CLASS_HANG,
    CLASS_KILLED,
    CLASS_NATIVE_CRASH,
    CLASS_OOM,
    CLASS_PREEMPTED,
    CLASS_ROLLBACK_EXHAUSTED,
    QUARANTINE_DIRNAME,
    RestartPolicy,
    Supervisor,
    classify_exit,
    preflight_resume,
    read_events_tail,
    read_heartbeat,
    tail_rss_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_classify_named_exit_codes():
    assert classify_exit(0)[0] == CLASS_CLEAN
    assert classify_exit(EXIT_PREEMPTED)[0] == CLASS_PREEMPTED
    assert classify_exit(EXIT_ROLLBACK_EXHAUSTED)[0] == CLASS_ROLLBACK_EXHAUSTED
    assert classify_exit(45)[0] == "config_error"
    assert classify_exit(2)[0] == "config_error"  # argparse usage error
    assert classify_exit(46)[0] == "data_quality"
    # ISSUE 5: a bind failure must never restart-loop against the same
    # occupied socket — fatal class, matching the README table
    from moco_tpu.resilience.supervisor import FATAL_CLASSES

    assert classify_exit(47)[0] == "serve_bind"
    assert "serve_bind" in FATAL_CLASSES
    assert classify_exit(1)[0] == CLASS_CRASH
    assert classify_exit(77)[0] == CLASS_CRASH  # unknown positive code


def test_classify_signal_deaths():
    assert classify_exit(-int(signal.SIGSEGV))[0] == CLASS_NATIVE_CRASH
    assert classify_exit(-int(signal.SIGABRT))[0] == CLASS_NATIVE_CRASH
    assert classify_exit(-int(signal.SIGBUS))[0] == CLASS_NATIVE_CRASH
    assert classify_exit(-int(signal.SIGKILL))[0] == CLASS_KILLED
    assert classify_exit(-int(signal.SIGTERM))[0] == CLASS_KILLED


def test_classify_hang_wins_over_exit_code():
    """A SIGTERM-responsive hang exits EXIT_PREEMPTED on the way down —
    the supervisor's own kill decision must still classify it as a hang
    (it gets the restart, but the record says why it died)."""
    cls, detail = classify_exit(EXIT_PREEMPTED, hang_killed=True)
    assert cls == CLASS_HANG
    assert "staleness" in detail


def test_classify_oom_from_events_tail():
    tail = [
        {"kind": "step", "step": 9, "host_rss_bytes": 2e9},
        {"kind": "step", "step": 10, "host_rss_bytes": 9e9},
        {"kind": "event", "event": "watchdog"},
    ]
    assert classify_exit(-9, events_tail=tail, oom_rss_bytes=8e9)[0] == CLASS_OOM
    # below the threshold, or with no threshold configured: external kill
    assert classify_exit(-9, events_tail=tail, oom_rss_bytes=1e10)[0] == CLASS_KILLED
    assert classify_exit(-9, events_tail=tail)[0] == CLASS_KILLED
    assert tail_rss_bytes(tail) == 9e9
    assert tail_rss_bytes([]) == 0.0


def test_read_events_tail_skips_torn_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write('{"kind": "step", "step": 1}\n')
        f.write('{"kind": "step", "step": 2}\n')
        f.write('{"kind": "step", "ste')  # torn tail: SIGKILL mid-flush
    records = read_events_tail(path)
    assert [r["step"] for r in records] == [1, 2]
    assert read_events_tail(str(tmp_path / "missing.jsonl")) == []


def test_read_heartbeat_absent_or_torn(tmp_path):
    path = str(tmp_path / "heartbeat.json")
    assert read_heartbeat(path) is None
    with open(path, "w") as f:
        f.write('{"step": 4')
    assert read_heartbeat(path) is None
    with open(path, "w") as f:
        json.dump({"step": 4, "pid": 123}, f)
    assert read_heartbeat(path) == {"step": 4, "pid": 123}


# ---------------------------------------------------------------------------
# backoff + budget
# ---------------------------------------------------------------------------


def test_backoff_exponential_capped_jittered():
    import random

    p = RestartPolicy(backoff_base_secs=1.0, backoff_max_secs=8.0,
                      backoff_jitter=0.0)
    rng = random.Random(0)
    assert [p.backoff_secs(n, rng) for n in (1, 2, 3, 4, 5)] == \
        [1.0, 2.0, 4.0, 8.0, 8.0]
    jittered = RestartPolicy(backoff_base_secs=1.0, backoff_max_secs=8.0,
                             backoff_jitter=0.5)
    vals = [jittered.backoff_secs(1, random.Random(s)) for s in range(32)]
    assert all(1.0 <= v <= 1.5 for v in vals)
    assert len(set(vals)) > 1  # jitter actually varies


def _bare_supervisor(tmp_path, **policy_kw):
    return Supervisor(
        ["true"], telemetry_dir=str(tmp_path),
        policy=RestartPolicy(**policy_kw),
    )


def test_budget_consumed_by_no_progress_refunded_by_progress(tmp_path):
    sup = _bare_supervisor(tmp_path, max_restarts=2)
    assert sup._note_exit(progressed=False)   # budget 2 -> 1
    assert sup._note_exit(progressed=False)   # budget 1 -> 0
    assert not sup._note_exit(progressed=False)  # exhausted: crash loop
    sup = _bare_supervisor(tmp_path, max_restarts=2)
    assert sup._note_exit(progressed=False)
    assert sup._note_exit(progressed=True)    # progress refunds the budget
    assert sup._note_exit(progressed=False)
    assert sup._note_exit(progressed=False)
    assert not sup._note_exit(progressed=False)


def test_zero_budget_never_restarts(tmp_path):
    sup = _bare_supervisor(tmp_path, max_restarts=0)
    assert not sup._note_exit(progressed=True)


def test_progress_marker_prefers_heartbeat_falls_back_to_ckpt(tmp_path):
    ckpt = tmp_path / "ckpt"
    (ckpt / "8").mkdir(parents=True)
    sup = Supervisor(["true"], telemetry_dir=str(tmp_path),
                     ckpt_dir=str(ckpt))
    assert sup._progress_marker() == 8  # no heartbeat yet: newest ckpt step
    with open(tmp_path / "heartbeat.json", "w") as f:
        json.dump({"step": 11, "pid": 1}, f)
    assert sup._progress_marker() == 11


# ---------------------------------------------------------------------------
# resume-integrity preflight
# ---------------------------------------------------------------------------


def _fake_ckpt_step(ckpt_dir, step, manifest=True):
    d = ckpt_dir / str(step)
    d.mkdir(parents=True)
    (d / "payload.bin").write_bytes(b"x" * 2048)
    if manifest:
        from moco_tpu.resilience.integrity import write_manifest

        write_manifest(str(ckpt_dir), step)


def test_preflight_quarantines_corrupt_newest_stops_at_survivor(tmp_path):
    ckpt = tmp_path / "ckpt"
    _fake_ckpt_step(ckpt, 4, manifest=False)  # pre-manifest: never touched
    _fake_ckpt_step(ckpt, 8)
    _fake_ckpt_step(ckpt, 12)
    _fake_ckpt_step(ckpt, 16)
    (ckpt / "16" / "payload.bin").write_bytes(b"y" * 1024)  # corrupt newest
    (ckpt / "12" / "payload.bin").write_bytes(b"z" * 1024)  # and the next
    emitted = []
    gone = preflight_resume(str(ckpt), emit=lambda e, **f: emitted.append((e, f)))
    # newest-first: 16 and 12 quarantined, the walk STOPS at verifying 8 —
    # resume only ever reads the newest survivor, so older steps are not
    # re-hashed on every relaunch
    assert gone == [16, 12]
    assert sorted(n for n in os.listdir(ckpt) if n.isdigit()) == ["4", "8"]
    assert os.path.isdir(ckpt / QUARANTINE_DIRNAME / "16")
    assert os.path.isdir(ckpt / QUARANTINE_DIRNAME / "12")
    # the corrupt steps' sidecars must not survive as dangling references
    assert not os.path.exists(ckpt / ".integrity" / "16.json")
    assert [e for e, _ in emitted] == ["preflight_quarantine"] * 2
    assert [f["step"] for _, f in emitted] == [16, 12]
    # second pass: newest (8) verifies immediately, nothing to do
    assert preflight_resume(str(ckpt)) == []
    assert preflight_resume(str(tmp_path / "missing")) == []


def test_preflight_manifestless_newest_stops_walk(tmp_path):
    """A manifest-less newest step verifies vacuously (restore is then the
    gate) and ends the walk — a corrupt step behind it is unreachable
    except through the child's own per-candidate walk-back."""
    ckpt = tmp_path / "ckpt"
    _fake_ckpt_step(ckpt, 8)
    (ckpt / "8" / "payload.bin").write_bytes(b"y" * 1024)  # corrupt, behind
    _fake_ckpt_step(ckpt, 12, manifest=False)
    assert preflight_resume(str(ckpt)) == []
    assert sorted(n for n in os.listdir(ckpt) if n.isdigit()) == ["12", "8"]


# ---------------------------------------------------------------------------
# chaos kill/freeze plumbing
# ---------------------------------------------------------------------------


def test_parse_chaos_spec_kill_and_freeze():
    plan = parse_chaos_spec("kill_at_step=6,freeze_at_step=9")
    assert plan.kill_at_step == 6
    assert plan.freeze_at_step == 9


def test_chaos_fire_once_persists_across_processes(tmp_path):
    """A kill/freeze fault must fire once per SCENARIO, not once per
    process: the restarted child re-traverses the fault's step and would
    otherwise crash-loop the drill. The marker is written BEFORE the fault
    executes (a SIGKILL leaves no later chance)."""
    state = str(tmp_path / "chaos_state")
    first = ChaosPlan(kill_at_step=5, state_dir=state)
    assert first._fire_once("kill")
    assert os.path.exists(os.path.join(state, "fired_kill"))
    assert not first._fire_once("kill")
    # a fresh plan (the restarted process) sees the marker and stays quiet
    second = ChaosPlan(kill_at_step=5, state_dir=state)
    assert not second._fire_once("kill")
    assert second._fire_once("freeze")  # other faults unaffected


def test_env_chaos_state_dir_wired(tmp_path, monkeypatch):
    from moco_tpu.resilience.chaos import active_chaos, clear_chaos

    monkeypatch.setenv("MOCO_TPU_CHAOS", "kill_at_step=3")
    monkeypatch.setenv("MOCO_TPU_CHAOS_STATE", str(tmp_path))
    clear_chaos()
    try:
        plan = active_chaos()
        assert plan.kill_at_step == 3
        assert plan.state_dir == str(tmp_path)
    finally:
        clear_chaos()


# ---------------------------------------------------------------------------
# stub-child e2e: the real Supervisor loop, seconds-cheap children
# ---------------------------------------------------------------------------

_STUB = textwrap.dedent("""\
    import json, os, sys, time
    tdir, state_path = sys.argv[1], sys.argv[2]
    plan = sys.argv[3].split(",")
    extra = sys.argv[4:]  # e.g. the supervisor-appended `--resume auto`
    n = 0
    if os.path.exists(state_path):
        n = int(open(state_path).read())
    open(state_path, "w").write(str(n + 1))
    with open(os.path.join(tdir, "argv_%d.json" % n), "w") as f:
        json.dump(extra, f)
    behavior = plan[min(n, len(plan) - 1)]
    def beat(step, phase="step"):
        p = os.path.join(tdir, "heartbeat.json")
        with open(p + ".tmp", "w") as f:
            json.dump({"v": 1, "t": round(time.time(), 3), "step": step,
                       "pid": os.getpid(), "phase": phase}, f)
        os.replace(p + ".tmp", p)
    kind, _, arg = behavior.partition(":")
    if kind == "hang":
        beat(int(arg or 1))
        time.sleep(300)
    elif kind == "ok":
        beat(int(arg or 5))
        sys.exit(0)
    elif kind == "eval_pause":
        # step beats, then a declared eval phase whose silence outlives
        # the tight window, then back to stepping — must NOT be killed
        beat(3)
        beat(3, phase="eval")
        time.sleep(float(arg or 1.5))
        beat(5)
        sys.exit(0)
    elif kind == "silent_ok":
        # never beats at all (telemetry off / wrong dir) — must not be
        # kill-looped; exits fine on its own
        time.sleep(float(arg or 1.0))
        sys.exit(0)
    elif kind == "preempt":
        beat(int(arg or 3), phase="preempt_exit")
        sys.exit(43)
    elif kind == "exit":
        sys.exit(int(arg))
    else:
        raise SystemExit("unknown stub behavior %r" % behavior)
""")


def _stub_supervisor(tmp_path, plan, **policy_kw):
    stub = tmp_path / "stub.py"
    stub.write_text(_STUB)
    tdir = tmp_path / "telemetry"
    tdir.mkdir(exist_ok=True)
    defaults = dict(
        max_restarts=3, heartbeat_stale_secs=0.5, startup_grace_secs=10.0,
        term_grace_secs=1.0, backoff_base_secs=0.05, backoff_max_secs=0.2,
        backoff_jitter=0.0, poll_secs=0.1,
    )
    defaults.update(policy_kw)
    return Supervisor(
        [sys.executable, str(stub), str(tdir), str(tmp_path / "attempts"),
         plan],
        telemetry_dir=str(tdir),
        policy=RestartPolicy(**defaults),
        seed=0,
    ), tdir


def test_e2e_hang_killed_within_window_then_restarted(tmp_path):
    """A child that beats once then goes silent is killed within 2x the
    staleness window and the relaunch finishes the run."""
    sup, tdir = _stub_supervisor(tmp_path, "hang:1,ok:5")
    t0 = time.monotonic()
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.classifications == [CLASS_HANG, CLASS_CLEAN]
    assert result.restarts == 1 and not result.gave_up
    # detection latency: the kill incident lands within 2x the staleness
    # window (+ the SIGTERM grace) of the child's last beat
    kills = [r for r in sup.incidents if r["event"] == "kill"]
    assert kills and kills[0]["reason"] == "heartbeat_stale"
    # 2x the window, plus fixed slack for scheduler noise at this tiny
    # (0.5 s) window — the soak pins the strict 2x bound at a real scale
    assert kills[0]["stale_secs"] <= 2 * sup.policy.heartbeat_stale_secs + 1.0
    assert time.monotonic() - t0 < 30.0
    # the whole story is one JSONL stream, supervisor records included
    records = read_events_tail(os.path.join(str(tdir), "events.jsonl"))
    events = [r["event"] for r in records if r.get("kind") == "supervisor"]
    assert "launch" in events and "kill" in events and "done" in events


def test_e2e_crash_loop_exhausts_budget(tmp_path):
    sup, _ = _stub_supervisor(tmp_path, "exit:1", max_restarts=2)
    result = sup.run()
    assert result.gave_up
    assert result.final_class == CLASS_CRASH
    assert result.launches == 3  # initial + max_restarts
    assert all(c == CLASS_CRASH for c in result.classifications)
    give_up = [r for r in sup.incidents if r["event"] == "give_up"]
    assert give_up and "budget exhausted" in give_up[0]["reason"]


def test_e2e_fatal_class_never_restarts(tmp_path):
    sup, _ = _stub_supervisor(tmp_path, "exit:44")
    result = sup.run()
    assert result.final_class == CLASS_ROLLBACK_EXHAUSTED
    assert result.launches == 1 and not result.gave_up
    assert [r["event"] for r in sup.incidents if r["event"] == "restart"] == []


def test_e2e_preempt_relaunches_without_backoff_and_forces_resume(tmp_path):
    sup, tdir = _stub_supervisor(tmp_path, "preempt:3,ok:7")
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.classifications == [CLASS_PREEMPTED, CLASS_CLEAN]
    # preemption: the machine is healthy, no backoff before the relaunch
    assert [r for r in sup.incidents if r["event"] == "backoff"] == []
    # EVERY launch carries --resume auto (attempt 0 included: a restarted
    # supervisor over an existing ckpt_dir must continue, not retrain)
    for attempt in (0, 1):
        with open(tdir / f"argv_{attempt}.json") as f:
            assert json.load(f) == ["--resume", "auto"]


def test_e2e_eval_phase_widens_staleness_window(tmp_path):
    """A declared non-step phase (the kNN eval's "eval" beat) suspends the
    tight window — the supervisor-side analogue of watchdog.suspended().
    The pause here (1.5 s) is 3x the stale window; only the startup grace
    (10 s) applies while the newest beat says "eval"."""
    sup, _ = _stub_supervisor(tmp_path, "eval_pause:1.5")
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.restarts == 0
    assert [r for r in sup.incidents if r["event"] == "kill"] == []


def test_e2e_never_any_heartbeat_disables_kill_not_loops(tmp_path):
    """A child that never writes a heartbeat (telemetry off, mismatched
    --telemetry-dir) must NOT be kill-restarted on a cycle — the channel
    is missing, not the child. Hang detection disables with a loud
    incident and the child finishes on its own."""
    sup, _ = _stub_supervisor(tmp_path, "silent_ok:1.2",
                              startup_grace_secs=0.3)
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.restarts == 0
    assert [r for r in sup.incidents if r["event"] == "kill"] == []
    warns = [r for r in sup.incidents if r["event"] == "no_heartbeat"]
    assert len(warns) == 1


def test_e2e_stale_zero_disables_hang_detection(tmp_path):
    """heartbeat_stale_secs <= 0: no kill ever (non-main pod hosts never
    write a heartbeat — they must not be killed as 'hung' on a cycle).
    The child here beats once then exits on its own; with a live window
    this same shape gets killed (see the hang test above)."""
    sup, _ = _stub_supervisor(tmp_path, "ok:5", heartbeat_stale_secs=0.0,
                              startup_grace_secs=0.01)
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert [r for r in sup.incidents if r["event"] == "kill"] == []


def test_launch_respects_equals_form_resume(tmp_path):
    """`--resume=latest` in the child argv must suppress the appended
    `--resume auto` exactly like the space-separated form — argparse
    last-wins would silently override the operator's pinned choice."""
    sup = Supervisor(
        ["python", "-m", "moco_tpu.train", "--resume=7"],
        telemetry_dir=str(tmp_path),
    )
    # reach into the argv assembly without launching a process
    argv_out = {}

    class _FakePopen:
        pid = 1

        def __init__(self, argv, **kw):
            argv_out["argv"] = argv

    import moco_tpu.resilience.supervisor as supmod

    orig = supmod.subprocess.Popen
    supmod.subprocess.Popen = _FakePopen
    try:
        sup._launch(attempt=1)
    finally:
        supmod.subprocess.Popen = orig
    assert argv_out["argv"].count("--resume") == 0
    assert "--resume=7" in argv_out["argv"]
    assert "auto" not in argv_out["argv"]


def test_e2e_progress_refunds_budget(tmp_path):
    """Three deaths, each after fresh step progress, on a budget of 1: a
    crash loop would die at the second, a progressing run keeps going."""
    sup, _ = _stub_supervisor(
        tmp_path, "preempt:3,preempt:6,preempt:9,ok:12", max_restarts=1,
    )
    result = sup.run()
    assert result.final_class == CLASS_CLEAN
    assert result.restarts == 3 and not result.gave_up


# ---------------------------------------------------------------------------
# the full chaos soak: real training, kill@ + freeze@, bit-identical result
# ---------------------------------------------------------------------------


def _train_child_argv(tdir, ckpt_dir):
    return [
        sys.executable, "-m", "moco_tpu.train",
        "--preset", "cifar10-moco-v1", "--fake-devices", "8",
        "--arch", "resnet_tiny", "--dataset", "synthetic",
        "--image-size", "16", "--batch-size", "16",
        "--num-negatives", "64", "--embed-dim", "32", "--lr", "0.1",
        "--epochs", "3", "--steps-per-epoch", "4", "--print-freq", "1000",
        "--knn-monitor", "false", "--num-classes", "10",
        "--watchdog-secs", "0",
        "--telemetry-dir", str(tdir), "--telemetry-flush-steps", "4",
        "--heartbeat-secs", "0.05", "--ckpt-dir", str(ckpt_dir),
    ]


def _soak_env(tmp_path, chaos="", chaos_state=""):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # NO persistent compile cache: a SIGKILL-grade fault can poison this
    # jax build's cache (a child dying around a cache write left an entry
    # whose load heap-corrupts every later process — glibc "corrupted
    # double-linked list" at startup), converting restarts into a
    # native-crash loop. The supervisor's budget contained it exactly as
    # designed (give_up after max_restarts no-progress deaths), but the
    # soak needs the run to COMPLETE. See README "Run supervision".
    env["MOCO_TPU_NO_CACHE"] = "1"
    if chaos:
        env["MOCO_TPU_CHAOS"] = chaos
        env["MOCO_TPU_CHAOS_STATE"] = chaos_state
    else:
        env.pop("MOCO_TPU_CHAOS", None)
        env.pop("MOCO_TPU_CHAOS_STATE", None)
    return env


def _restore_leaves(ckpt_dir, step):
    """Final checkpoint's raw leaves, loaded WITHOUT building a model —
    the bit-identity comparison must not depend on reconstruction."""
    import numpy as np
    import orbax.checkpoint as ocp

    with ocp.PyTreeCheckpointer() as ckptr:
        tree = ckptr.restore(os.path.join(str(ckpt_dir), str(step), "default"))
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_chaos_soak_bitidentical(tmp_path):
    """ISSUE 4 acceptance: a supervised CPU run through a SIGKILL at step 6
    and a wedged-collective freeze at step 9 completes within the restart
    budget, the hang is detected and killed within 2x the staleness
    window, the final checkpoint is bit-identical to an uninterrupted
    run's, and the supervisor's incidents render in telemetry_report."""
    import numpy as np

    # uninterrupted reference, same subprocess environment
    ref_t = tmp_path / "ref_telemetry"
    ref_ckpt = tmp_path / "ref_ckpt"
    proc = subprocess.run(
        _train_child_argv(ref_t, ref_ckpt), env=_soak_env(tmp_path),
        capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]

    # supervised run with process-level faults injected via the env plan
    sup_t = tmp_path / "sup_telemetry"
    sup_ckpt = tmp_path / "sup_ckpt"
    sup_t.mkdir()
    stale = 3.0
    sup = Supervisor(
        _train_child_argv(sup_t, sup_ckpt),
        telemetry_dir=str(sup_t),
        ckpt_dir=str(sup_ckpt),
        env=_soak_env(tmp_path, chaos="kill_at_step=6,freeze_at_step=9",
                      chaos_state=str(tmp_path / "chaos_state")),
        policy=RestartPolicy(
            max_restarts=4, heartbeat_stale_secs=stale,
            startup_grace_secs=600.0, term_grace_secs=3.0,
            backoff_base_secs=0.1, backoff_max_secs=1.0, poll_secs=0.25,
        ),
        seed=0,
    )
    result = sup.run()
    assert result.final_class == CLASS_CLEAN, result
    assert not result.gave_up
    assert result.restarts == 2, result
    assert result.classifications == [CLASS_KILLED, CLASS_HANG, CLASS_CLEAN]

    # hang detected within 2x the staleness window
    kills = [r for r in sup.incidents if r["event"] == "kill"]
    assert kills and kills[0]["stale_secs"] <= 2 * stale

    # bit-identical final state: every leaf of the step-12 checkpoint
    ref_leaves = _restore_leaves(ref_ckpt, 12)
    sup_leaves = _restore_leaves(sup_ckpt, 12)
    assert len(ref_leaves) == len(sup_leaves)
    for a, b in zip(ref_leaves, sup_leaves):
        np.testing.assert_array_equal(a, b)

    # incidents present in the stream and rendered by the report tool
    report = os.path.join(REPO, "tools", "telemetry_report.py")
    events = os.path.join(str(sup_t), "events.jsonl")
    out = subprocess.run([sys.executable, report, events],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "supervisor:" in out.stdout and "death classifications" in out.stdout
    as_json = subprocess.run([sys.executable, report, events, "--json"],
                             capture_output=True, text=True)
    summary = json.loads(as_json.stdout)
    assert summary["supervisor"]["restarts"] == 2
    assert summary["supervisor"]["outcome"] == "done"
    assert sorted(summary["supervisor"]["classifications"]) == \
        sorted(["killed", "hang", "clean"])
