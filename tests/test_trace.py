"""Distributed-tracing suite (ISSUE 8): span parenting and mode
filtering, capture-window lifecycle (trigger file / SIGUSR1 / anomaly
detectors, budgeted), cross-PROCESS id propagation supervisor → child →
staging worker under one trace_id, Chrome-trace schema validation of
tools/trace_report.py, the live-tail --follow mode, the StepPhaseTimer
`telemetry` sub-phase fix, R12 lint fixtures, and the acceptance smoke: a
30-step CPU train with chaos slow-step injection whose anomaly detector
auto-captures exactly once within budget."""

import importlib.util
import io
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from moco_tpu.telemetry.registry import MetricsRegistry
from moco_tpu.telemetry.timing import StepPhaseTimer
from moco_tpu.telemetry.trace import (
    ENV_RUN_ID,
    ENV_TRACE_PARENT,
    NULL_SPAN,
    SPANS_FILENAME,
    TRIGGER_FILENAME,
    SlowSampleDetector,
    SpikeDetector,
    Tracer,
    null_tracer,
    parse_parent,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_report = _load_tool("trace_report")
telemetry_report = _load_tool("telemetry_report")


def read_spans(telemetry_dir):
    path = os.path.join(str(telemetry_dir), SPANS_FILENAME)
    spans = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            if line.strip():
                spans.append(json.loads(line))
    return spans


# ---------------------------------------------------------------------------
# span basics: parenting, modes, retroactive recording
# ---------------------------------------------------------------------------


def test_span_nesting_parents_and_flush(tmp_path):
    t = Tracer(str(tmp_path), "steps", proc="driver")
    with t.span("outer", cat="test", k=1) as outer:
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id == t.trace_id
    t.flush()
    spans = read_spans(tmp_path)
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["outer"]["attrs"] == {"k": 1}
    assert by_name["outer"]["run"] == t.run_id
    assert by_name["outer"]["proc"] == "driver"
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    assert t.spans_recorded == t.spans_written == 2


def test_modes_filter_detail_spans(tmp_path):
    off = Tracer(str(tmp_path / "off"), "off")
    assert off.span("x") is NULL_SPAN
    assert off.record_step(1, {"step_s": 0.1}) is None

    steps = Tracer(str(tmp_path / "steps"), "steps")
    assert steps.span("fine", detail=True) is NULL_SPAN
    with steps.span("coarse"):
        pass
    assert steps.record_span("retro", time.time(), 0.01, detail=True) is None
    steps.flush()
    assert [s["name"] for s in read_spans(tmp_path / "steps")] == ["coarse"]

    full = Tracer(str(tmp_path / "full"), "full")
    with full.span("fine", detail=True):
        pass
    full.flush()
    assert [s["name"] for s in read_spans(tmp_path / "full")] == ["fine"]


def test_record_step_emits_phase_children_at_full(tmp_path):
    """The step tree at `full` is built from REAL spans (ISSUE 25): the
    driver's open `step` span takes the phases as attrs and its children
    are the spans that ran inside it, at their own start times; a
    retroactive record_step (no open step span) has no children."""
    t = Tracer(str(tmp_path), "full")
    phases = {"step_s": 0.1, "data_s": 0.03, "host_s": 0.02,
              "telemetry_s": 0.01, "device_s": 0.05}
    with t.span("step", cat="step", step=7) as sp:
        with t.span("data_wait", detail=True):
            pass
        with t.span("dispatch", detail=True):
            pass
        with t.span("telemetry", detail=True):
            sid = t.record_step(7, phases, loss=1.5)
    assert sid == sp.span_id
    t.flush()
    spans = read_spans(tmp_path)
    step = next(s for s in spans if s["cat"] == "step")
    assert step["span"] == sid and [s["cat"] for s in spans].count("step") == 1
    assert step["attrs"]["step"] == 7 and step["attrs"]["loss"] == 1.5
    # device_s is a fenced drain sample, not a wall segment: attr only
    assert step["attrs"]["device_s"] == 0.05 and step["attrs"]["data_s"] == 0.03
    children = {s["name"]: s for s in spans if s.get("parent") == sid}
    assert set(children) == {"data_wait", "dispatch", "telemetry"}
    assert all(step["t"] <= c["t"] <= step["t"] + step["dur"] + 1e-3
               for c in children.values())
    # at `steps` level the children are filtered, the step span remains;
    # and without an open step span the step is recorded retroactively
    t2 = Tracer(str(tmp_path / "s"), "steps")
    with t2.span("step", cat="step", step=8):
        with t2.span("data_wait", detail=True):
            pass
        t2.record_step(8, phases)
    t2.record_step(9, phases)
    t2.flush()
    recs = read_spans(tmp_path / "s")
    assert [s["cat"] for s in recs] == ["step", "step"]
    assert [s["attrs"]["step"] for s in recs] == [8, 9]
    assert recs[1]["dur"] == pytest.approx(0.1)


def test_null_tracer_is_inert():
    t = null_tracer()
    assert t.span("x", detail=True) is NULL_SPAN
    assert t.tick(3) is None and t.capture_state() is None
    assert not t.maybe_autocapture("slow_step")
    assert t.child_env() == {}
    assert NULL_SPAN.context() is None


def test_parse_parent():
    assert parse_parent("abc:def") == ("abc", "def")
    assert parse_parent("") is None
    assert parse_parent(None) is None
    assert parse_parent("malformed") is None
    assert parse_parent(":") is None


# ---------------------------------------------------------------------------
# capture windows: trigger file, SIGUSR1, budget
# ---------------------------------------------------------------------------


def test_capture_window_lifecycle_and_budget(tmp_path):
    t = Tracer(str(tmp_path), "off", capture_steps=3, capture_budget=1,
               trigger_poll_secs=0.0)
    assert t.tick(0) is None  # idle: no transitions
    t.request_capture("manual")
    evt = t.tick(1)
    assert evt["action"] == "start" and evt["reason"] == "manual"
    assert t.capture_state() == {
        "capturing": True, "window_steps_left": 3,
        "captures_used": 1, "capture_budget": 1,
    }
    # capture elevates an OFF tracer to full detail
    with t.span("detail_during_capture", detail=True):
        pass
    assert t.tick(2) is None
    assert t.tick(3) is None
    evt = t.tick(4)
    assert evt["action"] == "end"
    assert not t.capture_state()["capturing"]
    # budget spent: the detector entry point still ROUTES the request (a
    # budget-exhausted anomaly must stay visible, not vanish) and the
    # next tick answers with ONE visible denial
    assert t.maybe_autocapture("slow_step")
    assert t.tick(5)["action"] == "denied"
    assert not t.capture_state()["capturing"]
    t.request_capture("manual3")
    assert t.tick(6) is None  # denial reported once, not per request
    assert t.captures_used == 1  # the denied requests never started
    spans = read_spans(tmp_path)
    names = [s["name"] for s in spans]
    assert "capture_start" in names and "capture_end" in names
    assert "detail_during_capture" in names


def test_trigger_file_arms_capture(tmp_path):
    t = Tracer(str(tmp_path), "off", trigger_poll_secs=0.0,
               capture_steps=2, capture_budget=3)
    trigger = tmp_path / TRIGGER_FILENAME
    trigger.write_text("")
    evt = t.tick(10)
    assert evt["action"] == "start" and evt["reason"] == "trigger_file"
    assert not trigger.exists()  # consumed: re-touch re-arms
    # a touch DURING the active window queues (the file is consumed either
    # way — dropping the request would make the operator's touch vanish):
    # the next capture starts on the first tick after this window ends
    trigger.write_text("")
    assert t.tick(11) is None           # window step 1; request queued
    assert not trigger.exists()
    assert t.tick(12)["action"] == "end"
    evt = t.tick(13)
    assert evt["action"] == "start" and evt["reason"] == "trigger_file"
    assert t.captures_used == 2


def test_sigusr1_arms_capture(tmp_path):
    t = Tracer(str(tmp_path), "off")
    prev = signal.getsignal(signal.SIGUSR1)
    assert t.install_signal()
    try:
        signal.raise_signal(signal.SIGUSR1)
        evt = t.tick(1)
        assert evt["action"] == "start" and evt["reason"] == "sigusr1"
    finally:
        t.close()
    assert signal.getsignal(signal.SIGUSR1) is prev


def test_detectors():
    det = SlowSampleDetector(k=3.0, min_samples=4, floor_s=0.01)
    for _ in range(4):
        assert not det.observe(0.1)  # builds the window
    assert not det.observe(0.2)      # 2x: not anomalous
    assert det.observe(1.0)          # >3x p95
    # last_p95 is the PRE-append threshold the anomaly violated (p95 of
    # [0.1 x4, 0.2]) — the post-append p95 could be the anomaly itself
    assert det.last_p95 == pytest.approx(0.2)
    assert not det.observe(0.005)    # below floor regardless of window
    det2 = SlowSampleDetector(k=3.0, min_samples=8)
    assert not det2.observe(100.0)   # too few samples: never fires

    # warmup skip: compile-scale samples are discarded, not windowed —
    # without it two warmup steps put k*p95 at compile scale forever
    det3 = SlowSampleDetector(k=3.0, min_samples=4, skip=2)
    assert not det3.observe(5.0) and not det3.observe(3.0)  # skipped
    for _ in range(4):
        assert not det3.observe(0.02)
    assert det3.p95() == pytest.approx(0.02)  # warmup never entered
    assert det3.observe(1.0)

    spike = SpikeDetector(min_events=3, window_s=60.0)
    now = 1000.0
    assert not spike.note(now) and not spike.note(now + 1)
    assert spike.note(now + 2)       # 3 within the window
    assert not spike.note(now + 3)   # cleared after firing
    assert not SpikeDetector(min_events=0).note()  # disabled


# ---------------------------------------------------------------------------
# events.jsonl joins the timeline (registry stamp)
# ---------------------------------------------------------------------------


def test_registry_stamp_lands_on_every_record(tmp_path):
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(path, flush_every=1,
                          stamp={"run_id": "r1", "trace_id": "t1"})
    reg.emit("step", step=1)
    reg.emit("event", event="x", run_id="explicit-wins")
    reg.close()
    records = [json.loads(l) for l in open(path)]
    assert records[0]["run_id"] == "r1" and records[0]["trace_id"] == "t1"
    assert records[1]["run_id"] == "explicit-wins"


# ---------------------------------------------------------------------------
# StepPhaseTimer: explicit telemetry sub-phase (satellite fix)
# ---------------------------------------------------------------------------


def test_timer_books_telemetry_subphase_out_of_data():
    """`on_step` runs after `finish_step`, inside the driver's `telemetry`
    phase: its seconds are the NEXT record's `telemetry_s`, a phase of their
    own and no part of `data_s` (the loader wait's own two clock reads)."""
    timer = StepPhaseTimer(stride=0)
    timer.epoch_start()
    with timer.phase("telemetry_s"):
        time.sleep(0.01)       # what the span layer spent of the window
    with timer.phase("data_s"):
        time.sleep(0.02)       # the loader wait
    with timer.phase("host_s"):
        pass
    phases = timer.finish_step()
    assert phases["telemetry_s"] >= 0.01
    assert 0.02 <= phases["data_s"] < 0.03 + phases["telemetry_s"]
    assert phases["data_s"] + phases["telemetry_s"] <= phases["step_s"] + 1e-6
    # next step: booking reset
    with timer.phase("data_s"):
        pass
    with timer.phase("host_s"):
        pass
    assert "telemetry_s" not in timer.finish_step()


def test_timer_phases_sum_to_the_step_and_an_epoch_start_drops_the_rest():
    timer = StepPhaseTimer(stride=0)
    timer.epoch_start()
    with timer.phase("data_s"):
        pass
    with timer.phase("host_s"):
        time.sleep(0.002)
    with timer.phase("wait_s"):
        time.sleep(0.003)
    time.sleep(0.002)           # under no phase: the loop's own
    phases = timer.finish_step()
    fields = ("data_s", "host_s", "telemetry_s", "wait_s", "fence_s",
              "readback_s", "loop_s")
    assert sum(phases.get(f, 0.0) for f in fields) == pytest.approx(
        phases["step_s"], abs=1e-9)
    assert phases["loop_s"] >= 0.002 and phases["wait_s"] >= 0.003
    assert "fence_s" not in phases and "readback_s" not in phases
    # telemetry booked after an epoch's last step falls outside every window
    with timer.phase("telemetry_s"):
        time.sleep(0.001)
    timer.epoch_start()
    with timer.phase("data_s"):
        pass
    with timer.phase("host_s"):
        pass
    assert "telemetry_s" not in timer.finish_step()


# ---------------------------------------------------------------------------
# import diet: trace.py (and the supervisor through it) without jax/numpy
# ---------------------------------------------------------------------------


def test_trace_and_supervisor_import_without_jax_or_numpy():
    code = textwrap.dedent("""
        import sys
        class Block:
            def find_module(self, name, path=None):
                root = name.split('.')[0]
                if root in ('jax', 'jaxlib', 'numpy', 'flax', 'optax',
                            'orbax', 'scipy'):
                    raise ImportError('blocked heavy import: ' + name)
        sys.meta_path.insert(0, Block())
        import moco_tpu.telemetry.trace as trace
        import moco_tpu.resilience.supervisor as sup
        t = trace.Tracer(None, 'off')
        assert t.span('x') is trace.NULL_SPAN
        print('CLEAN')
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "CLEAN" in out.stdout


# ---------------------------------------------------------------------------
# cross-process propagation: supervisor -> child (driver) -> staging worker
# ---------------------------------------------------------------------------

# The child is a REAL consumer of the staging pipeline: it builds a
# Prefetcher (full trace mode) over a synthetic dataset, so its staging
# WORKER threads write decode_slice spans continuing the coordinator's
# stage_batch spans — which parent under the child root span, which
# parents under the supervisor's per-launch span via the env stamp.
_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, sys.argv[1])
    tdir = sys.argv[2]
    from moco_tpu.telemetry.trace import Tracer
    import numpy as np
    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.data.loader import Prefetcher
    from moco_tpu.parallel.mesh import create_mesh

    tracer = Tracer(tdir, "full", proc="driver")  # env ids from supervisor
    mesh = create_mesh(1)
    ds = SyntheticDataset(num_samples=64, image_size=8)
    with tracer.span("driver_root", cat="driver") as root:
        pf = Prefetcher(ds, np.arange(32), 8, mesh, workers=2,
                        tracer=tracer)
        try:
            batches = list(pf)
        finally:
            pf.close_quietly()
        assert len(batches) == 4
    tracer.close()
""")


@pytest.fixture(scope="module")
def supervised_trace_run(tmp_path_factory):
    from moco_tpu.resilience.supervisor import RestartPolicy, Supervisor

    tmp_path = tmp_path_factory.mktemp("trace_prop")
    tdir = tmp_path / "telemetry"
    child_py = tmp_path / "child.py"
    child_py.write_text(_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(ENV_RUN_ID, None)
    env.pop(ENV_TRACE_PARENT, None)
    sup = Supervisor(
        [sys.executable, str(child_py), REPO, str(tdir)],
        telemetry_dir=str(tdir),
        env=env,
        force_resume=False,
        # the stub writes no heartbeat: hang detection off
        policy=RestartPolicy(heartbeat_stale_secs=0.0, poll_secs=0.1),
        seed=0,
    )
    result = sup.run()
    return sup, result, tdir


def test_trace_propagation_one_run_one_parent_chain(supervised_trace_run):
    sup, result, tdir = supervised_trace_run
    assert result.final_class == "clean", result
    spans = read_spans(tdir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # ONE run_id and ONE trace_id across supervisor, driver and workers
    assert {s["run"] for s in spans} == {sup.run_id}
    assert len({s["trace"] for s in spans}) == 1
    launch = by_name["child"][0]          # supervisor's per-launch span
    root = by_name["driver_root"][0]      # child process root
    stage = by_name["stage_batch"]        # coordinator, per batch
    slices = by_name["decode_slice"]      # staging workers (full detail)
    assert launch["proc"] == "supervisor"
    assert root["proc"] == "driver"
    # the parent CHAIN: worker slice -> stage_batch -> driver_root ->
    # supervisor launch span
    assert root["parent"] == launch["span"]
    assert all(s["parent"] == root["span"] for s in stage)
    stage_ids = {s["span"] for s in stage}
    assert slices and all(sl["parent"] in stage_ids for sl in slices)
    # worker spans really came from the worker threads
    assert any(sl["thread"].startswith("staging-w") for sl in slices)
    assert len(stage) == 4
    # supervisor lifecycle records carry the same run id
    events, _ = telemetry_report.load_events(
        os.path.join(str(tdir), "events.jsonl"))
    sup_records = [r for r in events if r.get("kind") == "supervisor"]
    assert sup_records and all(
        r.get("run_id") == sup.run_id for r in sup_records)


def test_trace_report_chrome_schema(supervised_trace_run, tmp_path):
    sup, _result, tdir = supervised_trace_run
    out = tmp_path / "trace.json"
    rc = trace_report.main([str(tdir), "-o", str(out), "--json"])
    assert rc == 0
    # the summary object is the last stdout line — re-run capturing it via
    # the module API instead
    data = trace_report.filter_run(
        trace_report.collect([str(tdir)]), sup.run_id)
    summary = trace_report.summarize(data)
    assert summary["run_ids"] == [sup.run_id]
    assert summary["spans_by_proc"]["supervisor"] >= 1
    assert summary["spans_by_proc"]["driver"] >= 5
    chrome = json.loads(out.read_text())
    events = chrome["traceEvents"]
    assert isinstance(events, list) and events
    phs = {e["ph"] for e in events}
    assert phs <= {"X", "i", "M"}
    spans = [e for e in events if e["ph"] == "X"]
    assert spans
    for e in spans:
        assert isinstance(e["name"], str)
        assert isinstance(e["ts"], float) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["run_id"] == sup.run_id
    # instants from events.jsonl (supervisor lifecycle) made it in
    assert any(e["ph"] == "i" and e["cat"] == "supervisor" for e in events)
    # every pid got a process_name metadata track
    meta_pids = {e["pid"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
    assert {e["pid"] for e in spans} <= meta_pids
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"supervisor", "driver"} <= names


# ---------------------------------------------------------------------------
# telemetry_report --follow (satellite)
# ---------------------------------------------------------------------------


def test_follow_renders_lines_and_survives_partial_writes(tmp_path):
    path = str(tmp_path / "events.jsonl")
    out = io.StringIO()
    stop = threading.Event()
    th = threading.Thread(
        target=telemetry_report.follow,
        args=(path, out, 0.02, stop), daemon=True)
    th.start()
    try:
        time.sleep(0.1)  # starts before the file exists
        with open(path, "w") as f:
            f.write(json.dumps({"v": 1, "kind": "step", "step": 3,
                                "step_s": 0.025, "data_s": 0.005,
                                "imgs_per_sec": 640.0, "loss": 2.5}) + "\n")
            f.write(json.dumps({"v": 1, "kind": "supervisor",
                                "event": "launch", "pid": 7}) + "\n")
            f.flush()
            # a PARTIAL line: must not be rendered (or crash) until its
            # newline lands
            f.write('{"v": 1, "kind": "event", "eve')
            f.flush()
            deadline = time.time() + 5.0
            while out.getvalue().count("\n") < 2 and time.time() < deadline:
                time.sleep(0.02)
            rendered = out.getvalue()
            assert "step      3" in rendered and "loss 2.5" in rendered
            assert "supervisor: launch pid=7" in rendered
            assert rendered.count("\n") == 2  # partial line still buffered
            f.write('nt": "rollback", "msg": "boom"}\n')
            f.flush()
        deadline = time.time() + 5.0
        while "[rollback]" not in out.getvalue() and time.time() < deadline:
            time.sleep(0.02)
        assert "[rollback] boom" in out.getvalue()
    finally:
        stop.set()
        th.join(timeout=5.0)


def test_follow_render_record_shapes():
    assert telemetry_report.render_record({"kind": "pod"}) is None
    line = telemetry_report.render_record(
        {"kind": "run_start", "name": "x", "arch": "r18",
         "batch_size": 8, "run_id": "abc"})
    assert "run_id=abc" in line
    line = telemetry_report.render_record(
        {"kind": "serve", "requests": 10, "served": 9,
         "latency_ms": {"p95": 12.0}, "queue_depth": 1})
    assert "9/10 served" in line


# ---------------------------------------------------------------------------
# R12 lint fixtures (satellite)
# ---------------------------------------------------------------------------

sys.path.insert(0, REPO)
from tools.mocolint.config import DEFAULT_CONFIG  # noqa: E402
from tools.mocolint.engine import Engine  # noqa: E402


def _lint(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return Engine(DEFAULT_CONFIG, select=("R12",)).run([str(path)]).findings


def test_r12_flags_bare_span_open(tmp_path):
    findings = _lint(tmp_path, "moco_tpu/serve/thing.py", """
        def f(tracer):
            sp = tracer.span("x")
            do_work()
    """)
    assert len(findings) == 1 and findings[0].rule == "R12"
    assert "context-manager" in findings[0].message


def test_r12_accepts_with_and_retroactive(tmp_path):
    findings = _lint(tmp_path, "moco_tpu/serve/thing.py", """
        import time
        def f(tracer):
            with tracer.span("x") as sp:
                do_work()
            tracer.record_span("retro", time.time(), 0.1)
            tracer.instant("marker")
    """)
    assert findings == []


def test_r12_flags_nonstdlib_import_in_trace_py(tmp_path):
    findings = _lint(tmp_path, "moco_tpu/telemetry/trace.py", """
        import os

        def f():
            import numpy as np
            return np.zeros(3)
    """)
    assert len(findings) == 1
    assert "numpy" in findings[0].message and "(lazy)" in findings[0].message
    # and the real trace.py is clean under the full default gate (the
    # repo-wide tier-1 gate test in test_mocolint covers the rest)
    real = Engine(DEFAULT_CONFIG, select=("R12",)).run(
        [os.path.join(REPO, "moco_tpu", "telemetry", "trace.py")])
    assert real.findings == []


# ---------------------------------------------------------------------------
# RunTelemetry heartbeat surfacing (satellite)
# ---------------------------------------------------------------------------


def test_heartbeat_carries_trace_state_and_last_step_ms(tmp_path, mesh8):
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry import RunTelemetry
    from moco_tpu.utils.meters import Throughput

    config = get_preset("cifar10-moco-v1").replace(
        telemetry_dir=str(tmp_path), trace_mode="steps",
        heartbeat_secs=0.0, peak_flops_per_chip=1e12,
    )
    tel = RunTelemetry(config, n_chips=1, n_procs=1, process_index=0,
                       steps_per_epoch=10)
    try:
        tel.timer.epoch_start()
        with tel.timer.phase("data_s"):
            pass
        with tel.timer.phase("host_s"):
            pass
        phases = tel.timer.finish_step()
        tel.on_step(1, phases, Throughput(1))
        hb = json.load(open(tmp_path / "heartbeat.json"))
        assert hb["phase"] == "step"
        assert hb["last_step_ms"] >= 0
        assert hb["trace"] == {"capturing": False, "window_steps_left": 0,
                               "captures_used": 0, "capture_budget": 3}
    finally:
        tel.close()
    # the final run_end beat keeps the trace state too
    hb = json.load(open(tmp_path / "heartbeat.json"))
    assert hb["phase"] == "run_end" and "trace" in hb


# ---------------------------------------------------------------------------
# serve: batcher spans + shed-spike arming
# ---------------------------------------------------------------------------


def test_batcher_records_flush_and_request_spans(tmp_path):
    import numpy as np

    from moco_tpu.serve.batcher import MicroBatcher

    tracer = Tracer(str(tmp_path), "full", proc="serve")
    mb = MicroBatcher(lambda x: np.asarray(x, np.float32).sum(axis=(1,)),
                      buckets=(1, 4), flush_ms=5.0, max_queue=16,
                      tracer=tracer)
    try:
        pending = [mb.submit(np.full((3,), i, np.uint8)) for i in range(3)]
        for p in pending:
            p.wait(timeout=5.0)
    finally:
        mb.close()
    tracer.flush()
    spans = read_spans(tmp_path)
    flushes = [s for s in spans if s["name"] == "flush_batch"]
    requests = [s for s in spans if s["name"] == "request"]
    engines = [s for s in spans if s["name"] == "engine"]
    assert flushes and engines and len(requests) == 3
    assert all(r["attrs"]["outcome"] == "ok" for r in requests)
    # requests correlate to their flush via the shared seq attr
    seqs = {f["attrs"]["seq"] for f in flushes}
    assert {r["attrs"]["seq"] for r in requests} <= seqs
    # the engine span nests inside its flush span
    assert all(e["parent"] in {f["span"] for f in flushes} for e in engines)


def test_batcher_shed_spike_arms_capture(tmp_path):
    import numpy as np

    from moco_tpu.serve.batcher import MicroBatcher, OverloadedError

    tracer = Tracer(str(tmp_path), "off", capture_budget=1,
                    trigger_poll_secs=1e9)
    release = threading.Event()

    def slow_batch(x):
        release.wait(10.0)
        return np.zeros((len(x), 2), np.float32)

    mb = MicroBatcher(slow_batch, buckets=(1,), flush_ms=0.0, max_queue=1,
                      tracer=tracer, shed_spike_min=3)
    try:
        mb.submit(np.zeros(2, np.uint8))   # occupies the flusher
        time.sleep(0.1)
        mb.submit(np.zeros(2, np.uint8))   # fills the queue
        sheds = 0
        for _ in range(4):
            with pytest.raises(OverloadedError):
                mb.submit(np.zeros(2, np.uint8))
            sheds += 1
        assert sheds == 4
        # the spike (>= 3 sheds in the window) armed a pending capture
        assert tracer.tick(1)["reason"] == "shed_spike"
    finally:
        release.set()
        mb.close(drain=False)


# ---------------------------------------------------------------------------
# acceptance smoke: 30-step CPU train, chaos slow step, one auto-capture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_chaos_run(mesh8, tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("trace_smoke")
    from moco_tpu.config import get_preset
    from moco_tpu.train import train

    config = get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=16,
        num_negatives=64, embed_dim=32, lr=0.1, epochs=2, steps_per_epoch=15,
        ckpt_dir="", tb_dir="", print_freq=5, num_classes=10,
        knn_monitor=False, staging_workers=2,
        telemetry_dir=str(tmp_path / "telemetry"),
        telemetry_flush_steps=8, telemetry_stride=5,
        peak_flops_per_chip=1e12,
        trace_mode="steps", trace_capture_steps=4, trace_capture_budget=1,
        # a 2 s stall at step 20: a blowout no honest p95 multiple misses
        chaos="slow_at_step=20,slow_ms=2000",
    )
    state, metrics = train(config, mesh8)
    return config, state, metrics


def _events(config):
    records, skipped = telemetry_report.load_events(
        os.path.join(config.telemetry_dir, "events.jsonl"))
    assert skipped == 0
    return records


def test_chaos_slow_step_auto_captures_once_within_budget(traced_chaos_run):
    config, state, _metrics = traced_chaos_run
    assert int(state.step) == 30
    records = _events(config)
    anomalies = [r for r in records if r.get("event") == "trace_anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["anomaly"] == "slow_step"
    assert anomalies[0]["step"] == 20
    captures = [r for r in records if r.get("event") == "trace_capture"]
    actions = [c["action"] for c in captures]
    assert actions == ["start", "end"]  # exactly ONE window, within budget
    assert captures[0]["reason"] == "slow_step"
    assert captures[0]["captures_used"] == 1
    ends = [r for r in records if r.get("kind") == "run_end"]
    assert ends[0]["trace"]["captures_used"] == 1
    assert ends[0]["trace"]["capture_budget"] == 1
    # every record joined the timeline: one run_id stream-wide
    run_ids = {r.get("run_id") for r in records}
    assert len(run_ids) == 1 and None not in run_ids
    # the slow step is visible in the record itself
    slow = next(r for r in records
                if r.get("kind") == "step" and r.get("step") == 20)
    assert slow["step_s"] >= 2.0
    # the telemetry sub-phase rides the stream (booked every step)
    assert any("telemetry_s" in r for r in records
               if r.get("kind") == "step")


def test_chaos_run_spans_elevate_during_capture(traced_chaos_run):
    config, _state, _metrics = traced_chaos_run
    spans = read_spans(config.telemetry_dir)
    steps = [s for s in spans if s["cat"] == "step"]
    assert len(steps) == 30  # trace_mode=steps: one span per step
    stage = [s for s in spans if s["name"] == "stage_batch"]
    assert stage  # coordinator spans at the coarse level
    # the capture window (steps ~21-24) recorded FULL detail: staging
    # worker decode slices appear only there
    slices = [s for s in spans if s["name"] == "decode_slice"]
    assert slices
    assert any(s["thread"].startswith("staging-w") for s in slices)
    cap_names = [s["name"] for s in spans if s["cat"] == "capture"]
    assert cap_names.count("capture_start") == 1
    assert cap_names.count("capture_end") == 1


def test_chaos_run_trace_report_merges_and_summarizes(traced_chaos_run,
                                                      tmp_path):
    config, _state, _metrics = traced_chaos_run
    out = tmp_path / "trace.json"
    rc = trace_report.main([config.telemetry_dir, "-o", str(out)])
    assert rc == 0
    chrome = json.loads(out.read_text())
    assert {e["ph"] for e in chrome["traceEvents"]} <= {"X", "i", "M"}
    data = trace_report.collect([config.telemetry_dir])
    summary = trace_report.summarize(data)
    assert summary["steps"] == 30
    assert summary["step_time_ms"]["p95"] > 0
    share = summary["phase_share"]
    assert "data" in share and "host" in share and "telemetry" in share
    assert "critical_path" in summary
    assert summary["captures"]
    assert summary["anomalies"][0]["anomaly"] == "slow_step"
    rendered = trace_report.render(summary)
    assert "critical path" in rendered and "capture: slow_step" in rendered


def test_chaos_run_heartbeat_final_state(traced_chaos_run):
    config, _state, _metrics = traced_chaos_run
    hb = json.load(open(os.path.join(config.telemetry_dir,
                                     "heartbeat.json")))
    assert hb["phase"] == "run_end"
    assert hb["trace"]["captures_used"] == 1
    assert not hb["trace"]["capturing"]


# ---------------------------------------------------------------------------
# full acceptance scenario, end to end out of process (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_train_chaos_slow_step_full_timeline(tmp_path):
    """ISSUE 8 acceptance, the whole sentence at once: a 30-step CPU train
    UNDER THE REAL SUPERVISOR with chaos slow-step injection; the anomaly
    detector auto-captures within budget, and trace_report emits a single
    valid Chrome-trace JSON merging supervisor, driver and staging-worker
    spans under the supervisor's one run_id."""
    from moco_tpu.resilience.supervisor import RestartPolicy, Supervisor

    tdir = tmp_path / "telemetry"
    env = dict(os.environ, JAX_PLATFORMS="cpu", MOCO_TPU_NO_CACHE="1")
    env.pop(ENV_RUN_ID, None)
    env.pop(ENV_TRACE_PARENT, None)
    child = [
        sys.executable, "-m", "moco_tpu.train",
        "--preset", "cifar10-moco-v1", "--fake-devices", "8",
        "--arch", "resnet_tiny", "--dataset", "synthetic",
        "--image-size", "16", "--batch-size", "16",
        "--num-negatives", "64", "--embed-dim", "32", "--lr", "0.1",
        "--epochs", "2", "--steps-per-epoch", "15", "--print-freq", "1000",
        "--knn-monitor", "false", "--num-classes", "10",
        "--watchdog-secs", "0", "--staging-workers", "2", "--ckpt-dir", "",
        "--telemetry-dir", str(tdir), "--telemetry-flush-steps", "8",
        "--heartbeat-secs", "0.05",
        # the window outlives the run (30 steps): the final run_end
        # heartbeat still says capturing=True, so the supervisor's
        # post-exit read surfaces it deterministically even though the
        # post-anomaly steps take milliseconds
        "--trace-mode", "steps", "--trace-capture-steps", "2000",
        "--trace-capture-budget", "1",
        "--chaos", "slow_at_step=20,slow_ms=3000",
    ]
    sup = Supervisor(
        child, telemetry_dir=str(tdir), env=env, force_resume=False,
        policy=RestartPolicy(heartbeat_stale_secs=60.0,
                             startup_grace_secs=600.0, poll_secs=0.2),
        seed=0,
    )
    result = sup.run()
    assert result.final_class == "clean", result
    spans = read_spans(tdir)
    assert {s["run"] for s in spans} == {sup.run_id}
    procs = {s["proc"] for s in spans}
    assert {"supervisor", "driver"} <= procs
    threads = {s["thread"] for s in spans}
    assert any(t.startswith("staging-") for t in threads)
    records, _ = telemetry_report.load_events(
        os.path.join(str(tdir), "events.jsonl"))
    captures = [r for r in records if r.get("event") == "trace_capture"]
    # the window was still open at run end (capture_steps > run length):
    # one start, and close() truncates it via a capture_end span
    assert [c["action"] for c in captures] == ["start"]
    assert captures[0]["reason"] == "slow_step"
    assert any(s["name"] == "capture_end"
               and (s.get("attrs") or {}).get("truncated") for s in spans)
    # the supervisor saw "currently profiling" from the heartbeat alone
    child_trace = [r for r in records if r.get("event") == "child_trace"]
    assert any(r.get("capturing") for r in child_trace)
    # one merged, valid Chrome trace
    out = tmp_path / "trace.json"
    assert trace_report.main([str(tdir), "-o", str(out),
                              "--run", sup.run_id]) == 0
    chrome = json.loads(out.read_text())
    span_events = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["run_id"] for e in span_events} == {sup.run_id}
    assert len({e["pid"] for e in span_events}) >= 2  # supervisor + driver
