"""ISSUE 35: every step record carries the main thread's phases, a counter of
steps dispatched to an idle device and of the interpreter's collections; at
`trace_mode` `off` the tracer keeps the staging threads' coarse spans in a
look-back ring, and a `stall` event says in which phase a slow step's time went
and has the ring written beside the step's own intervals."""

import gc
import importlib.util
import json
import os
import threading

import pytest

from moco_tpu.telemetry import scopes
from moco_tpu.telemetry.timing import (
    LOOP_FIELD,
    PHASE_FIELDS,
    GcWatch,
    StepPhaseTimer,
)
from moco_tpu.telemetry.trace import (
    SPANS_FILENAME,
    STALL_DRAIN_FIELDS,
    STALL_MIN_EXCESS_S,
    STALL_MIN_SHARE,
    STALL_SYNC_FIELD,
    STALL_WINDOW,
    StallDetector,
    Tracer,
    drained,
    is_stall,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUMMED = PHASE_FIELDS + (LOOP_FIELD,)
STRIDE, PRINT_FREQ, STEPS_PER_EPOCH = 4, 5, 12


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _records(telemetry_dir, kind="step"):
    with open(os.path.join(telemetry_dir, "events.jsonl"), encoding="utf-8") as f:
        return [r for r in map(json.loads, filter(str.strip, f)) if r.get("kind") == kind]


def _spans(telemetry_dir):
    path = os.path.join(str(telemetry_dir), SPANS_FILENAME)
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _tiny(tmp_path, **over):
    from moco_tpu.config import get_preset

    return get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", dataset="synthetic", image_size=16, batch_size=16,
        num_negatives=64, embed_dim=32, lr=0.1, epochs=2,
        steps_per_epoch=STEPS_PER_EPOCH, ckpt_dir="", tb_dir="",
        print_freq=PRINT_FREQ, num_classes=10, knn_monitor=False,
        staging_workers=2, telemetry_dir=str(tmp_path / "telemetry"),
        telemetry_flush_steps=8, telemetry_stride=STRIDE,
        peak_flops_per_chip=1e12, **dict({"trace_mode": "off"}, **over))


# -- (a) the driver's records ---------------------------------------------------


@pytest.fixture(scope="module")
def plain_run(tmp_path_factory):
    """On ONE device: `float(loss)` reads one shard and `is_ready()` asks all of
    them, so on eight fake devices a drained step need not read as ready."""
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train import train

    config = _tiny(tmp_path_factory.mktemp("phases"))
    train(config, create_mesh(1))
    return config, _records(config.telemetry_dir)


def test_every_records_phases_sum_to_its_step(plain_run):
    _config, records = plain_run
    assert len(records) == 2 * STEPS_PER_EPOCH
    for r in records:
        assert sum(r.get(f, 0.0) for f in SUMMED) == pytest.approx(r["step_s"], abs=1e-6), r
        assert "data_s" in r and "host_s" in r and r.get(LOOP_FIELD, 0.0) > 0.0
        assert all(r[f] > 0.0 for f in SUMMED if f in r and f not in ("data_s", "host_s"))
    # the telemetry's own time is the NEXT record's: the first has none
    assert "telemetry_s" not in records[0] and "telemetry_s" in records[1]
    # the wait for the step before is a phase of (nearly) every step
    assert sum("wait_s" in r for r in records) >= len(records) - 2


def test_fence_stands_on_the_fenced_steps_only(plain_run):
    _config, records = plain_run
    fenced = [r["step"] for r in records if "fence_s" in r]
    assert fenced == [r["step"] for r in records if r["step"] % STRIDE == 0]
    assert fenced == [r["step"] for r in records if "device_s" in r]
    # the print's read-back: the first of every five of an epoch
    printed = [r["step"] for r in records if (r["step"] - 1) % STEPS_PER_EPOCH % PRINT_FREQ == 0]
    assert set(printed) <= {r["step"] for r in records if "readback_s" in r}


def test_starved_stands_after_every_drain(plain_run):
    """After a fence or a print's read-back of the step just dispatched the
    device has nothing queued: the next dispatch goes to an idle device. (On
    the CPU a tiny step may also be done before the next dispatch, so other
    steps may carry it: the necessary ones are asserted.)"""
    _config, records = plain_run
    by_step = {r["step"]: r for r in records}
    drained = [s for s, r in by_step.items() if "fence_s" in r or "readback_s" in r]
    assert len(drained) >= 2 * (STEPS_PER_EPOCH // STRIDE + 2)
    for s in drained:
        if s + 1 in by_step:
            assert by_step[s + 1].get("starved") == 1, by_step[s + 1]
    assert "starved" not in by_step[1]          # nothing before it to ask
    assert all(r.get("starved", 1) == 1 for r in records)   # 1, or left out


class _Pending:
    def __init__(self, ready):
        self._ready = ready

    def is_ready(self):
        return self._ready


@pytest.mark.parametrize("pending, starved", [
    (_Pending(True), True),     # the step before is done: nothing is queued
    (_Pending(False), False),   # still running: this dispatch queues behind it
    (None, False),              # the run's first step
    (1.0, False),               # a host float (chaos): nothing to ask
])
def test_probe_idle_asks_and_never_waits(pending, starved):
    timer = StepPhaseTimer(stride=0)
    timer.epoch_start()
    timer.probe_idle(pending)
    with timer.phase("host_s"):
        pass
    assert ("starved" in timer.finish_step()) == starved
    with timer.phase("host_s"):                 # re-armed: the next step asks anew
        pass
    assert "starved" not in timer.finish_step()


def test_a_phase_books_the_interval_its_span_holds(tmp_path, mesh8):
    """At `full` the span is recorded with its own clock reads: the field, the
    next item of the same `with`, lies inside it; at `off` the main thread's
    spans are annotations alone and the timer's intervals are all there is."""
    from moco_tpu.telemetry import RunTelemetry

    tel = RunTelemetry(_tiny(tmp_path, trace_mode="full"), n_chips=1, n_procs=1,
                       process_index=0, steps_per_epoch=10)
    try:
        tel.timer.epoch_start()
        for name, field in scopes.STEP_PHASES.items():
            with tel.tracer.span(name, detail=True), tel.timer.phase(field):
                pass
        phases = tel.timer.finish_step()
        t0, t1, booked = tel.timer.last_step
        assert [f for f, _a, _b in booked] == list(scopes.STEP_PHASES.values())
        assert all(t0 <= a <= b <= t1 for _f, a, b in booked)
        tel.tracer.flush()
        held = {s["name"]: s for s in _spans(tmp_path / "telemetry")}
        assert set(held) == set(scopes.STEP_PHASES)
        for name, field in scopes.STEP_PHASES.items():
            # the field lies inside its span: two clock reads within two
            assert 0.0 <= phases.get(field, 0.0) <= held[name]["dur"] + 1e-6
        stalled = tel._stalled_step(7, phases)
        assert [n for n, _a, _b in stalled["spans"]] == list(scopes.STEP_PHASES)
        assert stalled["attrs"]["step"] == 7 and stalled["window"] == (t0, t1)
    finally:
        tel.close()
    assert set(scopes.STEP_PHASES.values()) == set(PHASE_FIELDS)
    assert set(scopes.STEP_PHASES) <= set(scopes.LOOP_SPANS)


def test_the_driver_opens_every_phase_beside_its_span():
    """`train.py` enters `phase(<field>)` as the next item of the `with` that
    opens the span `scopes.STEP_PHASES` pairs it with, and nowhere else."""
    import re

    with open(os.path.join(REPO, "moco_tpu", "train.py"), encoding="utf-8") as f:
        source = f.read()
    pairs = re.findall(r'with tracer\.span\("(\w+)", detail=True\), \\\n\s+phase\("(\w+)"\):', source)
    assert set(pairs) == set(scopes.STEP_PHASES.items())
    assert len(pairs) == len(re.findall(r'\bphase\("', source)) == 7   # two read-backs


# -- (b) the stall rule, its event and the dumped ring -------------------------------


@pytest.mark.parametrize("step_s, median_s, drained, stall", [
    (1.57, 0.672, False, True),     # 0.9 s lost in the looped cell: 2.3 x, under 3 x p95
    (0.689, 0.672, False, False),   # the looped cell's routine longest step
    (0.591, 0.546, False, False),   # the long-document cell's
    (1.03, 0.132, False, True),     # the R50 cell's smallest counted stall
    (0.24, 0.132, False, True),     # a tenth of a second over a short step
    (0.225, 0.132, False, False),   # 70 % over, but under a tenth of a second
    (4.2, 4.0, False, False),       # 0.2 s over, but a twentieth of a long step
    # a step that reads its own loss back waits for two steps of the device: on
    # the chip every fenced and printing step of the R50 cell reads 263 ms of 131
    (0.2633, 0.1307, True, False),
    (0.2633, 0.1307, False, True),  # the same step taken for an ordinary one
    (1.163, 0.1307, True, True),    # 0.9 s lost in a fenced step
    (0.59, 0.2936, True, False),    # the routed cell's fenced step
])
def test_the_stall_rule(step_s, median_s, drained, stall):
    assert is_stall(step_s, median_s, drained) is stall


@pytest.mark.parametrize("phases, waited", [
    ({"host_s": 0.023, "wait_s": 0.64}, False),         # an ordinary step
    ({"host_s": 0.023, "fence_s": 1.3}, True),          # a fenced one
    ({"host_s": 0.023, "readback_s": 1.3}, True),       # a printing one
    # the benchmark's harness closes its window with a wait for the device inside
    # the last step's dispatch: `host` +0.133 s in every R50 run, and no stall
    ({"host_s": 0.672 + 0.133}, True),
    ({"host_s": 0.671}, False),
    ({}, False),
])
def test_a_step_that_waited_for_its_own_result_is_drained(phases, waited):
    assert drained(phases, 0.672) is waited
    assert STALL_SYNC_FIELD == "host_s"


def test_stall_detector_names_the_phase_and_skips_the_compiling_steps():
    det = StallDetector(SUMMED)
    steady = {"step_s": 0.672, "data_s": 0.001, "host_s": 0.023, "wait_s": 0.64,
              "loop_s": 0.008}
    for _ in range(3):                          # the compiling steps: skipped, not kept
        assert det.observe({"step_s": 90.0, "data_s": 0.0, "host_s": 90.0}) is None
    for _ in range(7):
        assert det.observe(steady) is None
    assert det.observe(dict(steady, step_s=1.572, wait_s=1.54)) is None   # seven before it: too few
    for _ in range(8):
        assert det.observe(steady) is None
    found = det.observe(dict(steady, step_s=1.572, wait_s=1.54))
    assert found["phase"] == "wait" and found["median_s"] == 0.672
    assert found["excess_s"] == pytest.approx(0.9) and found["excess"]["wait"] == pytest.approx(0.9)
    assert found["expected_s"] == 0.672
    assert found["excess"]["fence"] == 0.0 and set(found["excess"]) == {f[:-2] for f in SUMMED}
    # a fenced step waits for the step before and for itself: twice the median is
    # its routine, and 0.9 s over that a stall, in the fence that took the wait's place
    assert det.observe(dict(steady, step_s=1.344, wait_s=0.0, fence_s=1.312)) is None
    found = det.observe(dict(steady, step_s=2.244, wait_s=0.0, fence_s=2.212))
    assert found["phase"] == "fence" and found["excess"]["wait"] == pytest.approx(-0.64)
    assert found["expected_s"] == 1.344 and found["excess_s"] == pytest.approx(0.9)
    assert STALL_DRAIN_FIELDS == ("fence_s", "readback_s")
    # a dispatch that waited for the device (a caller's sync inside the call): two
    # steps are its routine too; 0.9 s over them is a stall in `host`
    assert det.observe(dict(steady, step_s=1.32, wait_s=0.0, host_s=1.3)) is None
    found = det.observe(dict(steady, step_s=2.25, wait_s=0.0, host_s=2.23))
    assert found["phase"] == "host" and found["expected_s"] == 1.344
    assert det.observe(dict(steady, step_s=0.689)) is None
    assert det._window.maxlen == STALL_WINDOW and (STALL_MIN_EXCESS_S, STALL_MIN_SHARE) == (0.1, 0.25)


@pytest.fixture(scope="module")
def stalled_run(mesh8, tmp_path_factory):
    """`tests/test_trace.py`'s drill with tracing OFF: the sleep lies under no
    span, inside step 20's window."""
    from moco_tpu.train import train

    config = _tiny(tmp_path_factory.mktemp("stall"), chaos="slow_at_step=20,slow_ms=600")
    train(config, mesh8)
    return config


def test_a_stall_says_where_the_time_went(stalled_run):
    stalls = [e for e in _records(stalled_run.telemetry_dir, "event")
              if e.get("event") == "stall" and e["step"] == 20]
    assert len(stalls) == 1
    stall = stalls[0]
    assert stall["phase"] == "loop" and stall["dump"] is True
    assert stall["excess_s"] >= 0.5 and stall["excess"]["loop"] >= 0.5
    assert stall["phases"]["loop_s"] >= 0.6 and stall["step_s"] == stall["phases"]["step_s"]
    assert stall["gc2_n"] == 0 and stall["starved"] in (0, 1) and stall["queue_depth"] >= 0
    record = next(r for r in _records(stalled_run.telemetry_dir) if r["step"] == 20)
    assert {k: record[k] for k in stall["phases"]} == stall["phases"]


def test_the_dumped_ring_holds_the_slow_step_itself(stalled_run):
    spans = _spans(stalled_run.telemetry_dir)
    # written from the record's clock reads, once (the steps after it are the
    # capture window's, which the k x p95 rule armed as ever)
    step = [s for s in spans if s["cat"] == "step" and s["attrs"]["step"] <= 20]
    assert [s["attrs"]["step"] for s in step] == [20]
    step = step[0]
    assert step["dur"] >= 0.6 and step["attrs"]["loop_s"] >= 0.6
    assert step["dur"] == pytest.approx(step["attrs"]["step_s"], abs=2e-6)
    children = [s for s in spans if s.get("parent") == step["span"]]
    assert {"data_wait", "dispatch", "sentinel", "telemetry"} <= {s["name"] for s in children}
    for child in children:     # each phase's own interval, inside the step's, on the wall clock
        assert step["t"] - 1e-5 <= child["t"] <= child["t"] + child["dur"] <= step["t"] + step["dur"] + 1e-5
    by_field = {f: sum(c["dur"] for c in children if c["name"] == n)
                for n, f in scopes.STEP_PHASES.items()}
    for field, seconds in by_field.items():
        assert seconds == pytest.approx(step["attrs"].get(field, 0.0), abs=1e-5)
    assert step["thread"] == "MainThread"
    # what the staging threads did before it: their coarse spans, no detail
    staged = [s for s in spans if s["thread"] != "MainThread"
              and s["t"] + s["dur"] <= step["t"] + step["dur"]]
    assert staged and {s["name"] for s in staged} == {"stage_batch"}
    assert any(s["t"] + s["dur"] <= step["t"] for s in staged)
    assert len({s["span"] for s in spans}) == len(spans)       # an id is written once

def test_the_reports_render_a_dumped_stall(stalled_run, tmp_path):
    telemetry_report, trace_report = _load_tool("telemetry_report"), _load_tool("trace_report")
    records, skipped = telemetry_report.load_events(
        os.path.join(stalled_run.telemetry_dir, "events.jsonl"))
    summary = telemetry_report.summarize(records, skipped)
    assert summary["stalls"]["count"] >= 1 and summary["stalls"]["each"][0]["phase"] == "loop"
    assert summary["drains"]["steps"] >= 8 and 0 < summary["drains"]["dispatch_share"] < 1
    assert summary["gc"]["collections"] >= summary["gc"]["full"] >= 0 and summary["gc"]["ms_each"] > 0
    assert {"wait", "fence", "readback", "telemetry", "loop"} <= set(summary["phase_share"])
    text = telemetry_report.render(summary)
    assert "stalls: " in text and "in loop (gc2 0" in text and "drains: " in text
    assert "idle at least" not in text and "  gc: " in text and " ms each" in text
    assert "· wait " in text and "· loop " in text
    # the dumped ring is ordinary spans.jsonl: the timeline tool needs nothing new
    out = tmp_path / "trace.json"
    assert trace_report.main([stalled_run.telemetry_dir, "-o", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    assert any(e["ph"] == "X" and e["name"] == "step" and e["dur"] >= 6e5 for e in events)
    assert any(e["ph"] == "X" and e["name"] == "stage_batch" for e in events)
    assert any(e["ph"] == "i" and e["name"].endswith("stall") for e in events)


# -- (c) collections of the interpreter ----------------------------------------------


def test_a_forced_collection_lands_in_the_steps_record(tmp_path, mesh8):
    from moco_tpu.telemetry import RunTelemetry
    from moco_tpu.utils.meters import Throughput

    tel = RunTelemetry(_tiny(tmp_path), n_chips=1, n_procs=1, process_index=0,
                       steps_per_epoch=10)
    try:
        watch = tel.gc
        assert watch._on_gc in gc.callbacks
        tel.timer.epoch_start()
        for step in (1, 2, 3):
            with tel.tracer.span("dispatch", detail=True), tel.timer.phase("host_s"):
                if step == 2:
                    gc.collect()
            tel.on_step(step, tel.timer.finish_step(), Throughput(1))
    finally:
        tel.close()
    assert watch._on_gc not in gc.callbacks
    by_step = {r["step"]: r for r in _records(str(tmp_path / "telemetry"))}
    assert by_step[2]["gc2_n"] >= 1 and by_step[2]["gc_n"] >= by_step[2]["gc2_n"]
    assert 0.0 < by_step[2]["gc_s"] <= by_step[2]["host_s"] + 1e-6
    assert "gc2_n" not in by_step[3]


def test_gc_watch_counts_what_ended_since_the_last_drain():
    watch = GcWatch()
    try:
        watch.drain()
        gc.collect(0)
        gc.collect(2)
        seen = watch.drain()
        assert seen["gc_n"] >= 2 and seen["gc2_n"] >= 1 and seen["gc_s"] > 0.0
        assert "gc2_n" not in watch.drain()
    finally:
        watch.close()
    watch.close()                                # idempotent


# -- (d) the look-back ring -------------------------------------------------------


def _on_a_thread(fn, name="staging-0"):
    thread = threading.Thread(target=fn, name=name)
    thread.start()
    thread.join()


def test_the_ring_at_off_never_writes_and_never_outgrows_its_length(tmp_path):
    t = Tracer(str(tmp_path), "off", lookback=True, ring_size=16, flush_every=4,
               dump_budget=1)

    def stage():
        for i in range(100):
            with t.span("stage_batch", cat="input", batch=i):
                with t.span("gather", cat="input", detail=True):
                    pass

    _on_a_thread(stage)
    for i in range(100):                               # the owner's spans: held nowhere
        with t.span("step", cat="step", step=i):
            with t.span("dispatch", detail=True):
                pass
            assert t.record_step(i, {"step_s": 0.1}) is None
    assert t.spans_recorded == t.spans_written == 0
    assert len(t._lookback) == 16 and not os.path.exists(tmp_path / SPANS_FILENAME)
    t.flush()
    assert not os.path.exists(tmp_path / SPANS_FILENAME)
    assert t.can_dump() and t.dump_lookback() == 16
    written = _spans(tmp_path)
    assert [s["attrs"]["batch"] for s in written] == list(range(84, 100))
    assert {s["name"] for s in written} == {"stage_batch"} and written[0]["thread"] == "staging-0"
    assert len(t._lookback) == 0
    _on_a_thread(stage)
    assert not t.can_dump() and t.dump_lookback() is None      # the run's budget is spent
    t.close()
    assert len(_spans(tmp_path)) == 16                 # and close() writes no held span


def test_a_dump_writes_the_stalled_step_from_its_clock_reads(tmp_path):
    import time

    t = Tracer(str(tmp_path), "off", lookback=True)
    _on_a_thread(lambda: _staged_once(t))
    now = time.perf_counter()
    step = {"window": (now - 1.0, now), "attrs": {"step": 5, "step_s": 1.0, "wait_s": 0.9},
            "spans": [("dispatch", now - 1.0, now - 0.95), ("sentinel", now - 0.9, now)]}
    assert t.dump_lookback(step) == 4
    spans = _spans(tmp_path)
    assert [s["name"] for s in spans] == ["stage_batch", "step", "dispatch", "sentinel"]
    parent = spans[1]
    assert parent["cat"] == "step" and parent["attrs"]["wait_s"] == 0.9 and parent["dur"] == 1.0
    assert abs(parent["t"] + 1.0 - time.time()) < 5.0          # on the wall clock
    assert all(s["parent"] == parent["span"] for s in spans[2:])
    assert spans[3]["dur"] == pytest.approx(0.9, abs=1e-6)
    assert spans[3]["t"] == pytest.approx(parent["t"] + 0.1, abs=1e-5)
    t.close()


def _staged_once(tracer):
    with tracer.span("stage_batch", cat="input"):
        pass


def test_without_lookback_off_records_nothing(tmp_path):
    t = Tracer(str(tmp_path), "off")
    _on_a_thread(lambda: _staged_once(t))
    with t.span("step", cat="step"):
        assert t.record_step(1, {"step_s": 0.1}) is None
    assert not t.can_dump() and t.dump_lookback() is None and len(t._lookback) == 0
    assert not Tracer(None, "off", lookback=True).lookback    # nowhere to write: no ring


@pytest.mark.parametrize("mode, on_disk", [("steps", ["stage_batch"]),
                                           ("full", ["gather", "stage_batch"])])
def test_the_ring_is_for_off_alone(tmp_path, mode, on_disk):
    """From `steps` up the mode writes the staging threads' coarse spans itself:
    the ring holds nothing, and a dump writes no step a second time."""
    t = Tracer(str(tmp_path), mode, lookback=True)

    def stage():
        with t.span("stage_batch", cat="input") as batch:
            with t.span("gather", cat="input", detail=True) as child:
                assert child.context() is None or child.parent_id == batch.span_id

    _on_a_thread(stage)
    assert len(t._lookback) == 0
    assert t.dump_lookback({"window": (0.0, 1.0), "attrs": {"step": 1}, "spans": []}) == len(on_disk)
    assert [s["name"] for s in _spans(tmp_path)] == on_disk
    t.close()


def test_span_ids_are_a_prefix_and_a_counter(tmp_path, monkeypatch):
    import moco_tpu.telemetry.trace as trace

    t = Tracer(str(tmp_path), "full")
    monkeypatch.setattr(trace.uuid, "uuid4", lambda: pytest.fail("a random draw a span"))
    ids = []
    for _ in range(3):
        with t.span("x") as sp:
            ids.append(sp.span_id)
    ids.append(t.record_span("y", 0.0, 0.1))
    assert len(set(ids)) == 4 and all(len(i) == 16 for i in ids)
    assert len({i[:8] for i in ids}) == 1
    assert [int(i[8:], 16) for i in ids] == [1, 2, 3, 4]
