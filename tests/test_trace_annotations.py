"""ISSUE 25 (b): the tracer's spans enter the profiler's trace through an
injected annotation factory, at every trace_mode, and `trace_mode` governs
`spans.jsonl` only. A recording fake stands in for
`jax.profiler.TraceAnnotation`; `trace.py` itself stays jax-free."""

import json
import os
import subprocess
import sys

import pytest

from moco_tpu.telemetry.trace import NULL_SPAN, Tracer, null_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Recorder:
    """`factory(name, attrs)` -> a context manager that logs enter / leave."""

    def __init__(self):
        self.log = []

    def __call__(self, name, attrs):
        outer = self

        class Annotation:
            def __enter__(self):
                outer.log.append(("enter", name, dict(attrs)))
                return self

            def __exit__(self, exc_type, exc, tb):
                outer.log.append(("leave", name, exc_type))
                return False

        return Annotation()

    def names(self, what):
        return [e[1] for e in self.log if e[0] == what]


def spans_of(path):
    p = os.path.join(str(path), "spans.jsonl")
    if not os.path.exists(p):
        return []
    with open(p) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_off_enters_the_annotation_and_records_no_span(tmp_path):
    t, rec = Tracer(str(tmp_path), "off"), Recorder()
    t.annotation_factory = rec
    with t.span("dispatch", detail=True, batch=3) as sp:
        sp.set(k=1)                 # the span's interface, a no-op here
        assert sp.context() is None
        assert rec.log == [("enter", "dispatch", {"batch": 3})]
    assert rec.names("leave") == ["dispatch"]
    t.close()
    assert t.spans_recorded == 0 and spans_of(tmp_path) == []


@pytest.mark.parametrize("mode", ["steps", "full"])
def test_recording_modes_do_both(tmp_path, mode):
    t, rec = Tracer(str(tmp_path), mode), Recorder()
    t.annotation_factory = rec
    with t.span("step", cat="step", step=4):
        with t.span("data_wait", detail=True):
            pass
    t.close()
    # the annotation is entered for every span, whatever is recorded
    assert rec.names("enter") == ["step", "data_wait"]
    assert rec.names("leave") == ["data_wait", "step"]
    assert rec.log[0][2] == {"step": 4}
    recorded = [s["name"] for s in spans_of(tmp_path)]
    assert recorded == (["data_wait", "step"] if mode == "full" else ["step"])


def test_detail_filtering_holds_with_a_factory(tmp_path):
    t, rec = Tracer(str(tmp_path), "steps"), Recorder()
    t.annotation_factory = rec
    with t.span("coarse"):
        with t.span("fine", detail=True) as fine:
            assert fine.context() is None          # annotation only
    t.close()
    assert [s["name"] for s in spans_of(tmp_path)] == ["coarse"]
    assert rec.names("enter") == ["coarse", "fine"]


@pytest.mark.parametrize("mode", ["off", "full"])
def test_an_exception_inside_a_span_leaves_both_closed(tmp_path, mode):
    t, rec = Tracer(str(tmp_path), mode), Recorder()
    t.annotation_factory = rec
    with pytest.raises(KeyError):
        with t.span("outer"):
            with t.span("inner", detail=True):
                raise KeyError("boom")
    assert rec.names("leave") == ["inner", "outer"]
    assert [e[2] for e in rec.log if e[0] == "leave"] == [KeyError, KeyError]
    assert t.current_context() is None             # the thread's span stack is empty again
    t.close()
    recorded = {s["name"]: s for s in spans_of(tmp_path)}
    if mode == "full":
        assert recorded["inner"]["attrs"]["error"] == "KeyError"
        assert recorded["outer"]["attrs"]["error"] == "KeyError"
    else:
        assert recorded == {}


def test_without_a_factory_off_is_still_the_null_span_singleton(tmp_path):
    t = Tracer(str(tmp_path), "off")
    assert t.annotation_factory is None
    assert t.span("x") is NULL_SPAN and t.span("y", detail=True) is NULL_SPAN
    assert null_tracer().span("x") is NULL_SPAN and null_tracer().annotation_factory is None
    steps = Tracer(str(tmp_path / "s"), "steps")
    assert steps.span("fine", detail=True) is NULL_SPAN


def test_self_time_accounting_is_gone():
    """The driver's `telemetry` phase books `on_step`'s window."""
    for name in ("consume_" + "self_time", "_note_self", "_self_lock", "_self_s"):
        assert not hasattr(Tracer(None), name) and not hasattr(null_tracer(), name)


def test_trace_and_scopes_import_without_jax_or_numpy():
    code = ("import sys\n"
            "import moco_tpu.telemetry.trace, moco_tpu.telemetry.scopes\n"
            "bad = [m for m in ('jax', 'numpy', 'flax') if m in sys.modules]\n"
            "assert not bad, bad\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 0, p.stderr


def test_run_telemetry_installs_the_profilers_annotations(tmp_path, mesh8):
    import jax

    from moco_tpu.config import get_preset
    from moco_tpu.telemetry import RunTelemetry, scopes

    config = get_preset("cifar10-moco-v1").replace(telemetry_dir=str(tmp_path),
                                                    peak_flops_per_chip=1e12)
    tel = RunTelemetry(config, n_chips=1, n_procs=1, process_index=0, steps_per_epoch=10)
    try:
        factory = tel.tracer.annotation_factory
        step = factory(scopes.STEP_SPAN, {"step": 12})
        assert isinstance(step, jax.profiler.StepTraceAnnotation)
        other = factory("dispatch", {})
        assert type(other) is jax.profiler.TraceAnnotation
        # trace_mode off: the main thread's spans are annotations only, the
        # coarse `step` span too (the look-back ring is the other threads')
        with tel.tracer.span("dispatch", detail=True) as sp:
            assert sp is not NULL_SPAN and sp.context() is None
        with tel.tracer.span(scopes.STEP_SPAN, cat="step", step=1) as sp:
            assert sp is not NULL_SPAN and sp.context() is None
        assert tel.tracer.spans_recorded == 0 and len(tel.tracer._lookback) == 0
    finally:
        tel.close()


def test_fence_due_says_when_maybe_fence_would_block():
    from moco_tpu.telemetry.timing import StepPhaseTimer

    timer = StepPhaseTimer(stride=4)
    timer.epoch_start()
    assert not timer.fence_due(4)              # no dispatch yet
    with timer.phase("host_s"):          # its end is the dispatch's return
        pass
    assert timer.fence_due(4) and not timer.fence_due(5)
    assert timer.maybe_fence(5, 1.0) is None and timer.maybe_fence(4, 1.0) is not None
    assert not StepPhaseTimer(stride=0).fence_due(4)
