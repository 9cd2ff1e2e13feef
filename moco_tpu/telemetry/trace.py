"""Distributed tracing + on-demand capture windows (ISSUE 8 tentpole).

The repo runs as a small distributed system — supervisor → train driver →
staging workers → device step, plus a serve stack — and its telemetry was
flat per-process JSONL: no way to follow one step or one request across a
process boundary, and no way to grab a profile *when* the slow step
actually happens. This module is the span layer every process shares:

  - `Tracer.span(name)` is a context manager that records one timed span
    into a lock-free ring buffer (a `deque.append` under the GIL — no
    lock, no syscall on the fast path) and flushes batches of spans as
    JSONL lines to `<telemetry_dir>/spans.jsonl` with O_APPEND one-line
    writes, safe to interleave across processes sharing the file.
  - Every span carries `run`/`trace`/`span`/`parent` ids. The ids
    propagate ACROSS processes through two env vars (`MOCO_TPU_RUN_ID`,
    `MOCO_TPU_TRACE_PARENT`): the supervisor stamps its child's env from
    inside its per-launch span, the child's Tracer picks the parent up at
    construction, and thread-side spans (staging workers) continue a
    coordinator span through an explicit `parent=span.context()`.
  - `trace_mode` knob, off by default: `off` writes nothing, `steps`
    writes the coarse spans (one per step / staged batch / serve flush /
    supervisor launch), `full` additionally writes the detail spans
    (the step's data_wait / dispatch / telemetry children, worker decode
    slices, per-shard H2D puts, engine calls). `trace_mode` governs
    `spans.jsonl` ONLY: where an `annotation_factory` is installed (the
    train driver installs `jax.profiler.TraceAnnotation`; the supervisor
    and the serve fleet install nothing) every span ALSO enters that
    annotation, at every mode including `off`, so a profiler session —
    a capture window's device trace, the benchmark's traced steps —
    holds the program's spans on the profiler's own clock (ISSUE 25).
  - A LOOK-BACK ring (ISSUE 35): a tracer built with `lookback=True` (the
    train driver's) holds, at `off`, the coarse spans of every thread but
    its owner's (the staging threads' `stage_batch`) in a second ring in
    memory (a `deque(maxlen=...)`, overwritten, never flushed by count).
    It reaches `spans.jsonl` only through `dump_lookback()`, which
    `RunTelemetry.on_step` calls after a `stall` (`is_stall`, below) with
    the stalled step's own clock reads: the file then holds the slow step
    ITSELF, written from the step record's intervals, beside what the
    staging threads did before and during it. The owner's spans stay
    annotations alone at `off` (the step record already holds their
    intervals), so the loop pays nothing for the ring. A capture window
    opens at the step AFTER an anomaly; the ring is what was already there.
  - On-demand and anomaly-triggered CAPTURE: SIGUSR1 or a
    `<telemetry_dir>/trace.trigger` file arms a bounded window during
    which the effective mode is `full` (and, when hooks are installed, a
    jax.profiler device trace lands under `<telemetry_dir>/traces/`).
    Anomaly detectors (`SlowSampleDetector` for step-time / staging-stall
    blowouts, `SpikeDetector` for serve shed spikes) arm the same window
    through `maybe_autocapture`, bounded by a per-run capture budget — a
    3 a.m. slowdown leaves a profile behind without anyone watching.

This module MUST stay importable without jax (and without numpy): the
out-of-process supervisor imports it, and the supervisor's whole contract
is surviving the failures that kill the jax runtime (mocolint R12 pins
both the import discipline and the context-manager-only span API).
`tools/trace_report.py` merges spans + events from every process of a run
into one Chrome-trace/Perfetto JSON.
"""

from __future__ import annotations

import itertools
import os
import signal
import statistics
import threading
import time
import uuid
from collections import deque

SCHEMA_VERSION = 1

SPANS_FILENAME = "spans.jsonl"
TRIGGER_FILENAME = "trace.trigger"
TRACES_DIRNAME = "traces"

ENV_RUN_ID = "MOCO_TPU_RUN_ID"
ENV_TRACE_PARENT = "MOCO_TPU_TRACE_PARENT"  # "<trace_id>:<span_id>"

TRACE_MODES = ("off", "steps", "full")
_LEVEL = {"off": 0, "steps": 1, "full": 2}


def new_id() -> str:
    """16-hex-char id (64 random bits): short enough to read in a report,
    long enough that a run's span set never collides. For ids made once a
    process (run, trace, a tracer's prefix): a span's id is the tracer's
    prefix and a counter (`Tracer._span_id`), no random draw, no system
    call."""
    return uuid.uuid4().hex[:16]


def parse_parent(value: str | None) -> tuple[str, str] | None:
    """`"<trace_id>:<span_id>"` → tuple; None on absent/malformed (a
    malformed env var must degrade to a fresh trace, never crash the
    child at import time)."""
    if not value:
        return None
    trace_id, sep, span_id = value.partition(":")
    if not sep or not trace_id or not span_id:
        return None
    return trace_id, span_id


# ---------------------------------------------------------------------------
# anomaly detectors (stdlib, shared by driver / loader / serve call sites)
# ---------------------------------------------------------------------------


class SlowSampleDetector:
    """Rolling-window tail detector: `observe(x)` returns True when `x`
    exceeds `k` × the window's p95 (with at least `min_samples` PRIOR
    samples, and `x` above `floor_s` so microsecond-scale noise on a fast
    phase can never trip it). The current sample is checked BEFORE it
    joins the window, so one anomaly does not raise the bar for the next.
    The first `skip` observations are DISCARDED entirely: cold-compile /
    warmup steps are seconds-scale by design, and two of them in the
    window put the p95 itself at warmup scale — every later real anomaly
    would hide under k × (compile time). Not thread-safe by design — each
    caller owns one detector."""

    def __init__(self, k: float = 3.0, window: int = 64,
                 min_samples: int = 8, floor_s: float = 0.0,
                 skip: int = 0):
        self.k = float(k)
        self.min_samples = int(min_samples)
        self.floor_s = float(floor_s)
        self._skip = int(skip)
        self.last_p95 = 0.0  # the threshold the last observe() compared
                             # against — snapshotted BEFORE the sample
                             # joined the window, so an anomaly report can
                             # name the p95 it actually violated
        self._window: deque = deque(maxlen=int(window))

    def p95(self) -> float:
        if not self._window:
            return 0.0
        ordered = sorted(self._window)
        rank = max(0, min(len(ordered) - 1,
                          round(0.95 * (len(ordered) - 1))))
        return ordered[rank]

    def observe(self, value: float) -> bool:
        if self._skip > 0:
            self._skip -= 1
            return False
        value = float(value)
        self.last_p95 = self.p95()
        anomalous = (
            len(self._window) >= self.min_samples
            and value > self.floor_s
            and value > self.k * self.last_p95
        )
        self._window.append(value)
        return anomalous


#: the stall rule's numbers (`perfbench/step_phases.py` keeps a copy, held
#: to these by a test): a step is a stall when it exceeds what it is
#: expected to take by at least `STALL_MIN_EXCESS_S` seconds AND
#: `STALL_MIN_SHARE` of the rolling median of the last `STALL_WINDOW` records
STALL_WINDOW = 64
STALL_MIN_EXCESS_S = 0.1
STALL_MIN_SHARE = 0.25
#: a step that reads its OWN loss back (the stride-gated fence, the print's
#: and the health block's read-backs) waits for the step before and for
#: itself: two steps of the device are its routine (and the step after it,
#: dispatched to an idle device, is short by as much)
STALL_DRAIN_FIELDS = ("fence_s", "readback_s")
#: so does a step whose DISPATCH took a whole step or more: it waited for
#: the device inside the call (a caller that blocks on the result there, as
#: the benchmark's harness does to close its window)
STALL_SYNC_FIELD = "host_s"


def drained(phases: dict, median_s: float) -> bool:
    """Whether the step waited for its own result: by a read-back of its
    loss, or inside its dispatch."""
    return (any(phases.get(f) for f in STALL_DRAIN_FIELDS)
            or phases.get(STALL_SYNC_FIELD, 0.0) >= median_s > 0.0)


def stall_expected_s(median_s: float, drained: bool = False) -> float:
    """What a step is expected to take: the rolling median, twice it where
    the step drained the queue itself."""
    return (2.0 if drained else 1.0) * median_s


def is_stall(step_s: float, median_s: float, drained: bool = False) -> bool:
    """The one rule (ISSUE 35). `k` × p95 (`SlowSampleDetector`, which arms
    the capture window) never sees a 0.9 s stall of a 0.67 s step (2.3
    times the usual); this does, and a routine longest step (0.689 s
    against 0.672) does not meet it. `drained`: the step waited for its own
    result, so it is held against twice the median (on the chip every fenced
    and every printing step of the R50 cell reads 263 ms against a median
    of 131: a rule that took those for stalls fired 54 times a window)."""
    excess = step_s - stall_expected_s(median_s, drained)
    return excess >= STALL_MIN_EXCESS_S and excess >= STALL_MIN_SHARE * median_s


class StallDetector:
    """`observe(phases)` → None, or on a stall what `RunTelemetry` puts in
    the `stall` event: the rolling median of `step_s`, what the step was
    expected to take (twice that where it drained the queue itself:
    `drained`), its excess over that, every phase's excess over
    ITS OWN rolling median (a phase a record leaves out counts as 0), and
    the phase that holds most of it (the field's name without `_s`). The
    first `skip` records are dropped (the compiling steps) and
    `min_samples` prior ones are needed, as in `SlowSampleDetector`; every
    sample joins the window (one outlier in 64 does not move a median).
    Not thread-safe: one caller owns it."""

    def __init__(self, fields: tuple, window: int = STALL_WINDOW,
                 min_samples: int = 8, skip: int = 3):
        self.fields = tuple(fields)
        self.min_samples = int(min_samples)
        self._skip = int(skip)
        self._window: deque = deque(maxlen=int(window))
        # the window's `step_s` alone: the one median every step needs
        self._steps: deque = deque(maxlen=int(window))

    def observe(self, phases: dict) -> dict | None:
        if self._skip > 0:
            self._skip -= 1
            return None
        found = None
        step_s = float(phases["step_s"])
        if len(self._steps) >= self.min_samples:
            median_s = statistics.median(self._steps)
            waited = drained(phases, median_s)
            if is_stall(step_s, median_s, waited):
                expected_s = stall_expected_s(median_s, waited)
                excess = {
                    f[:-2]: round(
                        float(phases.get(f, 0.0)) - statistics.median(
                            float(p.get(f, 0.0)) for p in self._window), 6)
                    for f in self.fields}
                found = {"median_s": round(median_s, 6),
                         "expected_s": round(expected_s, 6),
                         "excess_s": round(step_s - expected_s, 6),
                         "excess": excess,
                         "phase": max(excess, key=excess.get)}
        self._window.append(phases)
        self._steps.append(step_s)
        return found


class SpikeDetector:
    """Event-rate spike detector for discrete bad events (serve sheds):
    `note()` returns True when at least `min_events` landed within the
    trailing `window_s` seconds. After firing, the window is cleared so
    one sustained spike arms one capture, not one per shed. Thread-safe:
    sheds arrive from concurrent HTTP handler threads."""

    def __init__(self, min_events: int = 8, window_s: float = 5.0):
        self.min_events = int(min_events)
        self.window_s = float(window_s)
        self._times: deque = deque()
        self._lock = threading.Lock()

    def note(self, now: float | None = None) -> bool:
        if self.min_events <= 0:
            return False
        now = time.monotonic() if now is None else now
        with self._lock:
            self._times.append(now)
            while self._times and now - self._times[0] > self.window_s:
                self._times.popleft()
            if len(self._times) >= self.min_events:
                self._times.clear()
                return True
        return False


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _NullSpan:
    """The no-op span: returned whenever the tracer is off or the span's
    detail level is filtered — the fast path is one attribute check and
    this singleton's trivial __enter__/__exit__."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def context(self):
        return None


NULL_SPAN = _NullSpan()


class _AnnotationSpan:
    """What `span()` returns where nothing is recorded but an annotation
    factory is installed: the profiler annotation alone behind the span's
    interface — no ring, no ids, no `spans.jsonl` line. The annotation is
    a no-op while no profiler session is open, so with tracing off a span
    costs this object and the annotation's."""

    __slots__ = ("_annotation",)

    def __init__(self, annotation):
        self._annotation = annotation

    def __enter__(self):
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        return False

    def set(self, **attrs) -> None:
        pass

    def context(self):
        return None


class Span:
    """One live span (handle of `Tracer.span(...)`). Only ever used as a
    context manager (mocolint R12): __enter__ stamps the start and pushes
    onto the opening thread's span stack (so nested spans parent
    automatically), __exit__ records the completed span into the ring.
    With an `annotation` (the tracer's factory made one) the span enters
    and leaves it around its own interval."""

    __slots__ = ("_tracer", "name", "cat", "trace_id", "span_id",
                 "parent_id", "attrs", "_t_wall", "_t0", "_entered",
                 "_annotation", "_held")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 parent: tuple[str, str] | None, attrs: dict,
                 annotation=None, held: bool = False):
        self._tracer = tracer
        self._annotation = annotation
        self._held = held  # for the look-back ring, not for the file
        self.name = name
        self.cat = cat
        if parent is None:
            parent = tracer.current_context()
        self.trace_id = parent[0] if parent else tracer.trace_id
        self.parent_id = parent[1] if parent else tracer.root_parent
        self.span_id = tracer._span_id()
        self.attrs = attrs
        self._t_wall = 0.0
        self._t0 = 0.0
        self._entered = False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def context(self) -> tuple[str, str]:
        """(trace_id, span_id) — the handle a worker thread (or a child
        process, via `Tracer.child_env`) parents its own spans under."""
        return (self.trace_id, self.span_id)

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t_wall = time.time()
        self._t0 = time.perf_counter()
        self._entered = True
        self._tracer._push(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        dur_s = time.perf_counter() - self._t0
        self._tracer._pop(self)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._tracer._record(
            self.name, self.cat, self._t_wall, dur_s,
            self.trace_id, self.span_id, self.parent_id, self.attrs,
            self._held,
        )
        return False


class _NullTracer:
    """Shared do-nothing tracer so call sites never branch on `tracer is
    None` in hot loops: every method is a constant-return no-op."""

    run_id = ""
    trace_id = ""
    root_parent = None
    mode = "off"
    captures_used = 0
    capture_budget = 0
    spans_recorded = 0
    spans_written = 0
    lookback = False
    profiler_hooks = None
    annotation_factory = None

    def span(self, name, *, cat="span", detail=False, parent=None, **attrs):
        return NULL_SPAN

    def instant(self, name, *, cat="instant", parent=None, **attrs):
        return None

    def record_span(self, *a, **kw):
        return None

    def record_step(self, *a, **kw):
        return None

    def tick(self, step=None):
        return None

    def maybe_autocapture(self, reason):
        return False

    def request_capture(self, reason):
        pass

    def capture_state(self):
        return None

    def current_context(self):
        return None

    def child_env(self):
        return {}

    def install_signal(self):
        return False

    def flush(self):
        pass

    def can_dump(self):
        return False

    def dump_lookback(self, step=None):
        return None

    def close(self):
        pass


_NULL_TRACER = _NullTracer()


def null_tracer() -> _NullTracer:
    return _NULL_TRACER


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------


class Tracer:
    """Per-process span recorder + capture-window state machine.

    `telemetry_dir` is where `spans.jsonl` (O_APPEND, shared with every
    other process of the run), the `trace.trigger` file and the
    `traces/` profiler dir live; None disables recording entirely.
    `mode` is the configured `trace_mode`; a capture window elevates the
    EFFECTIVE level to `full` without touching the configured one.
    `proc` labels this process's track in the merged timeline
    ("supervisor" / "driver" / "serve" / ...).

    Overhead contract: recording one span is a dict build plus a
    `deque.append` (GIL-atomic, lock-free); the ring drains to disk only
    when `flush_every` spans accumulated (or at capture end / close). The
    driver's `tick` and the flushes it causes run inside
    `RunTelemetry.on_step`, whose own clock books them as the step's
    `telemetry` sub-phase.

    `lookback=True` (the train driver's) keeps, at `off`, the coarse spans
    of the threads other than the one that built the tracer in a second
    ring of `ring_size` spans, which overwrites itself and is written by
    `dump_lookback()` alone, at most `dump_budget` times a run (module
    docstring).

    `annotation_factory` (None, or `factory(name, attrs) -> context
    manager`) is injected like `profiler_hooks`, because this module stays
    jax-free: when set, every span enters the annotation it makes, whether
    or not the span is recorded."""

    def __init__(self, telemetry_dir: str | None, mode: str = "off", *,
                 proc: str = "proc", run_id: str | None = None,
                 parent: tuple[str, str] | None = None,
                 capture_steps: int = 50, capture_budget: int = 3,
                 ring_size: int = 4096, flush_every: int = 256,
                 trigger_poll_secs: float = 1.0, lookback: bool = False,
                 dump_budget: int = 8):
        if mode not in TRACE_MODES:
            raise ValueError(
                f"unknown trace_mode {mode!r}; choose from {TRACE_MODES}"
            )
        self.mode = mode
        self.proc = proc
        self.pid = os.getpid()
        self.run_id = run_id or os.environ.get(ENV_RUN_ID) or new_id()
        env_parent = parent or parse_parent(os.environ.get(ENV_TRACE_PARENT))
        if env_parent is not None:
            self.trace_id, self.root_parent = env_parent
        else:
            self.trace_id, self.root_parent = new_id(), None
        self.capture_steps = max(int(capture_steps), 1)
        self.capture_budget = max(int(capture_budget), 0)
        self.captures_used = 0
        self.spans_recorded = 0
        self.spans_written = 0
        self._capturing = False
        self._capture_left = 0
        self._capture_reason = ""
        # set from signal handlers / other threads: plain assignments only
        self._pending_reason: str | None = None
        self._denied_reported = False
        self._ring: deque = deque(maxlen=max(int(ring_size), 2))
        # the other threads' coarse spans at `off`, for `dump_lookback`
        # (never flushed by count; the deque drops its oldest)
        self._lookback: deque = deque(maxlen=max(int(ring_size), 2))
        self._owner = threading.get_ident()
        self.dumps = 0
        self.dump_budget = max(int(dump_budget), 0)
        # a span's id: this process's random prefix and a counter
        # (`next` of an `itertools.count` is one GIL-atomic call)
        self._id_prefix = new_id()[:8]
        self._id_counter = itertools.count(1)
        self._flush_every = max(int(flush_every), 1)
        self._io_lock = threading.Lock()
        self._tls = threading.local()
        self._trigger_poll_secs = float(trigger_poll_secs)
        self._last_trigger_poll = float("-inf")
        self._prev_sigusr1 = None
        self.profiler_hooks: tuple | None = None  # (start(dir), stop())
        self.annotation_factory = None  # factory(name, attrs) -> ctx manager
        self.profiler_error: str | None = None
        self._profiler_active = False
        self._path = None
        self._trigger_path = None
        self._traces_dir = None
        if telemetry_dir:
            os.makedirs(telemetry_dir, exist_ok=True)
            self._path = os.path.join(telemetry_dir, SPANS_FILENAME)
            self._trigger_path = os.path.join(telemetry_dir, TRIGGER_FILENAME)
            self._traces_dir = os.path.join(telemetry_dir, TRACES_DIRNAME)
        self.lookback = bool(lookback) and self._path is not None

    def _span_id(self) -> str:
        return f"{self._id_prefix}{next(self._id_counter) & 0xFFFFFFFF:08x}"

    # -- levels --------------------------------------------------------------
    def _level(self) -> int:
        if self._path is None:
            return 0
        if self._capturing:
            return 2
        return _LEVEL[self.mode]

    # -- span API (context-manager only: mocolint R12) -----------------------
    def span(self, name: str, *, cat: str = "span", detail: bool = False,
             parent: tuple[str, str] | None = None, **attrs):
        """Open one span as a context manager. `detail=True` marks a
        fine-grained span recorded only at `full` level (or inside a
        capture window); coarse spans record from `steps` up. At `off` a
        coarse span of another thread than the tracer's owner is held for
        the look-back ring, where one is kept. A span that is not recorded
        still enters the profiler annotation, where a factory is
        installed."""
        factory = self.annotation_factory
        annotation = factory(name, attrs) if factory is not None else None
        lvl = self._level()
        if lvl == 0 or (detail and lvl < 2):
            if (self.lookback and lvl == 0 and not detail
                    and threading.get_ident() != self._owner):
                return Span(self, name, cat, parent, attrs, annotation, True)
            if annotation is None:
                return NULL_SPAN
            return _AnnotationSpan(annotation)
        return Span(self, name, cat, parent, attrs, annotation)

    def instant(self, name: str, *, cat: str = "instant",
                parent: tuple[str, str] | None = None, **attrs):
        """Zero-duration marker (rendered as an instant event)."""
        return self.record_span(name, time.time(), 0.0, cat=cat,
                                parent=parent, **attrs)

    def record_span(self, name: str, t_start_wall: float, dur_s: float, *,
                    cat: str = "span", detail: bool = False,
                    parent: tuple[str, str] | None = None,
                    trace_id: str | None = None,
                    span_id: str | None = None, **attrs) -> str | None:
        """Retroactive span: record an already-measured interval (the step
        spans are derived from StepPhaseTimer after the fact — zero
        context-manager overhead inside the hot loop; serve request spans
        are stamped at resolve time). Same `detail` filtering as `span`.
        Returns the span id so callers can parent further retroactive
        children under it."""
        lvl = self._level()
        if lvl == 0 or (detail and lvl < 2):
            return None
        if parent is None:
            parent = self.current_context()
        sid = span_id or self._span_id()
        self._record(
            name, cat, t_start_wall, dur_s,
            trace_id or (parent[0] if parent else self.trace_id),
            sid,
            parent[1] if parent else self.root_parent,
            attrs,
        )
        return sid

    def record_step(self, step: int, phases: dict, **attrs) -> str | None:
        """One training step's phase dict (`step_s`/`data_s`/`host_s`/...,
        the fenced `device_s`/`comm_s` drain samples among them) as the
        attrs of its `step` span. Where the calling thread holds an open
        `step` span (the driver loop's: it records itself at its own start
        with its real children, `data_wait` / `dispatch` / `telemetry`,
        beneath it) the phases are stamped onto that one; otherwise the
        step is recorded retroactively from `step_s`, with no children."""
        if self._level() == 0:
            return None
        span_attrs = {k: round(float(v), 6) for k, v in phases.items()}
        span_attrs.update(attrs)
        span_attrs["step"] = int(step)
        for open_span in reversed(self._stack()):
            if open_span.cat == "step":
                open_span.set(**span_attrs)
                return open_span.span_id
        step_s = float(phases.get("step_s", 0.0))
        return self.record_span("step", time.time() - step_s, step_s,
                                cat="step", **span_attrs)

    # -- parenting -----------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        elif span in stack:  # exotic unwind order: drop it wherever it is
            stack.remove(span)

    def current_context(self) -> tuple[str, str] | None:
        """(trace_id, span_id) of this thread's innermost open span, else
        the process root context inherited from the parent process."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            return stack[-1].context()
        if self.root_parent is not None:
            return (self.trace_id, self.root_parent)
        return None

    def child_env(self) -> dict:
        """Env vars that make a child process continue this trace: its
        tracer adopts our run id and parents its spans under the CURRENT
        span of the calling thread (the supervisor calls this inside its
        per-launch span)."""
        ctx = self.current_context() or (self.trace_id, "")
        env = {ENV_RUN_ID: self.run_id}
        if ctx[1]:
            env[ENV_TRACE_PARENT] = f"{ctx[0]}:{ctx[1]}"
        return env

    # -- recording / flushing ------------------------------------------------
    def _record(self, name, cat, t_wall, dur_s, trace_id, span_id,
                parent_id, attrs, held: bool = False) -> None:
        thread = threading.current_thread()
        rec = {
            "v": SCHEMA_VERSION,
            "kind": "span",
            "name": name,
            "cat": cat,
            "run": self.run_id,
            "trace": trace_id,
            "span": span_id,
            "t": round(t_wall, 6),
            "dur": round(max(dur_s, 0.0), 6),
            "pid": self.pid,
            "proc": self.proc,
            "tid": thread.ident,
            "thread": thread.name,
        }
        if parent_id:
            rec["parent"] = parent_id
        if attrs:
            rec["attrs"] = attrs
        if held:  # at `off`: the look-back ring's, which overwrites itself
            self._lookback.append(rec)
            return
        self._ring.append(rec)  # lock-free fast path (GIL-atomic append)
        self.spans_recorded += 1
        if len(self._ring) >= self._flush_every:
            self.flush()

    def flush(self) -> None:
        """Drain the ring to spans.jsonl (one O_APPEND write of all
        pending lines — safe to interleave with other processes appending
        to the same file)."""
        self._write(self._ring)

    def _write(self, *rings) -> int:
        if self._path is None:
            return 0
        with self._io_lock:
            lines = []
            for ring in rings:
                while True:
                    try:
                        rec = ring.popleft()
                    except IndexError:
                        break
                    lines.append(_dumps(rec))
            if lines:
                with open(self._path, "a", encoding="utf-8") as f:
                    f.write("\n".join(lines) + "\n")
                self.spans_written += len(lines)
        return len(lines)

    def can_dump(self) -> bool:
        """Whether `dump_lookback()` would write: a ring is kept and the
        run's budget of dumps is not spent."""
        return self.lookback and self.dumps < self.dump_budget

    def dump_lookback(self, step: dict | None = None) -> int | None:
        """Write the look-back ring (and what the mode has pending, so the
        file stays whole) to spans.jsonl: ordinary span lines, which
        `tools/trace_report.py` renders like any other. `step`
        (`StepPhaseTimer.last_step`: the stalled step's window and the
        intervals of its phases, on `perf_counter`) is written with them
        at `off`, where the owner's spans are annotations alone: a `step`
        span with `attrs` and one child for each interval. Returns the
        spans written, or None where the run's `dump_budget` is spent (the
        caller's event then stands alone) or no ring is kept."""
        if not self.can_dump():
            return None
        self.dumps += 1
        if step is not None and self._level() == 0:
            to_wall = time.time() - time.perf_counter()
            t0, t1 = step["window"]
            parent = self._span_id()
            self._record("step", "step", to_wall + t0, t1 - t0, self.trace_id,
                         parent, self.root_parent, step["attrs"], True)
            for name, a, b in step["spans"]:
                self._record(name, "span", to_wall + a, b - a, self.trace_id,
                             self._span_id(), parent, {}, True)
        return self._write(self._lookback, self._ring)

    # -- capture windows -----------------------------------------------------
    def request_capture(self, reason: str) -> None:
        """Arm a capture window at the next `tick`. Signal-safe: a plain
        assignment, no locks, no I/O — callable straight from a SIGUSR1
        handler or any thread."""
        self._pending_reason = reason

    def maybe_autocapture(self, reason: str) -> bool:
        """Anomaly-detector entry: route a capture request unless one is
        already running/pending. Returns True when this call newly routed
        it — the caller then logs the anomaly. Deliberately NOT gated on
        the budget here: a budget-exhausted anomaly must still be visible
        (the next tick reports it through the once-only `denied` event)
        rather than vanish without a trace; spam is self-limiting because
        anomalous samples join the detector window and raise its p95."""
        if (self._path is None or self._capturing
                or self._pending_reason is not None):
            return False
        self._pending_reason = reason
        return True

    def tick(self, step=None) -> dict | None:
        """Advance the capture state machine one unit (a train step, a
        serve flush). Returns a small event dict on transitions (capture
        start / end / budget-denied) for the caller to land in
        events.jsonl, else None. Also polls the trigger file, time-gated
        so the stat() never rides every step."""
        if self._path is None:
            return None
        now = time.monotonic()
        if (self._trigger_path is not None
                and now - self._last_trigger_poll >= self._trigger_poll_secs):
            self._last_trigger_poll = now
            if os.path.exists(self._trigger_path):
                try:
                    os.remove(self._trigger_path)  # re-touch re-arms
                except OSError:
                    pass
                # also while a window is ACTIVE: the file is consumed
                # either way, so the request must queue (it starts on the
                # first tick after the current window ends) — deleting it
                # without arming would silently drop the operator's touch
                if self._pending_reason is None:
                    self._pending_reason = "trigger_file"
        if self._pending_reason is not None and not self._capturing:
            reason, self._pending_reason = self._pending_reason, None
            if self.captures_used >= self.capture_budget:
                if self._denied_reported:
                    return None
                self._denied_reported = True
                return {"action": "denied", "reason": reason,
                        "captures_used": self.captures_used,
                        "capture_budget": self.capture_budget}
            self.captures_used += 1
            self._capturing = True
            self._capture_left = self.capture_steps
            self._capture_reason = reason
            self.instant("capture_start", cat="capture", reason=reason,
                         step=step, captures_used=self.captures_used)
            self._start_profiler(reason, step)
            return {"action": "start", "reason": reason, "step": step,
                    "window_steps": self.capture_steps,
                    "captures_used": self.captures_used,
                    "capture_budget": self.capture_budget}
        if self._capturing:
            self._capture_left -= 1
            if self._capture_left <= 0:
                reason = self._capture_reason
                self._stop_profiler()
                self.instant("capture_end", cat="capture", reason=reason,
                             step=step)
                self._capturing = False
                self._capture_reason = ""
                self.flush()  # land the window's full-detail spans NOW
                return {"action": "end", "reason": reason, "step": step}
        return None

    def capture_state(self) -> dict:
        """The heartbeat/healthz payload: is a capture running, how much
        window is left, how much budget is spent."""
        return {
            "capturing": self._capturing,
            "window_steps_left": self._capture_left if self._capturing else 0,
            "captures_used": self.captures_used,
            "capture_budget": self.capture_budget,
        }

    def _start_profiler(self, reason: str, step) -> None:
        if self.profiler_hooks is None or self._traces_dir is None:
            return
        tag = f"{int(time.time())}-{reason}"
        if step is not None:
            tag += f"-s{step}"
        trace_dir = os.path.join(self._traces_dir, tag)
        try:
            os.makedirs(trace_dir, exist_ok=True)
            self.profiler_hooks[0](trace_dir)
            self._profiler_active = True
        except Exception as e:  # device profiler failure must not end the
            # run — the span capture still happens; the failure is visible
            # in the timeline and on `profiler_error`
            self._profiler_active = False
            self.profiler_error = repr(e)
            self.instant("profiler_error", cat="capture", error=repr(e))

    def _stop_profiler(self) -> None:
        if not self._profiler_active:
            return
        self._profiler_active = False
        try:
            self.profiler_hooks[1]()
        except Exception as e:  # ending the window must never end the run
            self.profiler_error = repr(e)
            self.instant("profiler_error", cat="capture", error=repr(e))

    # -- signals -------------------------------------------------------------
    def install_signal(self) -> bool:
        """SIGUSR1 → arm a capture window. Main-thread only (CPython
        restriction); returns False elsewhere. The previous handler is
        chained and restored by close()."""
        if threading.current_thread() is not threading.main_thread():
            return False
        prev = signal.getsignal(signal.SIGUSR1)

        def _handler(signum, frame):
            self.request_capture("sigusr1")  # assignment only: signal-safe
            if callable(prev):
                prev(signum, frame)

        self._prev_sigusr1 = prev
        signal.signal(signal.SIGUSR1, _handler)
        return True

    # -- teardown ------------------------------------------------------------
    def close(self) -> None:
        """Idempotent: stop any open capture, flush the ring, restore the
        signal disposition."""
        if self._capturing:
            self._stop_profiler()
            self.instant("capture_end", cat="capture",
                         reason=self._capture_reason, truncated=True)
            self._capturing = False
        self.flush()
        if self._prev_sigusr1 is not None:
            try:
                signal.signal(signal.SIGUSR1, self._prev_sigusr1)
            except ValueError:
                pass  # not the main thread anymore (interpreter teardown)
            self._prev_sigusr1 = None


def _dumps(rec: dict) -> str:
    """JSON without importing json at call time is not worth it — but the
    import IS stdlib; kept in a helper so a future binary format has one
    seam."""
    import json

    try:
        return json.dumps(rec)
    except (TypeError, ValueError):
        # foreign attr values (a numpy scalar from a caller): stringify
        # rather than lose the span
        return json.dumps({k: (v if isinstance(
            v, (str, int, float, bool, dict, list, type(None))) else str(v))
            for k, v in rec.items()}, default=str)
