"""Step-phase wall-clock splitting (ISSUE 2 tentpole part 2).

A training step's wall time decomposes into:

  data_s    — loader wait: the host blocked on the next batch (prefetch
              misses, decode stalls, filesystem hiccups)
  host_s    — dispatch: staging arrays + tracing-cache lookup + enqueue of
              the jitted program; in a healthy async pipeline this is the
              ONLY host cost per step
  device_s  — device-compute drain, measured ONLY on fenced samples: every
              `stride` steps the timer calls `block_until_ready` on a step
              output and times dispatch-return → ready. This measures the
              device backlog (the step itself plus anything still queued),
              which is the honest number for "is the device the
              bottleneck" — and the fence is what a comm/compute-overlap
              PR will move, so it must stay OFF the steady-state path
              (stride=0 never fences; off-stride steps stay fully async).
  comm_s    — gradient-sync tail (ISSUE 6), measured only on the SAME fenced
              samples: the step emits two probe scalars (gradsync's
              grads-ready psum and a reading of the reduced grads) and the
              fence drains them in order — comm_s is drain(reduced) −
              drain(grads-ready), i.e. how long the step sat between "local
              grads exist" and "the sync is visible". Honest caveat: on
              backends that materialize all program outputs atomically
              (CPU) both probes drain
              together and comm_s reads ~0 — the analytic sync-bytes/step
              in the `grad_sync` records is the backend-independent signal.
  telemetry_s — span-layer/telemetry self-time (ISSUE 8 satellite fix):
              the `telemetry` span, `RunTelemetry.on_step` — record
              building, span flushes, trigger-file polls, capture
              transitions, a stall's dump of the look-back ring. A phase
              of its own, so a capture window (which makes the span layer
              temporarily expensive on purpose) cannot masquerade as a
              data/host-phase regression in the phase-share report.
  wait_s    — the `sentinel` span: the one-step-lag read of the step
              BEFORE's loss. In a device-bound steady state this is where
              the main thread spends the step (it is the wait for the
              device), so a stall of the device shows here (ISSUE 35).
  fence_s   — the `fence` span, on fenced steps only: the stride-gated
              drain of THIS step (`device_s` is the same wait measured
              from the dispatch's return).
  readback_s — the `loss_readback` spans: the print's `float(v)` of this
              step's metrics and the health block's `device_get`. Like the
              fence they read the step JUST dispatched and so drain the
              device's queue.
  loop_s    — what is left of `step_s`: the loop's own (the meters,
              `resize.poll`, the watchdog's beat, `progress.display`,
              `writer.write`), under no span.
  step_s    — the whole iteration; on fenced steps it includes the fence
              wait. `data_s + host_s + telemetry_s + wait_s + fence_s +
              readback_s + loop_s == step_s` on every record (each rounded
              to the microsecond first, `loop_s` derived from the rounded
              ones); a phase that took no time is left out (`data_s` and
              `host_s` always stand).

Every phase but `loop_s` is booked by `phase(field)`, a context manager the
driver enters as the second item of the `with` that opens the tracer span
of the same phase (`data_wait` / `dispatch` / `sentinel` / `fence` /
`loss_readback` / `telemetry`: `scopes.STEP_PHASES`), so a step record's
field and the span a profiler trace holds are the same interval, and the
field has clock reads where the span at `trace_mode` `off` is an annotation
alone. The intervals themselves are kept for one step (`last_step`): after
a `stall` the tracer writes the stalled step's spans from them. The
`telemetry` span runs AFTER `finish_step` (it needs the phases), so its
seconds land in the NEXT record's `telemetry_s`: the window they were spent
in.

`starved` (ISSUE 35): just before a dispatch the driver hands
`probe_idle` the loss of the step before; if it `is_ready()` (a
non-blocking query, no wait, no transfer) nothing was queued behind it, so
the device stands idle now and until this dispatch returns. The record
carries `starved: 1` on such steps: it counts the drains after `fence` and
`loss_readback`, and reads every step where the feed bounds the run.

Usage per iteration (driver order):
    timer.epoch_start()                  # aligns the first window
    with tracer.span("data_wait", ...), timer.phase("data_s"): ... loader yields ...
    timer.probe_idle(previous_loss)
    with tracer.span("dispatch", ...), timer.phase("host_s"): ... dispatch returns ...
    timer.maybe_fence(step, sync_obj)    # stride-gated, inside phase("fence_s")
    phases = timer.finish_step()         # {"step_s", "data_s", "host_s", ...}
    with tracer.span("telemetry", ...), timer.phase("telemetry_s"): ... on_step(phases) ...

`GcWatch` (ISSUE 35) counts the interpreter's collections where they happen
(`gc.callbacks`): a full collection over a large heap holds the interpreter
lock for its whole length and lands in whichever phase the main thread is in.
"""

from __future__ import annotations

import gc
import time

#: the booked phases of a step record, in the record's order; `loop_s` is
#: what they leave of `step_s`
PHASE_FIELDS = ("data_s", "host_s", "telemetry_s", "wait_s", "fence_s",
                "readback_s")
LOOP_FIELD = "loop_s"


class _Phase:
    """`StepPhaseTimer.phase(field)`: two clock reads around the body."""

    __slots__ = ("_timer", "_field", "_t0")

    def __init__(self, timer: "StepPhaseTimer", field: str):
        self._timer, self._field, self._t0 = timer, field, 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        now = time.perf_counter()
        timer = self._timer
        timer._booked[self._field] += now - self._t0
        timer._spans.append((self._field, self._t0, now))
        if self._field == "host_s":
            timer._t_dispatch = now
        return False


class StepPhaseTimer:
    def __init__(self, stride: int = 0):
        self.stride = max(int(stride), 0)
        self.fences = 0  # how many steps actually paid a fence (tests pin
                         # that this NEVER exceeds steps/stride)
        self._t_iter = None
        self._t_dispatch = None
        self._device_s = None
        self._comm_s = None
        self._starved = False
        self._booked = dict.fromkeys(PHASE_FIELDS, 0.0)
        self._spans = []
        # the step `finish_step` closed last: its window and the intervals
        # of its phases, `(field, start, end)` on `perf_counter`
        self.last_step = None

    def _rearm(self, now: float | None) -> None:
        self._t_iter = now
        self._t_dispatch = None
        self._device_s = None
        self._comm_s = None
        self._starved = False
        for field in PHASE_FIELDS:
            self._booked[field] = 0.0
        self._spans = []

    def epoch_start(self) -> None:
        # telemetry time booked after the previous epoch's last step falls
        # outside every step window: dropped with the rest
        self._rearm(time.perf_counter())

    def phase(self, field: str) -> _Phase:
        """Context manager: the body's seconds are booked into `field` (one
        of `PHASE_FIELDS`) of the current iteration window. The end of
        `host_s` is also the dispatch's return, from which a fence measures
        `device_s`."""
        return _Phase(self, field)

    def probe_idle(self, pending) -> None:
        """Just before a dispatch: `pending` is the loss of the step before
        (None before the first). Where it is ready nothing is queued behind
        it, so this dispatch goes to an idle device: the record's `starved`.
        `is_ready()` is a non-blocking query of the array: no wait, no
        transfer. It asks every shard, so on several devices it answers
        "all of them are done"."""
        is_ready = getattr(pending, "is_ready", None)
        if is_ready is not None and is_ready():
            self._starved = True

    def fence_due(self, step: int) -> bool:
        """Whether `maybe_fence(step, ...)` would block on the device."""
        return (self.stride > 0 and step % self.stride == 0
                and self._t_dispatch is not None)

    def maybe_fence(self, step: int, sync_obj, comm_pre=None,
                    comm_post=None) -> float | None:
        """Stride-gated device fence; returns device_s on sampled steps.

        `sync_obj` is any step output (the loss array); draining it fences
        this step's program and everything queued before it. A scalar is
        read back (`float`), which is a fence by construction;
        `block_until_ready` covers non-scalar outputs.

        `comm_pre`/`comm_post` are the gradient-sync probe scalars (ISSUE
        6): when both are present on a fenced step they are drained FIRST,
        in order, and their gap is recorded as the `comm_s` phase — see the
        module docstring for what that number can and cannot claim."""
        if not self.fence_due(step):  # off-stride, or no dispatch yet
            return None
        if comm_pre is not None and comm_post is not None:
            try:
                float(comm_pre)
                t_pre = time.perf_counter()
                float(comm_post)
                self._comm_s = max(time.perf_counter() - t_pre, 0.0)
            except (TypeError, ValueError):
                self._comm_s = None  # non-scalar probes: no comm sample
        try:
            float(sync_obj)
        except (TypeError, ValueError):
            import jax

            jax.block_until_ready(sync_obj)
        self._device_s = time.perf_counter() - self._t_dispatch
        self.fences += 1
        return self._device_s

    def finish_step(self) -> dict:
        """Close the iteration; returns the phase dict and re-arms for the
        next step (the next window starts now). Seconds are rounded to the
        microsecond HERE and `loop_s` is taken from the rounded ones, so
        the phases of a record sum to its `step_s` exactly."""
        now = time.perf_counter()
        t0 = self._t_iter if self._t_iter is not None else now
        phases = {"step_s": round(now - t0, 6)}
        left = phases["step_s"]
        for field in PHASE_FIELDS:
            seconds = round(self._booked[field], 6)
            if seconds > 0.0 or field in ("data_s", "host_s"):
                phases[field] = seconds
                left -= seconds
        left = round(left, 6)
        if left != 0.0:
            phases[LOOP_FIELD] = left
        if self._device_s is not None:
            phases["device_s"] = self._device_s
        if self._comm_s is not None:
            phases["comm_s"] = self._comm_s
        if self._starved:
            phases["starved"] = 1
        self.last_step = (t0, now, self._spans)
        self._rearm(now)
        return phases


class GcWatch:
    """Seconds and counts of the interpreter's collections, from
    `gc.callbacks`: summed on whatever thread a collection runs (it holds
    the interpreter lock throughout, so the main thread stands still with
    it), generation 2 counted apart. `drain()` hands over what ENDED since
    the last drain: `gc_s`, `gc_n`, `gc2_n`, each left out at 0."""

    def __init__(self):
        self._t0 = None
        self._seconds = 0.0
        self._n = 0
        self._n2 = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self._seconds += time.perf_counter() - self._t0
            self._t0 = None
            self._n += 1
            if info.get("generation") == 2:
                self._n2 += 1

    def drain(self) -> dict:
        out = {}
        if self._n:
            out = {"gc_s": round(self._seconds, 6), "gc_n": self._n}
            if self._n2:
                out["gc2_n"] = self._n2
            self._seconds, self._n, self._n2 = 0.0, 0, 0
        return out

    def close(self) -> None:
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass
