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
              record-keeping the telemetry stack itself paid inside this
              step's window — span flushes, trigger-file polls, capture
              transitions, the on_step bookkeeping. Booked explicitly via
              `note_telemetry` and SUBTRACTED from the window it would
              otherwise pollute, so a capture window (which makes the
              span layer temporarily expensive on purpose) cannot
              masquerade as a data/host-phase regression in the
              phase-share report. In this driver the telemetry work runs
              between one step's finish and the next step's loader wait,
              so the polluted window is the NEXT step's `data_s`.
  step_s    — the whole iteration (data_s + host_s + meters + everything);
              on fenced steps it includes the fence wait.

Usage per iteration (driver order):
    timer.epoch_start()                  # aligns the first data window
    ... loader yields ...
    timer.mark_data()
    ... fused_step dispatch returns ...
    timer.mark_dispatch()
    timer.maybe_fence(step, sync_obj)    # stride-gated block_until_ready
    phases = timer.finish_step()         # {"data_s", "host_s", ...}

The driver makes each mark as the LAST statement inside the tracer span
of the same phase (`data_wait` around the loader's `next`, `dispatch`
around the step call; ISSUE 25), so the step record's `data_s` / `host_s`
and the spans a profiler trace holds end on the same clock read and
cannot disagree; `fence_due` lets it open the `fence` span on fenced steps
only.
"""

from __future__ import annotations

import time


class StepPhaseTimer:
    def __init__(self, stride: int = 0):
        self.stride = max(int(stride), 0)
        self.fences = 0  # how many steps actually paid a fence (tests pin
                         # that this NEVER exceeds steps/stride)
        self._t_iter = None
        self._t_data = None
        self._t_dispatch = None
        self._device_s = None
        self._comm_s = None
        self._telemetry_s = 0.0

    def epoch_start(self) -> None:
        now = time.perf_counter()
        self._t_iter = now
        self._t_data = self._t_dispatch = None
        self._device_s = None
        self._comm_s = None
        # telemetry time booked after the previous epoch's last step falls
        # outside every step window — dropping it is correct, carrying it
        # would over-subtract from the new epoch's first data phase
        self._telemetry_s = 0.0

    def note_telemetry(self, seconds: float) -> None:
        """Book span-layer/telemetry self-time into the CURRENT iteration
        window (the driver calls this right after its per-step telemetry
        work, which runs between finish_step and the next loader wait)."""
        self._telemetry_s += max(float(seconds), 0.0)

    def mark_data(self) -> None:
        self._t_data = time.perf_counter()

    def mark_dispatch(self) -> None:
        self._t_dispatch = time.perf_counter()

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
        if not self.fence_due(step):  # off-stride, or no dispatch mark
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
        next step (the next data window starts now)."""
        now = time.perf_counter()
        t0 = self._t_iter if self._t_iter is not None else now
        t_data = self._t_data if self._t_data is not None else t0
        t_disp = self._t_dispatch if self._t_dispatch is not None else t_data
        # carve the booked telemetry self-time OUT of the phase it landed
        # in (the loader-wait window, see the class docstring) into its
        # own bucket: data_s + host_s + telemetry_s still sums within
        # step_s, and the phase-share report stops blaming the input
        # pipeline for capture-window overhead
        telemetry_s = min(self._telemetry_s, max(t_data - t0, 0.0))
        phases = {
            "step_s": now - t0,
            "data_s": max(t_data - t0 - telemetry_s, 0.0),
            "host_s": t_disp - t_data,
        }
        if telemetry_s > 0.0:
            phases["telemetry_s"] = telemetry_s
        if self._device_s is not None:
            phases["device_s"] = self._device_s
        if self._comm_s is not None:
            phases["comm_s"] = self._comm_s
        self._t_iter = now
        self._t_data = self._t_dispatch = None
        self._device_s = None
        self._comm_s = None
        self._telemetry_s = 0.0
        return phases
