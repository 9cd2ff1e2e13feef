"""RunTelemetry — the one object the driver talks to (ISSUE 2 tentpole).

Owns the registry/sink, the phase timer, the MFU estimator, the device
monitor, the pod aggregator, and the heartbeat, and registers itself as a
`log_event` sink so every resilience incident (preempt / rollback / chaos
/ watchdog / sentinel) lands in the same events.jsonl stream it writes
step records to.

Process topology: EVERY process builds a RunTelemetry (the pod allgather
needs all hosts' vectors), but only process 0 gets a file sink and a
heartbeat — non-main registries aggregate instruments and drop record
buffers, so the call sites stay identical on every host.

Overhead contract (acceptance criterion): with telemetry off the driver
holds no RunTelemetry and none of these paths run; with it on, the only
synchronizing call is the stride-gated fence inside StepPhaseTimer —
everything else is host-side arithmetic and buffered writes.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time

from moco_tpu.telemetry.device import DeviceMonitor
from moco_tpu.telemetry.mfu import MFUEstimator
from moco_tpu.telemetry.pod import PodAggregator
from moco_tpu.telemetry.registry import (
    EVENTS_FILENAME,
    HEARTBEAT_FILENAME,
    Heartbeat,
    MetricsRegistry,
)
from moco_tpu.data.stats import InputPipelineStats
from moco_tpu.telemetry import scopes
from moco_tpu.telemetry.timing import (
    LOOP_FIELD,
    PHASE_FIELDS,
    GcWatch,
    StepPhaseTimer,
)
from moco_tpu.telemetry.trace import (
    SlowSampleDetector,
    StallDetector,
    Tracer,
    null_tracer,
)
from moco_tpu.utils import logging as mlog
from moco_tpu.utils.cache import CompileCounters

#: the loop span that a step record's field times (`scopes.STEP_PHASES`)
_SPAN_OF_FIELD = {field: name for name, field in scopes.STEP_PHASES.items()}


class RunTelemetry:
    def __init__(self, config, *, n_chips: int, n_procs: int,
                 process_index: int, steps_per_epoch: int, device=None):
        import jax

        if device is None:
            device = jax.local_devices()[0]
        is_main = process_index == 0
        run_dir = config.telemetry_dir
        self.events_path = os.path.join(run_dir, EVENTS_FILENAME)
        # span layer (ISSUE 8): process 0 only, like every file sink. The
        # tracer exists even at trace_mode="off" — that is what makes the
        # SIGUSR1 / trigger-file / anomaly capture windows reachable on a
        # run that wasn't started with tracing on. `lookback`: at `off` the
        # staging threads' coarse spans are held in memory, so that a
        # `stall` (below) can write them beside the slow step (ISSUE 35).
        self.tracer = (
            Tracer(
                run_dir,
                getattr(config, "trace_mode", "off"),
                proc="driver",
                capture_steps=getattr(config, "trace_capture_steps", 50),
                capture_budget=getattr(config, "trace_capture_budget", 3),
                lookback=True,
            )
            if is_main else null_tracer()
        )
        self.tracer.install_signal()
        if is_main:
            # every span of this process also enters the profiler's trace
            # (ISSUE 25): a no-op while no profiler session is open
            self.tracer.annotation_factory = functools.partial(
                _annotation, jax.profiler)
        if is_main and getattr(config, "trace_device_profile", False):
            self.tracer.profiler_hooks = (_profiler_start, _profiler_stop)
        # anomaly detectors arming the capture window (budgeted in the
        # tracer): a slow step vs the rolling p95, and a staging stall
        # seen as a data-phase blowout (the consumer side of an empty
        # prefetch queue). Floors keep µs-scale noise on a healthy phase
        # from ever tripping them.
        # skip=3: the cold-compile/warmup steps are seconds-scale by
        # design; left in the window they put k×p95 at compile scale and
        # hide every later real anomaly. Higher input-stall floor: the
        # first step after an epoch boundary legitimately waits on a fresh
        # Prefetcher's spin-up — a sub-250 ms data wait is never the stall
        # worth spending a capture budget on.
        k = getattr(config, "trace_slow_step_k", 3.0)
        self._slow_step = SlowSampleDetector(k=k, floor_s=0.005, skip=3)
        self._input_stall = SlowSampleDetector(k=k, floor_s=0.25, skip=3)
        # the stall rule (ISSUE 35; `trace.is_stall`): its event says in
        # which phase the time went and has the look-back ring written.
        # It arms no capture: the k × p95 detectors above do, as before.
        self._stall = StallDetector(PHASE_FIELDS + (LOOP_FIELD,))
        # collections of the interpreter, counted where they happen
        self.gc = GcWatch()
        self.registry = MetricsRegistry(
            self.events_path if is_main else None,
            flush_every=config.telemetry_flush_steps,
            stamp={"run_id": self.tracer.run_id,
                   "trace_id": self.tracer.trace_id} if is_main else None,
        )
        self.heartbeat = (
            Heartbeat(os.path.join(run_dir, HEARTBEAT_FILENAME),
                      min_interval_secs=getattr(config, "heartbeat_secs", 1.0))
            if is_main else None
        )
        self.timer = StepPhaseTimer(stride=config.telemetry_stride)
        # input-pipeline counters (ISSUE 3): threaded into every Prefetcher
        # and CachedDataset of the run by the driver; snapshots ride the
        # step records at the device-sampling stride
        self.input_stats = InputPipelineStats()
        # compile counters (ISSUE 25): cumulative on every step record; the
        # listeners are unregistered in close()
        self.compiles = CompileCounters()
        self._compiles_seen = 0
        # set-up spans' seconds by name, written once as the `setup` event
        self._setup_s: dict = {}
        self._setup_emitted = False
        self._attn: dict | None = None
        self._moe: dict | None = None
        self.mfu = MFUEstimator.for_config(config, n_chips, device.device_kind)
        self.devices = DeviceMonitor(device)
        self.pod = PodAggregator(self.registry, n_procs, process_index)
        self.n_chips = n_chips

        self._step_hist = self.registry.histogram("step_s")
        self._mfu_hist = self.registry.histogram("mfu")
        self._hbm_gauge = self.registry.gauge("hbm_peak_bytes")
        self._incidents = self.registry.counter("incidents")
        self._grad_sync: dict | None = None
        self._closed = False
        mlog.add_event_sink(self._on_event)
        self.registry.emit(
            "run_start",
            name=config.name,
            variant=config.variant,
            arch=config.arch,
            image_size=config.image_size,
            batch_size=config.batch_size,
            steps_per_epoch=steps_per_epoch,
            n_chips=n_chips,
            n_procs=n_procs,
            sharding=getattr(config, "sharding", "dp"),
            platform=device.platform,
            device_kind=device.device_kind,
            peak_flops_per_chip=self.mfu.peak_flops_per_chip,
            flops_per_step=self.mfu.flops_per_step,
            flops_per_image=self.mfu.flops_per_step / max(config.batch_size, 1),
            telemetry_stride=config.telemetry_stride,
        )
        if self.heartbeat is not None:
            self.heartbeat.beat(0, phase="run_start")

    # -- incidents (log_event sink) -----------------------------------------
    def _on_event(self, kind: str, msg: str, fields: dict) -> None:
        self._incidents.inc()
        self.registry.emit("event", event=kind, msg=msg, **fields)

    def event(self, kind: str, **fields) -> None:
        """Structured non-incident event (e.g. knn_eval, epoch_summary)."""
        self.registry.emit("event", event=kind, **fields)

    def set_grad_sync(self, info: dict) -> None:
        """Record the gradient-sync plan (ISSUE 6): mode, knobs, analytic
        sync-bytes/step/device. Emitted once as a routine `grad_sync` event;
        the compressed modes (quantized/demo) also stamp the dict onto step
        records at the sampling stride, so a stream tail is self-describing
        about the bytes its step times were measured under."""
        self._grad_sync = dict(info)
        self.registry.emit("event", event="grad_sync", **info)

    def set_sharding(self, info: dict) -> None:
        """Record the sharding plan (ISSUE 15): mode, mesh shape, measured
        per-device param/optimizer bytes. One routine `sharding` event —
        the per-device footprint claim every "fsdp cuts state N-fold" row
        in a BENCH record rests on; telemetry_report renders it as the
        `sharding:` line and MFU is thereby labeled per mode."""
        self.registry.emit("event", event="sharding", **info)

    def phase_beat(self, phase: str, step: int) -> None:
        """Forced heartbeat declaring a known-long non-step phase (the
        epoch-boundary kNN eval): the supervisor widens its staleness
        window to the startup grace while the newest beat's phase is not
        "step" — the out-of-process analogue of StepWatchdog.suspended()
        (a multi-minute eval with no step beats would otherwise be killed
        as a hang)."""
        if self.heartbeat is not None:
            self.heartbeat.beat(step, phase=phase)

    # -- set-up ---------------------------------------------------------------
    @contextlib.contextmanager
    def setup_span(self, name: str):
        """One of the run's set-up phases (`scopes.SETUP_SPANS`): a tracer
        span, so it reaches `spans.jsonl` and a profiler trace like any
        other, whose seconds are also kept for the `setup` event — the
        same two clock reads for both."""
        with self.tracer.span(name, cat="setup"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self._setup_s[name] = (self._setup_s.get(name, 0.0)
                                       + time.perf_counter() - t0)

    def _stalled_step(self, step: int, phases: dict) -> dict | None:
        """The step the timer closed last as `Tracer.dump_lookback` takes
        it: its window, its record's phases, and the interval of each phase
        under its span's name (`scopes.STEP_PHASES`)."""
        if self.timer.last_step is None:
            return None
        t0, t1, booked = self.timer.last_step
        return {"window": (t0, t1),
                "attrs": dict({k: round(float(v), 6)
                               for k, v in phases.items()}, step=int(step)),
                "spans": [(_SPAN_OF_FIELD[f], a, b) for f, a, b in booked]}

    def set_attn(self, plan: dict) -> None:
        """How a token encoder's attention was built (`ops/pallas_attention.py::
        attention_plan`: the path, the score tiles computed and skipped, and who
        prepares q and k: `qk_prep`; under remat, what a layer keeps from the
        query forward to the backward pass: `kept`, names and bytes a layer).
        Static per program, so it rides the `setup` event and costs the step
        nothing."""
        self._attn = dict(plan)

    def set_moe(self, path: dict) -> None:
        """How a routed encoder's expert layers move their rows (`models/sdar.py::
        dispatch_path`: `kernels` or `xla`, and the passes' static sizes).
        Static per program, as `set_attn`."""
        self._moe = dict(path)

    def _emit_setup(self) -> None:
        """Once, with the first step record: every set-up span's seconds, and
        the `attn` and `moe` blocks where the encoder has them."""
        self._setup_emitted = True
        fields = {name: block for name, block in (("attn", self._attn), ("moe", self._moe))
                  if block is not None}
        self.registry.emit(
            "event", event="setup",
            spans={k: round(v, 6) for k, v in self._setup_s.items()}, **fields,
        )

    # -- per-step ------------------------------------------------------------
    def on_step(self, step: int, phases: dict, throughput, loss=None,
                health: dict | None = None) -> bool:
        """Emit one step record; returns True when this step flushed the
        sink (the driver aligns ScalarWriter.flush with that cadence).

        `health` is the learning-health block (ISSUE 13): the driver
        passes the host-pulled collapse diagnostics on health-stride
        steps (None otherwise), and the record carries them under a
        `health` sub-dict — the obsd `health:<key>` objectives and the
        report's `health:` section read exactly that shape.

        Everything this method does — record building, span recording,
        capture-window ticks, detector checks, a stall's dump of the
        look-back ring — runs inside the driver's `telemetry` span and
        phase, which book it as the NEXT record's `telemetry_s`, so the
        phase-share report never blames the input pipeline for the span
        layer's own cost (ISSUE 8 satellite)."""
        gc_seen = self.gc.drain()
        stall = self._stall.observe(phases)
        if stall is not None:
            dump = self.tracer.can_dump()
            self.registry.emit(
                "event", event="stall", step=int(step),
                step_s=phases["step_s"], **stall,
                phases={k: round(float(v), 6) for k, v in phases.items()},
                gc_s=gc_seen.get("gc_s", 0.0), gc2_n=gc_seen.get("gc2_n", 0),
                starved=int(phases.get("starved", 0)),
                queue_depth=self.input_stats.queue_depth_last,
                dump=dump,
            )
            if dump:
                self.tracer.dump_lookback(self._stalled_step(step, phases))
        # anomaly → capture window (budgeted): check BEFORE the step span
        # records, so the capture's full-detail window starts as early as
        # the step after the anomaly
        # the anomaly event lands whenever the request was newly routed —
        # including past the capture budget, where the tick below answers
        # with one visible `denied` instead of a silent nothing
        if self._slow_step.observe(phases["step_s"]):
            if self.tracer.maybe_autocapture("slow_step"):
                self.registry.emit(
                    "event", event="trace_anomaly", anomaly="slow_step",
                    step=int(step), step_s=round(phases["step_s"], 6),
                    # pre-append snapshot: .p95() here would already
                    # contain the anomalous sample and could equal it
                    p95_s=round(self._slow_step.last_p95, 6),
                )
        if self._input_stall.observe(phases["data_s"]):
            if self.tracer.maybe_autocapture("input_stall"):
                self.registry.emit(
                    "event", event="trace_anomaly", anomaly="input_stall",
                    step=int(step), data_s=round(phases["data_s"], 6),
                    p95_s=round(self._input_stall.last_p95, 6),
                )
        self.tracer.record_step(step, phases)
        capture_evt = self.tracer.tick(step)
        if capture_evt is not None:
            self.registry.emit("event", event="trace_capture", **capture_evt)
        if not self._setup_emitted:
            self._emit_setup()
        compiles = self.compiles.snapshot()
        if compiles["n"] > self._compiles_seen:
            recent = self.compiles.drain_recent()
            if step > 2:
                # steps 1 and 2 each load one step program by design; any
                # later compile names the step it fell in
                self.registry.emit(
                    "event", event="compile", step=int(step),
                    n=compiles["n"] - self._compiles_seen,
                    fun_names=recent[:16])
            self._compiles_seen = compiles["n"]
        record = dict(step=int(step))
        record["compile"] = compiles
        for key, value in phases.items():
            record[key] = round(value, 6)
        record.update(gc_seen)
        if phases.get("step_s"):
            # the data-stall share, stamped per record (ISSUE 12): the
            # SLO rules and the live tail key on it directly instead of
            # each consumer re-deriving data_s/step_s
            record["data_share"] = round(
                phases.get("data_s", 0.0) / phases["step_s"], 4)
        rolling = throughput.rolling_imgs_per_sec
        record["imgs_per_sec"] = round(rolling, 2)
        record["imgs_per_sec_cum"] = round(throughput.imgs_per_sec, 2)
        self._step_hist.observe(phases["step_s"])
        mfu = self.mfu.mfu(phases["step_s"])
        if mfu is not None:
            record["mfu"] = round(mfu, 5)
            self._mfu_hist.observe(mfu)
        if loss is not None:
            record["loss"] = float(loss)
        if health:
            record["health"] = dict(health)
        stride = self.timer.stride or self.registry.flush_every
        if step % stride == 0:
            sampled = self.devices.sample()
            record.update(sampled)
            if "hbm_peak_bytes" in sampled:
                self._hbm_gauge.set(sampled["hbm_peak_bytes"])
            self.pod.update(**sampled)
            if self.input_stats.staged_batches:
                # queue depth / cache hit rate / staged-batch latency /
                # worker busy fraction, cumulative for the run so far
                record["input"] = self.input_stats.snapshot()
            if self._grad_sync and self._grad_sync.get("mode") in (
                    "quantized", "demo"):
                record["grad_sync"] = self._grad_sync
        self.pod.update(
            step_s=phases["step_s"], data_s=phases["data_s"],
            imgs_per_sec=rolling, incidents=self._incidents.value,
        )
        flushed = self.registry.emit("step", **record)
        if self.heartbeat is not None:
            # EVERY step, decoupled from the sink's flush cadence (ISSUE 4
            # satellite): hang-detection granularity used to be an accident
            # of telemetry_flush_steps — a 50-step flush cadence meant the
            # supervisor saw a "hang" of 50 step times. The time gate
            # (heartbeat_secs) keeps the atomic replace off the fast path.
            # `last_step_ms` + `trace` (ISSUE 8 satellite): the supervisor
            # and /healthz read "currently profiling" and the latest step
            # time straight from the beat, no events.jsonl scrape.
            self.heartbeat.maybe_beat(
                step, phase="step",
                last_step_ms=round(phases["step_s"] * 1e3, 1),
                trace=self.tracer.capture_state(),
            )
        return flushed

    # -- pod sync (piggybacks on the resilience_sync_steps allgather) --------
    def pod_vector(self):
        return self.pod.local_vector()

    def pod_record(self, step: int, gathered) -> None:
        self.pod.record(step, gathered)

    # -- teardown ------------------------------------------------------------
    def close(self, **extra_summary) -> None:
        """Idempotent: the driver closes with the run summary in its normal
        finally; a bare safety-net close after an early abort no-ops if the
        rich close already ran."""
        if self._closed:
            return
        self._closed = True
        mlog.remove_event_sink(self._on_event)
        self.compiles.close()
        self.gc.close()
        summary = dict(
            steps=self._step_hist.count,
            incidents=self._incidents.value,
        )
        if self._step_hist.count:
            summary.update(
                step_s_p50=round(self._step_hist.percentile(50), 6),
                step_s_p95=round(self._step_hist.percentile(95), 6),
                step_s_p99=round(self._step_hist.percentile(99), 6),
            )
        if self._mfu_hist.count:
            summary["mfu_mean"] = round(self._mfu_hist.mean, 5)
        if self._hbm_gauge.high_water > float("-inf"):
            summary["hbm_peak_bytes"] = int(self._hbm_gauge.high_water)
        if self.input_stats.staged_batches:
            summary["input"] = self.input_stats.snapshot()
        summary["compile"] = self.compiles.snapshot()
        if self.tracer.captures_used or self.tracer.spans_recorded:
            summary["trace"] = dict(
                self.tracer.capture_state(),
                spans_recorded=self.tracer.spans_recorded,
            )
        summary.update(extra_summary)
        self.registry.emit("run_end", **summary)
        if self.heartbeat is not None:
            # the final heartbeat is the supervisor's progress record for
            # the restart-budget refund: last completed step + this pid,
            # phase distinguishing a preemption exit (relaunch expected)
            # from a natural end
            phase = "run_end"
            if summary.get("preempted"):
                phase = "preempt_exit"
            elif summary.get("resized"):
                # a resize exit expects a relaunch onto a NEW mesh; any
                # non-"step" phase already widens the supervisor's
                # staleness window during the elastic checkpoint
                phase = "resize_exit"
            self.heartbeat.beat(
                summary.get("last_step", self._step_hist.count),
                phase=phase,
                trace=self.tracer.capture_state(),
            )
        self.registry.close()
        self.tracer.close()


def _annotation(profiler, name: str, attrs: dict):
    """The tracer's annotation factory (bound to `jax.profiler`, which
    trace.py may not import): a span named `step` enters the profiler as
    `StepTraceAnnotation("train", step_num=...)`, which gives the device
    plane its `Steps` line; every other span as a `TraceAnnotation` of
    its own name."""
    if name == scopes.STEP_SPAN:
        return profiler.StepTraceAnnotation(
            scopes.STEP_ANNOTATION, step_num=int(attrs.get("step", 0)))
    return profiler.TraceAnnotation(name)


def _profiler_start(trace_dir: str) -> None:
    """Capture-window device-trace hook (config: trace_device_profile).
    Lazy jax import: trace.py itself must stay jax-free, so the hooks are
    injected from this (already jax-coupled) module."""
    import jax

    jax.profiler.start_trace(trace_dir)


def _profiler_stop() -> None:
    import jax

    jax.profiler.stop_trace()
