"""Persistent XLA compilation cache, at ONE place.

XLA compiles at trace time — minutes for the full R50 aug+step program —
so every entry point that compiles calls `enable_persistent_cache()` before
building a jitted program, and a restarted, resumed or repeated run loads
what the first one compiled.

Where the cache lives is decided outside the program when
`JAX_COMPILATION_CACHE_DIR` is set (JAX reads it itself; nothing here
overrides it), and is `<checkout>/.jax_cache` otherwise. Nowhere else: the
directory is part of the cache key, so a cache that moves never hits.
`MOCO_TPU_NO_CACHE=1` opts a process out (throwaway test children).

`CompileCounters` counts what the cache and the compiler did, from JAX's
own `jax.monitoring` events (ISSUE 25): how many programs were compiled or
loaded, the seconds that took, the persistent cache's hits and misses. The
telemetry puts its cumulative block on every step record, so a reader sees
set-up's compile seconds and whether anything compiled inside a window.
"""

from __future__ import annotations

import os
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_persistent_cache() -> str | None:
    """Returns the cache dir in effect, or None when opted out."""
    import jax

    if os.environ.get("MOCO_TPU_NO_CACHE"):
        # off for real: with the env var set JAX would cache there anyway
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


# the events JAX 0.9 records around a compile (jax/_src/dispatch.py,
# compiler.py, compilation_cache.py). The backend-compile duration spans
# `compile_or_get_cached`, so it times a cache read as well as a compile.
# Each duration event is announced by a scalar event of the same name when
# its interval opens; tracing nests (a jitted function traced inside
# another's trace reports its own duration too), so the listeners keep the
# depth per thread and count the outermost interval only.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration")
CACHE_EVENTS = {   # event -> the counter it bumps
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",   # recorded where an entry is written
}
FUSED_STEP_NAME = "fused_step"


class CompileCounters:
    """Cumulative compile counters of one run, fed by a `jax.monitoring`
    listener set that lives from construction to `close()` (a process may
    run `train()` many times; each run counts from its own zero). The
    listeners fire only when something is traced, lowered or compiled,
    from whichever thread does it."""

    def __init__(self):
        import jax

        self._monitoring = jax.monitoring
        self._lock = threading.Lock()
        self.n = 0               # backend compile requests (cache reads included)
        self.backend_s = 0.0
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.fused_step_n = 0    # ... of which the fused step program's,
        self.fused_step_s = 0.0  # and its trace + lower + backend seconds
        self._recent: list[str] = []   # fun_names since the last `drain_recent`
        self._depth = threading.local()  # open trace / lower intervals of this thread
        self._monitoring.register_event_duration_secs_listener(self._on_duration)
        self._monitoring.register_event_listener(self._on_event)
        self._monitoring.register_scalar_listener(self._on_scalar)
        self._open = True

    def _on_scalar(self, event: str, value, **kwargs) -> None:
        if event in TRACE_LOWER_EVENTS:   # an interval opens (its start time is the value)
            self._depth.n = getattr(self._depth, "n", 0) + 1

    def _on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            fun_name = str(kwargs.get("fun_name", ""))
            with self._lock:
                self.n += 1
                self.backend_s += float(duration_secs)
                if FUSED_STEP_NAME in fun_name:
                    self.fused_step_n += 1
                    self.fused_step_s += float(duration_secs)
                self._recent.append(fun_name)
        elif event in TRACE_LOWER_EVENTS:
            depth = max(getattr(self._depth, "n", 1) - 1, 0)
            self._depth.n = depth
            if depth == 0:                # the outermost: the nested are inside it
                with self._lock:
                    self.trace_lower_s += float(duration_secs)
                    if FUSED_STEP_NAME in str(kwargs.get("fun_name", "")):
                        self.fused_step_s += float(duration_secs)

    def _on_event(self, event: str, **kwargs) -> None:
        counter = CACHE_EVENTS.get(event)
        if counter is not None:
            with self._lock:
                setattr(self, counter, getattr(self, counter) + 1)

    def snapshot(self) -> dict:
        """The cumulative block a step record carries under `compile`."""
        with self._lock:
            return {
                "n": self.n,
                "backend_s": round(self.backend_s, 6),
                "trace_lower_s": round(self.trace_lower_s, 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "fused_step_n": self.fused_step_n,
                "fused_step_s": round(self.fused_step_s, 6),
            }

    def drain_recent(self) -> list[str]:
        """The `fun_name`s compiled since the last call."""
        with self._lock:
            recent, self._recent = self._recent, []
        return recent

    def close(self) -> None:
        """Unregister both listeners; idempotent."""
        if not self._open:
            return
        self._open = False
        for unregister, callback in (
                (self._monitoring.unregister_event_duration_listener,
                 self._on_duration),
                (self._monitoring.unregister_event_listener, self._on_event),
                (self._monitoring.unregister_scalar_listener, self._on_scalar)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):
                pass  # someone cleared every listener (jax.monitoring's own reset)
