"""Share of a window step in which the device ran no step program: one less the
fused step's median device time in the trace over the window's mean step
interval on the host's clock. The traced steps themselves follow the profiler's
start, during which the feed runs ahead, so they run back to back whatever the
window did; where the trace has no line of programs (the CPU backend), the
trace's own idle share is all there is."""

import statistics

from perfbench import trace_reduce


def read(run):
    t = run["trace"]
    ds = trace_reduce.durations(t["programs"], "fused_step")
    if ds and run.get("window_step_s"):
        return 100.0 * (1.0 - statistics.median(ds) / run["window_step_s"])
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
