"""What the main thread needs a step when it waits for nothing: the window's
mean of `data_s + host_s + telemetry_s + loop_s` (the loader's hand-over, the
step call's dispatch, the telemetry's own time and the loop's), every step of
the window, steady state. The step interval below which the cell turns
host-bound."""

from perfbench import step_phases


def read(run):
    return step_phases.mean_ms(run, step_phases.HOST_FLOOR)
