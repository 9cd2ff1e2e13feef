"""Milliseconds a step the interpreter spent collecting garbage, on whatever
thread (the collector holds the interpreter lock, so the main thread stands
still with it): the window's mean of the step records' `gc_s`."""

from perfbench import step_phases


def read(run):
    return step_phases.mean_ms(run, (step_phases.GC_SECONDS,))
