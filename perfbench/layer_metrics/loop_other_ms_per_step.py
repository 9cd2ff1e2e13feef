"""Main-thread milliseconds a step under none of the loop's spans (the meters,
the watchdog's beat, `resize.poll`, the progress line, the scalar writer): the
window's mean of the step records' `loop_s`. `loop_unspanned_ms_per_step` over
every step of the window instead of one traced step after it."""

from perfbench import step_phases


def read(run):
    return step_phases.mean_ms(run, ("loop_s",))
