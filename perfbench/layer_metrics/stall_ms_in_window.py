"""Milliseconds the window lost in stalled steps (the program's `stall` rule,
taken against the window's own median step): 0 in a sound window; a run that
reads otherwise is the one whose rate lies low."""

from perfbench import step_phases


def read(run):
    return step_phases.stall_ms(run)
