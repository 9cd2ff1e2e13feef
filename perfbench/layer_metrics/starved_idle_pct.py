"""The dispatch seconds (`host_s`) of the window's `starved` steps over the
window's `step_s`: how long the main thread took to hand the device its next
step on the occasions it found the device with nothing queued. One source,
steady state. It bounds the device's idle share from neither side (PERF.md §6,
PR 35): the device starts at the enqueue, before the dispatch call returns (in
the image cell it reads 3.11 beside a traced 1.07), and stands idle from the
drain's end, before the call begins (in the looped and long-document cells it
reads half the traced value). `starved_steps_pct` counts the occasions."""

from perfbench import step_phases


def read(run):
    recs, starved = step_phases.starved(run)
    if recs is None:
        return None
    total = sum(r["step_s"] for r in recs)
    return 100.0 * sum(r["host_s"] for r in starved) / total if total > 0 else None
