"""The step records' phases, over every step of the window (ISSUE 35).

Since PR 35 a step record of the program carries, beside `data_s`, `host_s`
and `telemetry_s`, the main thread's other phases (`wait_s`: the wait for the
step before; `fence_s`, `readback_s`: the two read-backs of the step just
dispatched, which drain the device's queue; `loop_s`: the loop's own, under no
span), all seven summing to `step_s`; `starved: 1` where the step was
dispatched to an idle device; and the interpreter's collections that ended in
it (`gc_s`, `gc_n`, `gc2_n`). A field that is 0 is left out of its record.

The readers of `layer_metrics/` that take these go through here. A program
from before PR 35 has none of the new fields on any record: `records` then
answers `None` and so does every reader (the metric is left out of the line,
nothing is raised). The field names and the stall rule's three numbers are this
side's own copy, held to the program's (`moco_tpu/telemetry/timing.py`,
`trace.py`) by `tests/perfbench/test_perfbench_step_phases.py`.
"""

import statistics

PHASES = ("data_s", "host_s", "telemetry_s", "wait_s", "fence_s", "readback_s", "loop_s")
# what the main thread needs a step when it waits for nothing
HOST_FLOOR = ("data_s", "host_s", "telemetry_s", "loop_s")
# the fields by which a record shows that its program carries the phases
SINCE_35 = ("wait_s", "fence_s", "readback_s", "loop_s")
STARVED = "starved"
GC_SECONDS = "gc_s"

# `trace.is_stall`: over what the step is expected to take by this many seconds
# AND this share of the median; a step that waited for its own result (`trace.
# drained`: a record with one of `DRAINS`, or whose dispatch, `SYNC`, took a
# whole step or more: the harness closes its window with a wait for the device
# inside the last step's dispatch) is expected to take two steps of the device
STALL_MIN_EXCESS_S = 0.1
STALL_MIN_SHARE = 0.25
DRAINS = ("fence_s", "readback_s")
SYNC = "host_s"


def records(run):
    """The window's step records, or `None` where there are none or the
    program does not carry the phases."""
    recs = run["window_records"]
    if not recs or not any(f in r for r in recs for f in SINCE_35):
        return None
    return recs


def mean_ms(run, fields):
    """Mean over the window's steps of the sum of `fields`, in milliseconds."""
    recs = records(run)
    if recs is None:
        return None
    return 1e3 * sum(r.get(f, 0.0) for r in recs for f in fields) / len(recs)


def starved(run):
    """The window's records and those of them dispatched to an idle device."""
    recs = records(run)
    if recs is None:
        return None, None
    return recs, [r for r in recs if r.get(STARVED)]


def drained(rec: dict, median_s: float) -> bool:
    return any(rec.get(f) for f in DRAINS) or rec.get(SYNC, 0.0) >= median_s > 0.0


def expected_s(median_s: float, drained: bool = False) -> float:
    return (2.0 if drained else 1.0) * median_s


def is_stall(step_s: float, median_s: float, drained: bool = False) -> bool:
    excess = step_s - expected_s(median_s, drained)
    return excess >= STALL_MIN_EXCESS_S and excess >= STALL_MIN_SHARE * median_s


def stall_ms(run):
    """Milliseconds the window lost in stalled steps: over the steps that meet
    the program's rule against the WINDOW's median `step_s`, the excess over
    what the step was expected to take. 0 in a sound window."""
    recs = records(run)
    if recs is None:
        return None
    median_s = statistics.median(r["step_s"] for r in recs)
    lost = 0.0
    for r in recs:
        waited = drained(r, median_s)
        if is_stall(r["step_s"], median_s, waited):
            lost += r["step_s"] - expected_s(median_s, waited)
    return 1e3 * lost
