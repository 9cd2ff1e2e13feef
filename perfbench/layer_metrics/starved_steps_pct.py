"""Share of the window's steps dispatched to an idle device: the program asks,
just before a dispatch, whether the step before is done already (`starved` on
the step record). In a device-bound cell these are the steps after a read-back
of the step just dispatched (the fence, the print); where the feed bounds the
run it reads every step."""

from perfbench import step_phases


def read(run):
    recs, starved = step_phases.starved(run)
    if recs is None:
        return None
    return 100.0 * len(starved) / len(recs)
