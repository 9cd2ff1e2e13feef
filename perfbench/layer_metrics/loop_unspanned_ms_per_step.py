"""Main-thread milliseconds of a traced step (the program's `train` step
annotation) covered by none of its child spans: the driver loop's self time.
A sample of the traced steps, which the harness takes after the window (one
whole step in a run of the manifest's cell), not of steady state."""

from perfbench import program_spans


def read(run):
    red = program_spans.reduction(run)
    if red is None or not red["loop"]:
        return None
    unspanned = red["loop"]["unspanned_s"]
    return 1e3 * sum(unspanned) / len(unspanned)
