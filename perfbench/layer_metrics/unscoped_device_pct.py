"""Share of the step program's device time in events that carry no scope: the
check on the five. The compiler's own asynchronous copies (`copy-done`,
`slice-done`: no instruction of the program's, so no `op_name`) are not in it;
they are `async_copy_wait_ms`. `None` where no event carries a scope at all (a
program without scopes, or one loaded from a compile cache filled before they
existed)."""

from perfbench import program_spans


def read(run):
    red = program_spans.reduction(run)
    if red is None or not red["scoped_events"] or not red["total_ps"]:
        return None
    return 100.0 * program_spans.unscoped_ps(red) / red["total_ps"]
