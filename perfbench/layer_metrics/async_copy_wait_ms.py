"""Device milliseconds a traced step spends in the compiler's own asynchronous
copies (`copy-start` / `copy-done`, `slice-start` / `slice-done`: the wait for a
prefetch into faster memory). They carry no `op_name`, so no scope: a bucket of
their own beside the five scopes, which with the unscoped rest sum to the step
program's device time. `None` where no event carries a scope at all."""

from perfbench import program_spans


def read(run):
    red = program_spans.reduction(run)
    if red is None or not red["scoped_events"]:
        return None
    return program_spans.per_step_ms(red, red["async_copy_ps"], run["traced_steps"])
