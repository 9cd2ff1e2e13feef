"""Device milliseconds a traced step spends in events whose innermost recognised
scope is `k_fwd` (`jax.named_scope` in the program's step builders; read from the
trace by `perfbench/program_spans.py`)."""

from perfbench import program_spans


def read(run):
    return program_spans.scope_ms(run, "k_fwd")
