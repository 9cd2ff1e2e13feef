"""Seconds of the program's `model_init` set-up span (the eager `model.init`
and the key encoder's copy), from the `setup` event the run writes once."""

from perfbench import program_spans


def read(run):
    setup = program_spans.events_of(run, "setup")
    return setup[-1].get("spans", {}).get("model_init") if setup else None
