"""Device milliseconds a traced step spends in events whose innermost nested
scope is `attn` (`jax.named_scope` in `moco_tpu/models/sdar.py`; forward and
transpose, key and query encoder; read by `perfbench/nested_spans.py`)."""

from perfbench import nested_spans


def read(run):
    return nested_spans.scope_ms(run, "attn")
