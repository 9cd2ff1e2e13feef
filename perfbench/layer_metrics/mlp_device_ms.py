"""Device milliseconds a traced step spends in events whose innermost nested
scope is `mlp` (the gate, up and down products and SwiGLU of every layer
application: `jax.named_scope` in `moco_tpu/models/ouro.py`; forward, recomputed
and transpose, key and query encoder, every pass; read by
`perfbench/looped_spans.py`)."""

from perfbench import looped_spans


def read(run):
    return looped_spans.scope_ms(run, "mlp")
