"""Device milliseconds a traced step spends in events whose innermost nested
scope is `norm` (a looped encoder's four sandwich norms a layer, the closing
norm of every pass and the residual adds: `jax.named_scope` in
`moco_tpu/models/ouro.py`; forward, recomputed and transpose, key and query
encoder; read by `perfbench/looped_spans.py`). An event is a fusion, and a
fusion carries one instruction's name: where XLA fuses a norm's arithmetic into
the product before or after it, that time reads under `attn` or `mlp`, so this
is the norms' and adds' time that is left standing as events of their own, a
lower bound on what the norms cost."""

from perfbench import looped_spans


def read(run):
    return looped_spans.scope_ms(run, "norm")
