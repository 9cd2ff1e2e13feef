"""Device milliseconds a traced step spends in events whose innermost nested
scope is `index` (a sparse-attention indexer's projections, LayerNorm, rotary
and scores: `jax.named_scope` in `moco_tpu/models/keye.py`; key and query
encoder, the rematerialised forward too; read by `perfbench/sparse_spans.py`)."""

from perfbench import sparse_spans


def read(run):
    return sparse_spans.scope_ms(run, "index")
