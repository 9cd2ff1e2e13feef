"""Device milliseconds a traced step spends in events whose innermost nested
scope is `embed_pool` (the token embedding and its gradient's scatter; the final
norm, mean pool and head: `moco_tpu/models/sdar.py`; read by
`perfbench/nested_spans.py`)."""

from perfbench import nested_spans


def read(run):
    return nested_spans.scope_ms(run, "embed_pool")
