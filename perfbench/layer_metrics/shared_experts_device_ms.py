"""Device milliseconds a traced step spends in events whose innermost latent
scope is `moe_shared` (the shared experts' SwiGLU, beside the routed ones:
`jax.named_scope` in `moco_tpu/models/sdar.py::Experts`; forward and transpose,
key and query encoder; read by `perfbench/latent_spans.py`)."""

from perfbench import latent_spans


def read(run):
    return latent_spans.scope_ms(run, "moe_shared")
