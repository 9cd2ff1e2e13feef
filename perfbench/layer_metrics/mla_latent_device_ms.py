"""Device milliseconds a traced step spends in events whose innermost latent
scope is `mla_latent` (the latent's down-projection, its RMSNorm and
up-projection, and the rotary key: `jax.named_scope` in
`moco_tpu/models/kanana.py`, inside `attn`; forward and transpose, key and
query encoder; read by `perfbench/latent_spans.py`)."""

from perfbench import latent_spans


def read(run):
    return latent_spans.scope_ms(run, "mla_latent")
