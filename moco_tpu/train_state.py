"""The MoCo training state pytree (SURVEY §5.4 build spec).

Everything the reference keeps as module/optimizer state —
`encoder_q`/`encoder_k` parameters, BN running stats for both encoders, the
SGD momentum buffers, the negative queue + pointer (`state_dict` buffers in
the reference, `main_moco.py:≈L322-328`) — lives in ONE explicit, replicated
pytree. The train step is `state' = f(state, batch)` with the state donated,
so XLA updates params/queue in place in HBM. Checkpointing this pytree with
Orbax is bit-faithful resume (queue and pointer included), matching the
reference's torch.save of the full state_dict.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax

from moco_tpu.ops.queue import init_queue


@flax.struct.dataclass
class TrainState:
    step: jax.Array                 # int32 scalar, number of completed steps
    params_q: Any                   # query encoder params (trainable)
    params_k: Any                   # key encoder params (EMA of params_q)
    batch_stats_q: Any              # query-encoder BN running stats
    batch_stats_k: Any              # key-encoder BN running stats
    opt_state: Any                  # optax state over params_q only
    queue: jax.Array | None         # [K, dim] negative keys (None for v3)
    queue_ptr: jax.Array | None     # int32 ring pointer (None for v3)
    rng: jax.Array                  # replicated base PRNG key (model-side RNG)
    # gradient-sync accumulators (ISSUE 6; parallel/gradsync.py): `{}` for
    # the stateless modes (fused/bucketed — dialect-1-compatible on disk),
    # else {"acc": <params-shaped tree>} of PER-DEVICE leaves with a leading
    # [n_dev] axis sharded over the data mesh — the quantized mode's
    # error-feedback residual / the demo mode's local momentum. Carried in
    # the state so checkpoints resume compression exactly (dialect 2,
    # checkpoint.TRAIN_STATE_DIALECTS; ties the checkpoint to the mesh size
    # — restore falls back to fresh zeros on mismatch).
    gradsync: Any = dataclasses.field(default_factory=dict)


def no_span(name: str):
    """The default of `create_train_state(span=...)`: no tracer at hand."""
    return contextlib.nullcontext()


def compiled_init(init_fields, tx: optax.GradientTransformation, span=no_span) -> TrainState:
    """The initial state as the output of compiled programs, not of one small
    program a primitive (an eager `model.init` of ResNet-50 is 213 of them,
    compiled again in every run: ISSUE 26). `init_fields()` is the jitted
    initialiser bound to its arguments; it returns every `TrainState` field
    but `opt_state`. `tx.init` is a second small program so that `opt_init`
    stays a span of its own. Each span waits for its outputs (dispatch is
    asynchronous), so it holds trace, compile and run. Nothing is placed or
    committed here."""
    with span("model_init"):
        fields = jax.block_until_ready(init_fields())
    with span("opt_init"):
        opt_state = jax.block_until_ready(jax.jit(tx.init)(fields["params_q"]))
    return TrainState(opt_state=opt_state, **fields)


# everything but the key is static: the model (a flax module hashes by its
# fields) and the shapes are closed over by the program, and a second call
# with an equal model finds it in jit's own cache. Under `jax.jit` the dummy
# forward of `model.init` is dead code: the program is the random draws and
# the q → k copies.
@functools.partial(jax.jit, static_argnames=("model", "input_shape", "input_dtype",
                                             "num_negatives", "embed_dim", "queue_dtype"))
def _init_fields(rng, *, model, input_shape, input_dtype, num_negatives, embed_dim,
                 queue_dtype):
    init_key, queue_key, state_key = jax.random.split(rng, 3)
    variables = model.init(init_key, jnp.zeros(input_shape, input_dtype), train=False)
    params_q = variables["params"]
    batch_stats_q = variables.get("batch_stats", {})
    if num_negatives is not None:
        queue, queue_ptr = init_queue(queue_key, num_negatives, embed_dim, queue_dtype)
    else:
        queue, queue_ptr = None, None
    return dict(
        step=jnp.zeros((), jnp.int32),
        params_q=params_q,
        params_k=jax.tree.map(jnp.copy, params_q),
        batch_stats_q=batch_stats_q,
        batch_stats_k=jax.tree.map(jnp.copy, batch_stats_q),
        queue=queue,
        queue_ptr=queue_ptr,
        rng=state_key,
    )


def create_train_state(
    rng: jax.Array,
    model,
    tx: optax.GradientTransformation,
    input_shape: tuple[int, ...],
    num_negatives: int | None,
    embed_dim: int,
    queue_dtype=jnp.float32,
    span=no_span,
    input_dtype=jnp.float32,
) -> TrainState:
    """Initialise q, copy q → k (the reference's param copy,
    `moco/builder.py:≈L20-24` — k starts identical to q), build queue.

    `input_shape` is a per-device-shaped dummy `[local_b, H, W, C]` (a token
    encoder's: `[local_b, L]` of `input_dtype` int32); init is shape-driven
    only. `span(name)` opens the driver's set-up span of that
    name (`model_init`, `opt_init`: ISSUE 25); each times a compiled call
    that is waited for (`compiled_init`).
    """
    init_fields = functools.partial(
        _init_fields, rng, model=model, input_shape=tuple(input_shape),
        input_dtype=jnp.dtype(input_dtype), num_negatives=num_negatives,
        embed_dim=embed_dim, queue_dtype=queue_dtype)
    return compiled_init(init_fields, tx, span)
