"""MoCo v2 over token sequences, plain float32: Contriever's recipe
(arXiv:2112.09118: momentum encoder, queue of negatives, InfoNCE, two
independent crops of a document as the positive pair, AdamW) with the
`sdar_moe` stack of `seq_nets.py` as the encoder. See `base.py` for what a
reference is; this one is fed `int32` token rows and their lengths.

At the published widths the float32 state is 1.7 GB a copy (weights, momentum
encoder, gradient, two Adam moments), and the harness keeps the initial weights
besides, so a step is not one program here but a plain sequence of small ones,
so that it fits one chip after the program under test has gone:

  - the momentum update and AdamW run leaf by leaf, in place;
  - rows are independent (no BatchNorm; every negative is a queue row), so the
    loss is a mean of per-row terms and the gradient is accumulated over blocks
    of `BLOCK_ROWS` rows.

Neither changes a number. A share of an expert layer (fewer experts held than
the router has outputs) does not update its router: the router's gradient is
whole only with the other chips' experts, so its kernel is a constant of the
step (`trainable`), as in the program. `precision` is `nets.Ops`'s (`float32`, `bfloat16`,
`float8`: the control), or `fault_<name>` for a fault planted in the float32
reference (`seq_nets.FAULTS`: a causal mask for the block-causal one, half the
experts a token, the weights renormalised over the held experts only); `rows`
is `base.py`'s half batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import nets, seq_nets
from perfbench.reference.base import Reference, cross_entropy
from perfbench.reference.moco_v2 import MocoV2

BLOCK_ROWS = 4
B1, B2, EPS = 0.9, 0.999, 1e-8      # optax.adamw's defaults


@functools.partial(jax.jit, donate_argnums=(0,))
def _ema_leaf(k, q, m):
    return k * m + q * (1.0 - m)


@functools.partial(jax.jit, donate_argnums=(2, 3))
def _adamw_leaf(q, g, mu, nu, lr, count, wd):
    """`optax.adamw`: bias-corrected moments, decay decoupled from them."""
    mu = B1 * mu + (1 - B1) * g
    nu = B2 * nu + (1 - B2) * jnp.square(g)
    c = count.astype(jnp.float32)
    adam = (mu / (1 - B1 ** c)) / (jnp.sqrt(nu / (1 - B2 ** c)) + EPS)
    return q - lr * (adam + wd * q), mu, nu


class MocoSeq(Reference):
    views = ()

    def __init__(self, cfg, precision="float32", rows=None):
        self.fault = precision[len("fault_"):] if precision.startswith("fault_") else None
        if self.fault is not None and self.fault not in seq_nets.FAULTS:
            raise ValueError(f"unknown fault {self.fault!r}; there are {seq_nets.FAULTS}")
        self.z = seq_nets.sizes_for(cfg)
        self.spec = seq_nets.spec(self.z, cfg["embed_dim"])
        super().__init__(cfg, "float32" if self.fault else precision, rows)
        self.step = self._step                      # a sequence of programs, not one
        self._loss_and_grads = jax.jit(self._loss_and_grads, donate_argnums=(2,))

    # the v2 recipe's schedule and momentum
    ema_momentum = MocoV2.ema_momentum
    learning_rate = MocoV2.learning_rate

    def init_state(self, weights: dict, queue=None, data_step: int = 0) -> dict:
        state = super().init_state(weights, queue, data_step)
        state["k"] = {p: jnp.copy(v) for p, v in state["k"].items()}   # updated in place
        return state

    def trainable(self, path: str) -> bool:
        return self.z["held"] == self.z["experts"] or "/router/" not in path

    def init_opt(self, q):
        zeros = {p: jnp.zeros_like(v) for p, v in q.items() if self.trainable(p)}
        return {"mu": zeros, "nu": {p: jnp.zeros_like(v) for p, v in zeros.items()},
                "count": jnp.zeros((), jnp.int32)}

    def embed(self, p, ids, chosen_out=None):
        return nets.l2_normalize(
            seq_nets.forward(self.ops, p, ids, self.z, self.fault, chosen_out))

    def _views(self, rows, lengths, data_step):
        return seq_nets.token_views(
            rows, lengths, jax.random.key(self.data_key_seed), data_step,
            self.cfg["seq_len"], self.z["vocab"] - 1)

    def _loss_and_grads(self, q, k, queue, ptr, data_step, rows, lengths):
        """Both forwards, the loss and the query encoder's gradient, block of rows
        by block of rows; the keys enqueued."""
        x1, x2 = self._views(rows, lengths, data_step)
        n = x1.shape[0]
        block = BLOCK_ROWS if n % BLOCK_ROWS == 0 else n
        blocks = (x1.reshape(n // block, block, -1), x2.reshape(n // block, block, -1))
        t = self.cfg["temperature"]

        def block_loss(q, ids_q, keys):
            qs = self.embed(q, ids_q)
            pos = jnp.sum(qs * keys, -1, keepdims=True)
            neg = self.ops.einsum("nc,kc->nk", qs, queue)
            logits = jnp.concatenate([pos, neg], 1) / t
            return cross_entropy(logits, jnp.zeros(block, jnp.int32)) * block / n

        def one(carry, ids):
            loss, grads = carry
            keys = self.embed(k, ids[1])
            l, g = jax.value_and_grad(block_loss)(q, ids[0], keys)
            return (loss + l, {p: grads[p] + g[p] for p in grads}), keys

        zero = {p: jnp.zeros_like(v) for p, v in q.items()}
        (loss, grads), keys = jax.lax.scan(one, (jnp.zeros(()), zero), blocks)
        queue = jax.lax.dynamic_update_slice_in_dim(queue, keys.reshape(n, -1), ptr, 0)
        return loss, grads, queue, (ptr + n) % queue.shape[0]

    def _step(self, state, rows, lengths):
        if self.rows is not None:
            rows, lengths = rows[: self.rows], lengths[: self.rows]
        m = self.ema_momentum(state["step"])
        k = {p: _ema_leaf(v, state["q"][p], m) for p, v in state["k"].items()}
        loss, grads, queue, ptr = self._loss_and_grads(
            state["q"], k, state["queue"], state["ptr"], state["data_step"],
            jnp.asarray(rows), jnp.asarray(lengths))
        lr, opt = self.learning_rate(state["step"]), state["opt"]
        count = opt["count"] + 1
        q, mu, nu = dict(state["q"]), {}, {}
        for p in opt["mu"]:
            q[p], mu[p], nu[p] = _adamw_leaf(state["q"][p], grads[p], opt["mu"][p], opt["nu"][p],
                                             lr, count, self.cfg["weight_decay"])
        new = dict(state, q=q, k=k, queue=queue, ptr=ptr, step=state["step"] + 1,
                   data_step=state["data_step"] + 1, opt={"mu": mu, "nu": nu, "count": count})
        return new, loss, grads, {}

    def chosen_sets(self, weights: dict, rows, lengths, data_step: int = 0):
        """Each layer's chosen experts `[layers, B * L, top_k]` in the query
        forward of the step that `data_step` keys, from the initial weights."""
        x1, _ = self._views(jnp.asarray(rows), jnp.asarray(lengths), data_step)
        out: list = []
        self.embed({p: jnp.asarray(v) for p, v in weights.items()}, x1, out)
        return jnp.stack(out)


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoSeq(cfg, precision, rows)
