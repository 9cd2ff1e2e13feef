"""MoCo v2 over long token sequences, plain float32: `moco_seq.py`'s step
(Contriever's recipe: momentum encoder, queue of negatives, InfoNCE, two
independent crops of a document, AdamW; the momentum update and AdamW leaf by
leaf, in place) with the stack of `sparse_nets.py` as the encoder: a routed
layer under learned sparse attention.

What differs from `moco_seq.py`: the encoder; the gradient is accumulated
DOCUMENT BY DOCUMENT (a view is 8 192 tokens: one document's two forwards and
its backward pass are what fits beside 10 GB of float32 state); and beside a
share's router, the indexer's leaves are constants of the step (`trainable`):
the selection passes them no gradient and the loss that would train them is
left out, in the program and here alike. `precision` is `nets.Ops`'s, or
`fault_<name>` for a fault planted in the float32 reference
(`sparse_nets.FAULTS`); `rows` is `base.py`'s half batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import nets, sparse_nets
from perfbench.reference.base import Reference, cross_entropy
from perfbench.reference.moco_seq import MocoSeq


class MocoSparse(MocoSeq):
    def __init__(self, cfg, precision="float32", rows=None):
        self.fault = precision[len("fault_"):] if precision.startswith("fault_") else None
        if self.fault is not None and self.fault not in sparse_nets.FAULTS:
            raise ValueError(f"unknown fault {self.fault!r}; there are {sparse_nets.FAULTS}")
        self.z = sparse_nets.sizes_for(cfg)
        self.spec = sparse_nets.spec(self.z, cfg["embed_dim"])
        Reference.__init__(self, cfg, "float32" if self.fault else precision, rows)
        self.step = self._step                      # a sequence of programs, not one
        self._loss_and_grads = jax.jit(self._loss_and_grads, donate_argnums=(2,))

    def trainable(self, path: str) -> bool:
        return sparse_nets.INDEXER not in path and super().trainable(path)

    def embed(self, p, ids, picked_out=None):
        return nets.l2_normalize(
            sparse_nets.forward(self.ops, p, ids, self.z, self.fault, picked_out))

    def _loss_and_grads(self, q, k, queue, ptr, data_step, rows, lengths):
        """`MocoSeq`'s, a document at a time."""
        x1, x2 = self._views(rows, lengths, data_step)
        n = x1.shape[0]
        t = self.cfg["temperature"]

        def document_loss(q, ids_q, key):
            qs = self.embed(q, ids_q)
            pos = jnp.sum(qs * key, -1, keepdims=True)
            neg = self.ops.einsum("nc,kc->nk", qs, queue)
            logits = jnp.concatenate([pos, neg], 1) / t
            return cross_entropy(logits, jnp.zeros(1, jnp.int32)) / n

        def one(carry, ids):
            loss, grads = carry
            key = self.embed(k, ids[1])
            l, g = jax.value_and_grad(document_loss)(q, ids[0], key)
            return (loss + l, {p: grads[p] + g[p] for p in grads}), key

        zero = {p: jnp.zeros_like(v) for p, v in q.items()}
        (loss, grads), keys = jax.lax.scan(one, (jnp.zeros(()), zero), (x1[:, None], x2[:, None]))
        queue = jax.lax.dynamic_update_slice_in_dim(queue, keys.reshape(n, -1), ptr, 0)
        return loss, grads, queue, (ptr + n) % queue.shape[0]

    def picked_pairs(self, weights: dict, rows, lengths, data_step: int = 0):
        """Each layer's selection `[layers, B, L, L]` bool in the query forward
        of the step that `data_step` keys, from the initial weights, a document
        at a time."""
        x1, _ = self._views(jnp.asarray(rows), jnp.asarray(lengths), data_step)
        p = {name: jnp.asarray(v) for name, v in weights.items()}

        def one(ids):
            out: list = []
            self.embed(p, ids[None], out)
            return jnp.stack(out)[:, 0]

        return jnp.moveaxis(jax.lax.map(one, x1), 0, 1)


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoSparse(cfg, precision, rows)
