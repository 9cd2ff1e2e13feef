"""MoCo v2 over token sequences with a looped, weight-shared stack as the
encoder, plain float32: `moco_seq.py`'s step (Contriever's recipe: momentum
encoder, queue of negatives, InfoNCE, two independent crops of a document as
the positive pair, AdamW; the momentum update and AdamW leaf by leaf, the
gradient accumulated over blocks of rows, so that it fits one chip after the
program under test has gone) around `looped_nets.py`'s forward pass.

The loop is a Python loop here, in the backward pass too: a step is a
sequence of small programs (the embedding, ONE pass, the head and the loss,
the transpose of one pass), each compiled once and called pass by pass from
Python, so the cotangents of the `ut_steps` uses of every shared weight are
added up by hand, leaf by leaf, and nothing of a scan or of its transpose is
leaned on. (One program of all `ut_steps x layers` float32 layer applications
and their transposes compiles for four minutes and in 17 GiB of host memory:
my chip run, PR 31.) A pass's transpose computes the pass again from the state
it started from, which is kept; that changes no number. Every parameter trains.
`precision` is `nets.Ops`'s (`float32`, `bfloat16`, `float8`: the control), or
`fault_<name>` for a fault planted in the float32 reference
(`looped_nets.FAULTS`: one pass fewer, the closing norm after the last pass
only, the gradient of the last pass alone); `rows` is `base.py`'s half batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import looped_nets, moco_seq, nets
from perfbench.reference.base import Reference, cross_entropy

BLOCK_ROWS = 2


def _part(tree: dict, prefix: str) -> dict:
    return {p: v for p, v in tree.items() if p.startswith(prefix)}


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


class MocoLooped(moco_seq.MocoSeq):
    def __init__(self, cfg, precision="float32", rows=None):
        self.fault = precision[len("fault_"):] if precision.startswith("fault_") else None
        if self.fault is not None and self.fault not in looped_nets.FAULTS:
            raise ValueError(f"unknown fault {self.fault!r}; there are {looped_nets.FAULTS}")
        self.z = z = looped_nets.sizes_for(cfg)
        self.spec = looped_nets.spec(z, cfg["embed_dim"])
        Reference.__init__(self, cfg, "float32" if self.fault else precision, rows)
        self.step = self._step                      # a sequence of programs, not one
        ops = self.ops
        self._views = jax.jit(self._views)
        self._embed = jax.jit(lambda table, ids: looped_nets.embed(ops, {"embed/embedding": table}, ids))
        self._embed_t = jax.jit(lambda acc, ids, g: acc.at[ids].add(g), donate_argnums=(0,))
        self._pass = jax.jit(lambda p, h, closing: looped_nets.one_pass(ops, p, h, z, closing),
                             static_argnums=(2,))
        self._pass_t = jax.jit(
            lambda p, h, g, closing: jax.vjp(
                lambda p, h: looped_nets.one_pass(ops, p, h, z, closing), p, h)[1](g),
            static_argnums=(3,))
        self._keys = jax.jit(lambda p, h: nets.l2_normalize(looped_nets.head(p, h)))
        self._head_loss = jax.jit(jax.value_and_grad(self._loss_of_state, argnums=(0, 1)),
                                  static_argnums=(4,))

    def trainable(self, path: str) -> bool:
        return True

    def embed(self, p, ids):
        return nets.l2_normalize(looped_nets.forward(self.ops, p, ids, self.z, self.fault))

    def _loss_of_state(self, p, h, keys, queue, share):
        """A block's part of the batch's mean loss from the query encoder's last
        state: the head, InfoNCE against the block's keys and the queue."""
        qs = self._keys(p, h)
        pos = jnp.sum(qs * keys, -1, keepdims=True)
        neg = self.ops.einsum("nc,kc->nk", qs, queue)
        logits = jnp.concatenate([pos, neg], 1) / self.cfg["temperature"]
        return cross_entropy(logits, jnp.zeros(h.shape[0], jnp.int32)) * share

    def _encode(self, p, ids):
        """The state each pass started from, and the last pass's output."""
        plan = looped_nets.plan(self.z, self.fault)
        loop, states = _part(p, "loop/"), [self._embed(p["embed/embedding"], ids)]
        for closing, _ in plan:
            states.append(self._pass(loop, states[-1], closing))
        return plan, loop, states

    def _loss_and_grads(self, q, k, queue, ptr, data_step, rows, lengths):
        """Both forwards, the loss and the query encoder's gradient, block of rows
        by block, pass by pass; the keys enqueued."""
        x1, x2 = self._views(rows, lengths, data_step)
        n = x1.shape[0]
        block = BLOCK_ROWS if n % BLOCK_ROWS == 0 else n
        # one accumulator a leaf, added to in place: beside the state (weights, momentum
        # copy, two moments, the harness's copy of the seed's weights) there is room
        # for it, one pass's cotangents and a block's activations, and little more
        acc = {p: jnp.zeros_like(v) for p, v in q.items()}
        acc_loop, acc_head = _part(acc, "loop/"), _part(acc, "fc")
        acc_embed, loss, keys_all = acc["embed/embedding"], 0.0, []
        del acc
        for at in range(0, n, block):
            ids_q, ids_k = x1[at: at + block], x2[at: at + block]
            keys = self._keys(_part(k, "fc"), self._encode(k, ids_k)[2][-1])
            plan, loop, states = self._encode(q, ids_q)
            part, (g_head, g) = self._head_loss(_part(q, "fc"), states[-1], keys, queue, block / n)
            acc_head = _add(acc_head, g_head)
            for (closing, cut), h in zip(plan[::-1], states[-2::-1]):
                g_pass, g = self._pass_t(loop, h, g, closing)    # this use's cotangents of the shared weights
                acc_loop = _add(acc_loop, g_pass)
                del g_pass
                if cut:         # the planted fault: nothing reaches the earlier passes
                    g = jnp.zeros_like(g)
                    break
            acc_embed = self._embed_t(acc_embed, ids_q, g)
            loss, keys_all = loss + part, keys_all + [keys]
        grads = {**acc_loop, **acc_head, "embed/embedding": acc_embed}
        queue = jax.lax.dynamic_update_slice_in_dim(queue, jnp.concatenate(keys_all), ptr, 0)
        return loss, {p: grads[p] for p in q}, queue, (ptr + n) % queue.shape[0]

    def pass_delta(self, weights: dict, rows, lengths, data_step: int = 0):
        """The query forward's last pass: mean over tokens of `|h_T - h_(T-1)| /
        |h_(T-1)|`, from the initial weights (the program's `ut_pass_delta`)."""
        x1, _ = self._views(jnp.asarray(rows), jnp.asarray(lengths), data_step)
        *_, before, after = looped_nets.passes_of(
            self.ops, {p: jnp.asarray(v) for p, v in weights.items()}, x1, self.z, self.fault)
        return jnp.mean(jnp.linalg.norm(after - before, axis=-1) / jnp.linalg.norm(before, axis=-1))


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoLooped(cfg, precision, rows)
