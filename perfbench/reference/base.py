"""What the plain float32 training steps share: one step from the two-crop
augmentation to the update (`moco_v2.py`: queue, SGD; `moco_v3.py`: symmetric
in-batch loss, AdamW). A configuration's file names its reference by the
module's name; a later configuration adds a module beside these.

A reference is built from the configuration's file alone. Its state is a
dict of flat `path -> array` dicts; `step` is one jitted function
`(state, images_u8, extents) -> (state, loss, gradient, seen)`, where the
gradient is what the optimizer is handed (before weight decay) and `seen` is
what the forward pass saw on its way (`bn_var`: the query encoder's batch
variance at every BatchNorm). BatchNorm running statistics are not kept:
training-mode BatchNorm never reads them. On one
chip ShuffleBN is a permutation inside the one BatchNorm batch and changes
nothing, so it is left out.

`rows` cuts every batch to its first rows: the fault "half of the batch left
out, the mean taken over the rest", planted here for the readings that the
limits are set against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import augment, nets


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


class Reference:
    """What the harness and the tests drive. `cfg` is the configuration file's
    `trainer` group plus `steps_per_epoch` and `seed` as the run sets them."""

    def __init__(self, cfg: dict, precision: str = "float32", rows: int | None = None):
        self.cfg = cfg
        self.ops = nets.Ops(precision)
        self.rows = rows
        self.data_key_seed = int(cfg["seed"]) + 1
        self.step = jax.jit(self._step)

    # -- what a configuration's reference fills in ---------------------------
    spec: list
    views: tuple

    def loss(self, q, k, state, x1, x2):
        raise NotImplementedError

    def ema_momentum(self, step):
        raise NotImplementedError

    def learning_rate(self, step):
        raise NotImplementedError

    def key_paths(self):
        """Leaves the momentum encoder holds."""
        return [s[0] for s in self.spec]

    def trainable(self, path: str) -> bool:
        return True

    def apply_update(self, state, grads, lr):
        raise NotImplementedError

    # -- shared --------------------------------------------------------------
    def init_state(self, weights: dict, queue=None, data_step: int = 0) -> dict:
        """`data_step` is the trainer's global step, which keys the
        augmentation's draws; it differs from `step` only where one process
        starts several runs of three steps (`calibrate.py`)."""
        q = dict(weights)
        state = {"q": q, "k": {p: q[p] for p in self.key_paths()},
                 "step": jnp.zeros((), jnp.int32), "opt": self.init_opt(q),
                 "data_step": jnp.asarray(data_step, jnp.int32)}
        if queue is not None:
            state["queue"], state["ptr"] = queue, jnp.zeros((), jnp.int32)
        return state

    def _step(self, state, imgs, extents):
        if self.rows is not None:
            imgs, extents = imgs[: self.rows], extents[: self.rows]
        x1, x2 = augment.two_crops(
            imgs, extents, jax.random.key(self.data_key_seed), state["data_step"], self.views,
            self.ops.a)
        return self.step_from_views(state, x1, x2)

    def step_from_views(self, state, x1, x2):
        """The step after the augmentation: momentum update of the key encoder,
        both forwards, the loss, the query encoder's gradient, the update."""
        m = self.ema_momentum(state["step"])
        k = {p: v * m + state["q"][p] * (1.0 - m) for p, v in state["k"].items()}
        (loss, aux), grads = jax.value_and_grad(
            lambda q: self.loss(q, k, state, x1, x2), has_aux=True)(state["q"])
        new = dict(state, k=k, step=state["step"] + 1, data_step=state["data_step"] + 1)
        new.update(self.apply_update(state, grads, self.learning_rate(state["step"])))
        seen = aux.pop("seen")
        new.update(aux)
        return new, loss, grads, seen
