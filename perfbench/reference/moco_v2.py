"""MoCo v2 training step, plain float32: see `base.py`."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference import augment, nets
from perfbench.reference.base import Reference, cross_entropy as _cross_entropy  # noqa: F401


class MocoV2(Reference):
    """He et al. 2019 / Chen et al. 2020: InfoNCE against a FIFO queue."""

    def __init__(self, cfg, precision="float32", rows=None):
        self.arch = cfg["arch"]
        self.stem = 3 if cfg.get("cifar_stem") else 7
        self.spec = nets.resnet_spec(self.arch, cfg["embed_dim"], stem=self.stem)
        v = augment.view(out_size=cfg["image_size"])
        self.views = (v, v)
        super().__init__(cfg, precision, rows)

    def init_opt(self, q):
        return {"trace": {p: jnp.zeros_like(v) for p, v in q.items()}}

    def ema_momentum(self, step):
        return self.cfg["momentum_ema"]

    def learning_rate(self, step):
        epoch = jnp.floor(step.astype(jnp.float32) / self.cfg["steps_per_epoch"])
        return self.cfg["lr"] * 0.5 * (1 + jnp.cos(math.pi * epoch / self.cfg["epochs"]))

    def loss(self, q, k, state, x1, x2):
        t = self.cfg["temperature"]
        keys = jax.lax.stop_gradient(nets.l2_normalize(
            nets.resnet_forward(self.ops, k, x2, self.arch, self.stem)))
        bn_var = {}
        qs = nets.l2_normalize(
            nets.resnet_forward(self.ops, q, x1, self.arch, self.stem, seen=bn_var))
        pos = jnp.sum(qs * keys, -1, keepdims=True)
        neg = self.ops.einsum("nc,kc->nk", qs, state["queue"])
        logits = jnp.concatenate([pos, neg], 1) / t
        loss = _cross_entropy(logits, jnp.zeros(qs.shape[0], jnp.int32))
        queue = jax.lax.dynamic_update_slice_in_dim(state["queue"], keys, state["ptr"], 0)
        ptr = (state["ptr"] + keys.shape[0]) % queue.shape[0]
        return loss, {"queue": queue, "ptr": ptr, "seen": {"bn_var": bn_var}}

    def apply_update(self, state, grads, lr):
        wd, mom = self.cfg["weight_decay"], self.cfg["sgd_momentum"]
        trace = {p: grads[p] + wd * state["q"][p] + mom * state["opt"]["trace"][p]
                 for p in grads}
        return {"q": {p: state["q"][p] - lr * trace[p] for p in grads},
                "opt": {"trace": trace}}


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoV2(cfg, precision, rows)
