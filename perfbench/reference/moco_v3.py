"""MoCo v3 training step, plain float32: see `base.py`."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference import augment, nets
from perfbench.reference.base import Reference, cross_entropy as _cross_entropy  # noqa: F401


class MocoV3(Reference):
    """Chen et al. 2021: both views through both encoders, predictor on the
    query side, AdamW, momentum on a cosine ramp, frozen patch projection."""

    def __init__(self, cfg, precision="float32", rows=None):
        self.arch = cfg["arch"]
        width = nets.VIT_SIZES[self.arch][0]
        self.spec = nets.vit_spec(self.arch) + nets.v3_head_spec(width, out=cfg["embed_dim"])
        base = augment.view(out_size=cfg["image_size"], min_scale=cfg.get("crop_min") or 0.08,
                            saturation=0.2)
        self.views = (dict(base, blur_prob=1.0), dict(base, blur_prob=0.1, solarize_prob=0.2))
        super().__init__(cfg, precision, rows)

    def key_paths(self):
        return [s[0] for s in self.spec if not s[0].startswith("predictor/")]

    def trainable(self, path):
        return "/patch_embed/" not in path

    def init_opt(self, q):
        zeros = {p: jnp.zeros_like(v) for p, v in q.items() if self.trainable(p)}
        return {"mu": zeros, "nu": dict(zeros), "count": jnp.zeros((), jnp.int32)}

    def ema_momentum(self, step):
        total = self.cfg["epochs"] * self.cfg["steps_per_epoch"]
        frac = step.astype(jnp.float32) / total
        return 1.0 - (1.0 - self.cfg["momentum_ema"]) * 0.5 * (1 + jnp.cos(math.pi * frac))

    def learning_rate(self, step):
        lr = self.cfg["base_lr"] * self.cfg["batch_size"] / 256.0
        epoch = step.astype(jnp.float32) / self.cfg["steps_per_epoch"]
        warm, total = self.cfg["warmup_epochs"], self.cfg["epochs"]
        cos = lr * 0.5 * (1 + jnp.cos(math.pi * (epoch - warm) / max(total - warm, 1e-8)))
        return jnp.where(epoch < warm, lr * epoch / max(warm, 1e-8), cos)

    def _embed(self, p, x, predict):
        z = nets.v3_project(self.ops, p, nets.vit_forward(self.ops, p, x, self.arch))
        if predict:
            z = nets.v3_predict(self.ops, p, z)
        return nets.l2_normalize(z)

    def loss(self, q, k, state, x1, x2):
        t = self.cfg["temperature"]
        k1 = jax.lax.stop_gradient(self._embed(k, x1, False))
        k2 = jax.lax.stop_gradient(self._embed(k, x2, False))
        q1, q2 = self._embed(q, x1, True), self._embed(q, x2, True)
        labels = jnp.arange(q1.shape[0], dtype=jnp.int32)

        def ctr(a, b):
            return _cross_entropy(self.ops.einsum("nc,mc->nm", a, b) / t, labels) * 2 * t

        return ctr(q1, k2) + ctr(q2, k1), {"seen": {}}

    def apply_update(self, state, grads, lr):
        b1, b2, eps, wd = 0.9, 0.999, 1e-8, self.cfg["weight_decay"]
        opt = state["opt"]
        count = opt["count"] + 1
        c = count.astype(jnp.float32)
        mu = {p: b1 * m + (1 - b1) * grads[p] for p, m in opt["mu"].items()}
        nu = {p: b2 * n + (1 - b2) * jnp.square(grads[p]) for p, n in opt["nu"].items()}
        q = dict(state["q"])
        for p in mu:
            adam = (mu[p] / (1 - b1 ** c)) / (jnp.sqrt(nu[p] / (1 - b2 ** c)) + eps)
            q[p] = q[p] - lr * (adam + wd * q[p])
        return {"q": q, "opt": {"mu": mu, "nu": nu, "count": count}}


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoV3(cfg, precision, rows)
