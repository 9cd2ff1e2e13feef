"""MoCo v2 over long token sequences, plain float32: `moco_seq.py`'s step
(Contriever's recipe: momentum encoder, queue of negatives, InfoNCE, two
independent crops of a document, AdamW; the momentum update and AdamW leaf by
leaf, in place) with the stack of `mla_nets.py` as the encoder: latent
attention, a leading dense layer, then shared and routed experts.

What differs from `moco_seq.py`: the encoder; the gradient is accumulated
DOCUMENT BY DOCUMENT (`moco_sparse.py`'s loop: a view is 4 096 tokens); beside a
share's router, the router's correction bias is a constant of the step
(`trainable`: its gradient is nought, and the rule that moves it reads every
chip's load); and every product runs under `jax.default_matmul_precision
("highest")`, besides the `highest` each of `nets.Ops`' products asks for.
`precision` is `nets.Ops`'s, or `fault_<name>` for a fault planted in the
float32 reference (`mla_nets.FAULTS`); `rows` is `base.py`'s half batch.
"""

from __future__ import annotations

import jax

from perfbench.reference import mla_nets, nets
from perfbench.reference.base import Reference
from perfbench.reference.moco_seq import MocoSeq
from perfbench.reference.moco_sparse import MocoSparse


class MocoMla(MocoSparse):
    def __init__(self, cfg, precision="float32", rows=None):
        self.fault = precision[len("fault_"):] if precision.startswith("fault_") else None
        if self.fault is not None and self.fault not in mla_nets.FAULTS:
            raise ValueError(f"unknown fault {self.fault!r}; there are {mla_nets.FAULTS}")
        self.z = mla_nets.sizes_for(cfg)
        self.spec = mla_nets.spec(self.z, cfg["embed_dim"])
        Reference.__init__(self, cfg, "float32" if self.fault else precision, rows)
        self.step = self._step                      # a sequence of programs, not one
        self._loss_and_grads = jax.jit(self._loss_and_grads, donate_argnums=(2,))

    def trainable(self, path: str) -> bool:
        return "/correction_bias" not in path and MocoSeq.trainable(self, path)

    def embed(self, p, ids, chosen_out=None):
        with jax.default_matmul_precision("highest"):
            return nets.l2_normalize(
                mla_nets.forward(self.ops, p, ids, self.z, self.fault, chosen_out))


def build(cfg: dict, precision: str = "float32", rows: int | None = None) -> Reference:
    return MocoMla(cfg, precision, rows)
