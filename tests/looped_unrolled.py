"""The looped encoder's loop written out, for the tests of `models/ouro.py`:
drop-in replacements for `ouro.looped(Pass, steps)` that apply the program's
own `Layer` and `RMSNorm` in a Python loop over passes and layers (no scan).

`unrolled(steps)` shares each layer's parameters between the passes, under the
names the scan gives them (`layer_<i>`, `norm`), and can plant a fault:
`pass_short` (one pass fewer), `norm_once` (the closing norm after the last pass
only), `last_pass_grad` (`stop_gradient` on the last pass's input, so the shared
weights miss the cotangents of their earlier uses). `written_out(steps)` gives
every (pass, layer) its OWN parameters (`pass_<t>_layer_<i>`, `pass_<t>_norm`):
the stack `steps x L` deep, with which the shared kernel's gradient is the sum
over its copies.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from moco_tpu.models import ouro
from moco_tpu.models.sdar import RMSNorm


def unrolled(fault=None):
    def looped(body, steps):
        class Unrolled(nn.Module):
            sizes: Any
            layers: int
            remat: bool = False
            dtype: Any = jnp.float32

            @nn.compact
            def __call__(self, h, _):
                layers = [ouro.Layer(self.sizes, self.dtype, name=f"layer_{i}")
                          for i in range(self.layers)]
                norm = RMSNorm(dict(self.sizes)["eps"], name="norm")
                n = steps - (fault == "pass_short")
                for t in range(n):
                    if fault == "last_pass_grad" and t == n - 1:
                        h = jax.lax.stop_gradient(h)
                    for layer in layers:
                        h = layer(h)
                    if fault != "norm_once" or t == n - 1:
                        h = norm(h).astype(self.dtype)
                return h, None

        return Unrolled

    return looped


def written_out(body, steps):
    class WrittenOut(nn.Module):
        sizes: Any
        layers: int
        remat: bool = False
        dtype: Any = jnp.float32

        @nn.compact
        def __call__(self, h, _):
            for t in range(steps):
                for i in range(self.layers):
                    h = ouro.Layer(self.sizes, self.dtype, name=f"pass_{t}_layer_{i}")(h)
                h = RMSNorm(dict(self.sizes)["eps"], name=f"pass_{t}_norm")(h).astype(self.dtype)
            return h, None

    return WrittenOut
