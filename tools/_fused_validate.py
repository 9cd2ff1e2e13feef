"""On-chip validation + A/B timing for the r3 perf levers.

1) bn_relu_matmul numerics on TPU vs the plain jnp math (bf16 tolerance)
2) Bottleneck fused-tail fwd+bwd vs unfused on TPU
3) fused MoCo-v2 R50 step timing A/B: {fused_bn_conv on/off} x {remat on/off}

Usage: python tools/_fused_validate.py [batch]
"""
import os as _os, sys as _sys, time

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

print("backend:", jax.default_backend(), flush=True)

# --- 1) kernel numerics ---
from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul

m, k, n = 2048, 64, 256
x = jax.random.normal(jax.random.key(0), (m, k)).astype(jnp.bfloat16)
a = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (k,))
b = 0.1 * jax.random.normal(jax.random.key(2), (k,))
w = (0.05 * jax.random.normal(jax.random.key(3), (k, n))).astype(jnp.bfloat16)
got = np.asarray(bn_relu_matmul(x, a, b, w, out_dtype=jnp.bfloat16), np.float32)
z = np.maximum(np.asarray(x, np.float32) * np.asarray(a) + np.asarray(b), 0)
want = z.astype(np.float32) @ np.asarray(w, np.float32)
err = np.abs(got - want) / (np.abs(want) + 1.0)
print(f"kernel rel err: mean {err.mean():.2e} max {err.max():.2e}")
assert err.max() < 0.05, "fused kernel numerics off on TPU"

# --- 1b) dW backward kernel numerics ---
from moco_tpu.ops.pallas_fused_conv import bn_relu_matmul_dw

dy = jax.random.normal(jax.random.key(4), (m, n)).astype(jnp.bfloat16)
dw_got = np.asarray(bn_relu_matmul_dw(x, a, b, dy), np.float32)
# apples-to-apples reference (first-chip finding, r5): the kernel — like
# the UNFUSED bf16 path — quantizes ẑ to bf16 before the MXU contraction
# (f32 accumulate). Comparing against an f32-ẑ product instead conflates
# that inherent input quantization with kernel error, and over an M=2048
# contraction the accumulated bf16 rounding alone reaches ~0.14 on
# near-zero entries (builder-measured on the v5e, 2026-07-31).
# So: gate hard against the bf16-ẑ f32-accumulate product; report the
# f32-ẑ delta for context only.
zb = np.asarray(jnp.asarray(z).astype(jnp.bfloat16), np.float32)
dw_want = zb.T @ np.asarray(dy, np.float32)
dw_f32 = z.astype(np.float32).T @ np.asarray(dy, np.float32)
dw_err = np.abs(dw_got - dw_want) / (np.abs(dw_want) + 1.0)
dw_info = np.abs(dw_got - dw_f32) / (np.abs(dw_f32) + 1.0)
print(f"dW kernel rel err vs bf16-z ref: mean {dw_err.mean():.2e} "
      f"max {dw_err.max():.2e} (vs f32-z ref, info only: "
      f"mean {dw_info.mean():.2e} max {dw_info.max():.2e})")
assert dw_err.max() < 0.05, "dW kernel numerics off on TPU"

# --- 1c) 3x3 kernels: forward + dW backward numerics ---
from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3, conv3x3_dw

bsz3, h3, w3, k3, n3 = 8, 28, 28, 128, 128
x3 = jax.random.normal(jax.random.key(20), (bsz3, h3, w3, k3)).astype(jnp.bfloat16)
a3 = 1.0 + 0.1 * jax.random.normal(jax.random.key(21), (k3,))
b3 = 0.1 * jax.random.normal(jax.random.key(22), (k3,))
w3x3 = (0.05 * jax.random.normal(jax.random.key(23), (3, 3, k3, n3))).astype(jnp.bfloat16)
dy3 = jax.random.normal(jax.random.key(24), (bsz3, h3, w3, n3)).astype(jnp.bfloat16)


def _ref3(x_, w_):
    z_ = jnp.maximum(x_.astype(jnp.float32) * a3 + b3, 0.0)
    return jax.lax.conv_general_dilated(
        z_, w_.astype(jnp.float32), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


got3 = np.asarray(bn_relu_conv3x3(x3, a3, b3, w3x3, out_dtype=jnp.bfloat16), np.float32)
want3 = np.asarray(_ref3(x3, w3x3), np.float32)
err3 = np.abs(got3 - want3) / (np.abs(want3) + 1.0)
print(f"conv3x3 kernel rel err: mean {err3.mean():.2e} max {err3.max():.2e}")
assert err3.max() < 0.05, "fused 3x3 kernel numerics off on TPU"

# bf16-ẑ reference, same reasoning as 1b: the kernel quantizes the
# recomputed ẑ to dy's dtype before each tap contraction
def _ref3q(x_, w_):
    z_ = jnp.maximum(x_.astype(jnp.float32) * a3 + b3, 0.0)
    z_ = z_.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.conv_general_dilated(
        z_, w_.astype(jnp.float32), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


_, _vjp3 = jax.vjp(lambda w_: _ref3q(x3, w_), w3x3.astype(jnp.float32))
(dw3_want,) = _vjp3(jnp.asarray(dy3, jnp.float32))
dw3_got = np.asarray(conv3x3_dw(x3, a3, b3, dy3), np.float32)
dw3_err = np.abs(dw3_got - np.asarray(dw3_want)) / (np.abs(np.asarray(dw3_want)) + 1.0)
print(f"conv3x3 dW kernel rel err: mean {dw3_err.mean():.2e} max {dw3_err.max():.2e}")
assert dw3_err.max() < 0.05, "3x3 dW kernel numerics off on TPU"

# --- 1d) stride-2 forward kernel numerics ---
from moco_tpu.ops.pallas_fused_conv3x3 import bn_relu_conv3x3_s2

gots2 = np.asarray(
    bn_relu_conv3x3_s2(x3, a3, b3, w3x3, out_dtype=jnp.bfloat16), np.float32)
wants2 = np.asarray(jax.lax.conv_general_dilated(
    jnp.maximum(x3.astype(jnp.float32) * a3 + b3, 0.0),
    w3x3.astype(jnp.float32), (2, 2), ((1, 1), (1, 1)),
    dimension_numbers=("NHWC", "HWIO", "NHWC")), np.float32)
errs2 = np.abs(gots2 - wants2) / (np.abs(wants2) + 1.0)
print(f"conv3x3 s2 kernel rel err: mean {errs2.mean():.2e} max {errs2.max():.2e}")
assert errs2.max() < 0.05, "stride-2 fused kernel numerics off on TPU"

# --- 2) block equivalence on TPU ---
from functools import partial
import flax.linen as nn
from moco_tpu.models.resnet import Bottleneck

conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32)
norm = partial(nn.BatchNorm, use_running_average=False, momentum=0.9,
               epsilon=1e-5, dtype=jnp.bfloat16, param_dtype=jnp.float32)
kw = dict(filters=64, strides=1, conv=conv, norm=norm)
plain = Bottleneck(**kw)
fused = Bottleneck(fused_tail=True, bn_momentum=0.9, dtype=jnp.bfloat16, **kw)
xb = jax.random.normal(jax.random.key(4), (8, 28, 28, 256), jnp.float32)
v = plain.init(jax.random.key(5), xb)


def loss(params, model):
    out, _ = model.apply({"params": params, "batch_stats": v["batch_stats"]},
                         xb, mutable=["batch_stats"])
    return jnp.sum((out.astype(jnp.float32)) ** 2)


la, ga = jax.jit(jax.value_and_grad(lambda p: loss(p, plain)))(v["params"])
lb, gb = jax.jit(jax.value_and_grad(lambda p: loss(p, fused)))(v["params"])
print(f"block loss plain {float(la):.4f} fused {float(lb):.4f}")
for pa, pb in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
    d = np.abs(np.asarray(pa, np.float32) - np.asarray(pb, np.float32))
    s = np.abs(np.asarray(pa, np.float32)).max() + 1e-6
    assert d.max() / s < 0.05, f"grad mismatch {d.max() / s}"
print("block fwd/bwd equivalence OK (bf16 tolerance)")

# --- 3) step timing A/B ---
from moco_tpu.config import get_preset
from moco_tpu.data.augment import build_two_crops_sharded, v2_aug_config, with_dtype
from moco_tpu.data.datasets import full_extents
from moco_tpu.parallel.mesh import create_mesh
from moco_tpu.train_state import create_train_state
from moco_tpu.train_step import (
    build_encoder, build_fused_step, build_optimizer, build_train_step,
)

B = int(_sys.argv[1]) if len(_sys.argv) > 1 else 128
mesh = create_mesh(1)
rng = np.random.RandomState(0)
stage = 252
imgs = jnp.asarray(rng.randint(0, 256, (B, stage, stage, 3), dtype=np.uint8))
ext = full_extents(B, stage, stage)


def time_step(fused_flag, remat_flag):
    cfg = get_preset("imagenet-moco-v2").replace(
        batch_size=B, fused_bn_conv=fused_flag, remat=remat_flag
    )
    model = build_encoder(cfg)
    tx, sched = build_optimizer(cfg, 1000)
    state = create_train_state(jax.random.key(0), model, tx, (B, 224, 224, 3),
                               cfg.num_negatives, cfg.embed_dim)
    step = build_train_step(cfg, model, tx, mesh, 1000, sched)
    two = build_two_crops_sharded(with_dtype(v2_aug_config(224), "bfloat16"), mesh)
    fstep = build_fused_step(step, two, jax.random.key(1))
    for i in range(8):
        state, mtr = fstep(state, imgs, ext, i)
    float(mtr["loss"])  # d2h sync
    best = 1e9
    for r in range(2):
        t0 = time.perf_counter()
        for i in range(20):
            state, mtr = fstep(state, imgs, ext, 100 * r + i)
        float(mtr["loss"])
        best = min(best, (time.perf_counter() - t0) / 20)
    return best


for fused_flag, remat_flag in [(False, False), (True, False), (True, True), (False, True)]:
    dt = time_step(fused_flag, remat_flag)
    print(
        f"fused={fused_flag} remat={remat_flag}: {dt * 1e3:.2f} ms/step "
        f"-> {B / dt:.1f} imgs/s/chip",
        flush=True,
    )
