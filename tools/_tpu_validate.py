"""TPU validation + timing after s2d stem and FastBatchNorm.
1) fast_bn/pallas-stats numerics on TPU vs jnp
2) s2d stem on TPU matches plain conv
3) fused-step timing at B=128 and B=256
4) train a few steps: record the first losses (finite, reference-magnitude)
   alongside the timing sweep
"""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import time, sys
import jax, jax.numpy as jnp, numpy as np
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

print("backend:", jax.default_backend())

# --- 1) pallas stats vs jnp on TPU ---
from moco_tpu.ops.pallas_stats import channel_sums, channel_grad_sums
x = jax.random.normal(jax.random.key(0), (128*56*56, 64)).astype(jnp.bfloat16)
s, sq = channel_sums(x)
xf = np.asarray(x, np.float32)
np.testing.assert_allclose(np.asarray(s), xf.sum(0), rtol=2e-3, atol=2.0)
np.testing.assert_allclose(np.asarray(sq), (xf*xf).sum(0), rtol=2e-3, atol=2.0)
print("channel_sums OK")

def timeit(fn, args, n=30, warm=8):
    for _ in range(warm): out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    t0=time.perf_counter()
    for _ in range(n): out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    return (time.perf_counter()-t0)/n*1e3

nbytes = x.size*2
t = timeit(jax.jit(channel_sums), (x,))
print(f"pallas channel_sums [{x.shape}]: {t:.2f} ms = {nbytes/t/1e6:.0f} GB/s")
@jax.jit
def xla_sums(x):
    xf = x.astype(jnp.float32)
    return jnp.sum(xf, axis=0), jnp.sum(xf*xf, axis=0)
t2 = timeit(xla_sums, (x,))
print(f"xla    sums        [{x.shape}]: {t2:.2f} ms = {nbytes/t2/1e6:.0f} GB/s")

# --- 3) fused step timing (assembly + timing shared via benchkit with
#        bench.py's step mode and tools/_perf_ab.py — review, r5) ---
from moco_tpu.config import get_preset
from moco_tpu.parallel.mesh import create_mesh
from moco_tpu.utils.benchkit import build_v2_fused_bench, time_fused_step

for B in (128, 256):
    mesh = create_mesh(1)
    config = get_preset("imagenet-moco-v2").replace(batch_size=B, dataset="synthetic")
    fused, st, imgs, ext = build_v2_fused_bench(config, mesh)
    losses = []
    for i in range(3):
        st, m = fused(st, imgs, ext, i)
        losses.append(float(m["loss"]))
    best, _warm, _loss, st = time_fused_step(
        fused, st, imgs, ext, warmup=7, steps=20, rounds=2)
    print(f"B={B}: {best*1e3:.2f} ms/step -> {B/best:.1f} imgs/s  first losses {losses}")
