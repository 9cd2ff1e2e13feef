import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import time
import jax, jax.numpy as jnp, numpy as np
import flax.linen as nn
from moco_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

def timeit(fn, args, n=30, warm=8):
    for _ in range(warm): out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    t0=time.perf_counter()
    for _ in range(n): out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    return (time.perf_counter()-t0)/n*1e3

for (B,H,W,C) in [(128,56,56,64),(128,56,56,256)]:
    nbytes = B*H*W*C*2
    x = jnp.asarray(np.random.rand(B,H,W,C), jnp.bfloat16)
    bn = nn.BatchNorm(use_running_average=False, momentum=0.9, epsilon=1e-5,
                      dtype=jnp.bfloat16, param_dtype=jnp.float32)
    v = bn.init(jax.random.key(0), x)
    params, stats = v["params"], v["batch_stats"]

    @jax.jit
    def fwd(p, s, x):
        return bn.apply({"params":p,"batch_stats":s}, x, mutable=["batch_stats"])
    t = timeit(fwd, (params, stats, x))
    print(f"[{B},{H},{W},{C}] {nbytes/1e6:.0f}MB BN fwd: {t:.2f} ms ({(2*nbytes)/t/1e6:.0f} GB/s eff 1R1W)", flush=True)

    @jax.jit
    def statpass(x):
        xf = x.astype(jnp.float32)
        return jnp.sum(xf, axis=(0,1,2)), jnp.sum(xf*xf, axis=(0,1,2))
    t3 = timeit(statpass, (x,))
    print(f"   raw sum+sumsq: {t3:.2f} ms ({nbytes/t3/1e6:.0f} GB/s read)", flush=True)
