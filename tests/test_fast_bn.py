"""FastBatchNorm (Pallas-stat BN) equivalence with `nn.BatchNorm`, and the
streaming reduction kernels in interpret mode (SURVEY §2.10: the cuDNN
fused-BN equivalent must be provably identical to the graph-level math)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.models.fast_bn import FastBatchNorm
from moco_tpu.ops.pallas_stats import channel_grad_sums, channel_sums
from jax import shard_map


def _pair(dtype):
    flax_bn = nn.BatchNorm(
        use_running_average=False, momentum=0.9, epsilon=1e-5,
        dtype=dtype, param_dtype=jnp.float32,
    )
    fast_bn = FastBatchNorm(
        use_running_average=False, momentum=0.9, epsilon=1e-5,
        dtype=dtype, param_dtype=jnp.float32,
    )
    return flax_bn, fast_bn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fast_bn_train_matches_flax(dtype):
    """Off-TPU the jnp path mirrors flax's op order exactly: forward output,
    running-stat updates, and (via the custom VJP's closed form) gradients."""
    flax_bn, fast_bn = _pair(dtype)
    x = jax.random.normal(jax.random.key(0), (8, 6, 6, 16)) * 2.0 + 1.0
    v1 = flax_bn.init(jax.random.key(1), x)
    v2 = fast_bn.init(jax.random.key(1), x)
    assert jax.tree.structure(v1) == jax.tree.structure(v2)
    # shared weights so outputs are comparable
    variables = {"params": v1["params"], "batch_stats": v1["batch_stats"]}

    ya, muta = flax_bn.apply(variables, x, mutable=["batch_stats"])
    yb, mutb = fast_bn.apply(variables, x, mutable=["batch_stats"])
    # off-TPU the fast module IS flax's graph — bit-identical in both dtypes
    np.testing.assert_array_equal(np.asarray(ya, np.float32), np.asarray(yb, np.float32))
    for a, b in zip(
        jax.tree.leaves(muta["batch_stats"]), jax.tree.leaves(mutb["batch_stats"]),
        strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def loss(bn):
        def f(params, x):
            y, _ = bn.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, mutable=["batch_stats"],
            )
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return f

    ga, gxa = jax.grad(loss(flax_bn), argnums=(0, 1))(variables["params"], x)
    gb, gxb = jax.grad(loss(fast_bn), argnums=(0, 1))(variables["params"], x)
    # grads agree to ~1 ulp (autodiff reassociates one mul differently vs
    # flax's in-place `mul *=` graph); the forward is bit-exact and the
    # training-trajectory pin is test_golden.py, which must stay unchanged
    np.testing.assert_allclose(
        np.asarray(gxa, np.float32), np.asarray(gxb, np.float32),
        rtol=3e-6, atol=5e-7,
    )
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-6, atol=5e-6)


def test_fast_bn_eval_matches_flax():
    flax_bn = nn.BatchNorm(use_running_average=True, epsilon=1e-5)
    fast_bn = FastBatchNorm(use_running_average=True, epsilon=1e-5)
    x = jax.random.normal(jax.random.key(2), (4, 5, 5, 8))
    v = flax_bn.init(jax.random.key(3), x)
    v["batch_stats"]["mean"] = jnp.linspace(-1, 1, 8)
    v["batch_stats"]["var"] = jnp.linspace(0.5, 2, 8)
    ya = flax_bn.apply(v, x)
    yb = fast_bn.apply(v, x)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))


def test_fast_bn_sync_axis(mesh8):
    """SyncBN path: cross-device pmean statistics inside shard_map equal
    global-batch statistics."""
    from jax.sharding import PartitionSpec as P

    bn = FastBatchNorm(use_running_average=False, axis_name="data")
    x = jax.random.normal(jax.random.key(4), (16, 4, 4, 8))
    v = bn.init(jax.random.key(5), x[:2])

    def body(x):
        y, mut = bn.apply(v, x, mutable=["batch_stats"])
        return y, mut["batch_stats"]["mean"]

    y, mean = jax.jit(
        shard_map(
            body, mesh=mesh8, in_specs=P("data"), out_specs=(P("data"), P()),
        )
    )(x)
    xf = np.asarray(x, np.float64)
    np.testing.assert_allclose(
        np.asarray(mean), 0.1 * xf.mean(axis=(0, 1, 2)), rtol=1e-4, atol=1e-5
    )  # running update: 0.9*0 + 0.1*batch_mean


def test_channel_sums_interpret_matches_jnp():
    x = jax.random.normal(jax.random.key(6), (1024, 24)).astype(jnp.bfloat16)
    s, sq = channel_sums(x, interpret=True)
    xf = np.asarray(x, np.float32)
    np.testing.assert_allclose(np.asarray(s), xf.sum(0), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(sq), (xf * xf).sum(0), rtol=1e-2, atol=1e-2)


def test_channel_grad_sums_interpret_matches_jnp():
    key = jax.random.key(7)
    dy = jax.random.normal(key, (2048, 16)).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.key(8), (2048, 16)).astype(jnp.bfloat16)
    mean = jnp.linspace(-0.5, 0.5, 16)
    rstd = jnp.linspace(0.8, 1.2, 16)
    dsum, dxh = channel_grad_sums(dy, x, mean, rstd, interpret=True)
    dyf = np.asarray(dy, np.float32)
    xh = (np.asarray(x, np.float32) - np.asarray(mean)) * np.asarray(rstd)
    np.testing.assert_allclose(np.asarray(dsum), dyf.sum(0), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(dxh), (dyf * xh).sum(0), rtol=1e-2, atol=2e-2)


def test_resnet_fast_bn_param_tree_unchanged():
    """ResNet with fast_bn on/off has identical param + batch_stats trees —
    checkpoints are interchangeable."""
    from moco_tpu.models.resnet import BasicBlock, ResNet

    kw = dict(stage_sizes=(1,), block_cls=BasicBlock, width=8,
              num_classes=16, cifar_stem=True)
    x = jnp.zeros((2, 16, 16, 3))
    va = ResNet(fast_bn=False, **kw).init(jax.random.key(0), x, train=False)
    vb = ResNet(fast_bn=True, **kw).init(jax.random.key(0), x, train=False)
    assert jax.tree.structure(va) == jax.tree.structure(vb)
    for a, b in zip(jax.tree.leaves(va), jax.tree.leaves(vb), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bn_train_custom_vjp_matches_autodiff(dtype):
    """The TPU path's custom VJP (closed-form dx, Pallas-shaped reductions —
    jnp fallback internals here) agrees with flax autodiff to float
    tolerance. On TPU this same code runs with the Pallas kernels."""
    from moco_tpu.models.fast_bn import _bn_train

    flax_bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=dtype, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(10), (8, 6, 6, 16)) * 1.7
    v = flax_bn.init(jax.random.key(11), x)
    scale, bias = v["params"]["scale"], v["params"]["bias"]

    def loss_custom(x, scale, bias):
        y, _, _ = _bn_train(x, scale, bias, 1e-5, dtype)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    def loss_flax(x, params):
        y, _ = flax_bn.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            x, mutable=["batch_stats"])
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    gx, gs, gb = jax.grad(loss_custom, argnums=(0, 1, 2))(x, scale, bias)
    gxa, ga = jax.grad(loss_flax, argnums=(0, 1))(x, v["params"])
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(gx, np.float32), np.asarray(gxa, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ga["scale"]), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ga["bias"]), rtol=1e-3, atol=1e-3)


def test_tile_rows_vmem_budget_and_override():
    """_tile_rows keeps every per-operand tile within the byte target (the
    r5 on-chip VMEM finding: the grad-sums kernel holds ~4 f32 tile-sized
    intermediates, so a 2 MB bf16 tile blew the 16 MB Mosaic scoped-VMEM
    limit at c=64), divides n exactly, floors at the f32 sublane count,
    and honors the tile-budget override (MOCO_TPU_STATS_TILE_KIB, read
    once at import — a mid-process change could never reach an
    already-jitted program, so the kib parameter is the testable seam)."""
    from moco_tpu.ops.pallas_stats import _tile_rows

    for n, c in [(128 * 56 * 56, 64), (128 * 7 * 7, 2048), (256, 512),
                 (8, 64), (12, 256)]:
        t = _tile_rows(n, c, kib=0)
        assert n % t == 0 or t == n
        # bf16 operand tile within the 1 MB default target (unless floored)
        assert t * c * 2 <= (1 << 20) or t == 8 or t == n
        assert t >= 1

    # the floor is 8, not 512: c=2048 must not get a 1M-element tile
    assert _tile_rows(128 * 7 * 7, 2048, kib=0) * 2048 * 2 <= (1 << 20)

    base = _tile_rows(1 << 16, 64, kib=0)
    # the row cap scales with the budget: a 2 MiB override must reach the
    # pre-fix 16384-row tile at c=64, not clamp back to the default tile
    assert _tile_rows(1 << 16, 64, kib=2048) == 2 * base
    assert _tile_rows(1 << 16, 64, kib=256) == base // 4

    # non-power-of-two budgets floor to a power-of-two tile instead of
    # degenerating to 1-row tiles (factor-3 target vs pow2 n) or silently
    # aliasing the default program
    n = 128 * 56 * 56
    assert _tile_rows(n, 64, kib=768) == 4096
    assert _tile_rows(n, 128, kib=1536) == 4096
    for kib in (3, 24, 768, 1536, 5000):
        t = _tile_rows(n, 64, kib=kib)
        assert t & (t - 1) == 0 and t >= 8, (kib, t)


def test_pallas_gates_are_decoupled(monkeypatch):
    """fast_bn's BN-stats kernels default OFF on TPU (r5 on-chip A/B:
    ~52 ms/step launch overhead) behind the MOCO_TPU_PALLAS_BN opt-in,
    while fused_block's separately-validated family stays reachable via
    its config switch — flipping one default must not silently gate the
    other (review, r5). "0" must mean off for the opt-in."""
    import unittest.mock as mock

    import moco_tpu.models.fast_bn as fbn
    import moco_tpu.models.fused_block as fb

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        monkeypatch.delenv("MOCO_TPU_PALLAS_BN", raising=False)
        monkeypatch.delenv("MOCO_TPU_DISABLE_PALLAS", raising=False)
        assert not fbn._use_pallas()      # opt-in, default off
        assert fb._use_pallas()           # fused family: config gates it

        monkeypatch.setenv("MOCO_TPU_PALLAS_BN", "1")
        assert fbn._use_pallas()
        monkeypatch.setenv("MOCO_TPU_PALLAS_BN", "0")
        assert not fbn._use_pallas()      # "0" is off, not truthy-on

        monkeypatch.setenv("MOCO_TPU_PALLAS_BN", "1")
        monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS", "1")
        assert not fbn._use_pallas()      # global kill-switch wins
        assert not fb._use_pallas()


def test_custom_vjp_gate(monkeypatch):
    """_use_custom_vjp never asks which backend it is on: OFF unless
    MOCO_TPU_BN_VJP opts in ("0" means off), so the backward the CPU
    tests pin is the backward the chip runs."""
    import unittest.mock as mock

    import moco_tpu.models.fast_bn as fbn

    monkeypatch.delenv("MOCO_TPU_BN_VJP", raising=False)
    assert not fbn._use_custom_vjp()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert not fbn._use_custom_vjp()  # same default on the chip
        monkeypatch.setenv("MOCO_TPU_BN_VJP", "0")
        assert not fbn._use_custom_vjp()
    monkeypatch.setenv("MOCO_TPU_BN_VJP", "1")
    assert fbn._use_custom_vjp()


def test_env_flag_zero_means_off_everywhere(monkeypatch):
    """Uniform '0'-means-off across ALL Pallas switches, including the
    DISABLE_* spellings: MOCO_TPU_DISABLE_PALLAS=0 must NOT kill the
    kernel families (review, r5)."""
    import unittest.mock as mock

    import moco_tpu.data.augment as aug
    import moco_tpu.models.fast_bn as fbn
    import moco_tpu.models.fused_block as fb

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS", "0")
        monkeypatch.setenv("MOCO_TPU_PALLAS_BN", "1")
        assert fbn._use_pallas()          # "0" disable = not disabled
        assert fb._use_pallas()
        cfg = aug.v2_aug_config(out_size=16)
        monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS_BLUR", "0")
        assert aug._use_pallas_blur(cfg)
        monkeypatch.setenv("MOCO_TPU_DISABLE_PALLAS", "1")
        assert not fbn._use_pallas()
        assert not fb._use_pallas()
        assert not aug._use_pallas_blur(cfg)
