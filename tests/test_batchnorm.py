"""`models/batchnorm.py::BatchNorm` against `flax.linen.BatchNorm`: the same
parameter tree, forward and running statistics bit for bit, a backward that
differs by an ulp (which, with flax's variance clamp, is why the ResNets do
not use flax's module yet: ROADMAP D1)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from moco_tpu.models.batchnorm import BatchNorm


def _pair(dtype):
    flax_bn = nn.BatchNorm(
        use_running_average=False, momentum=0.9, epsilon=1e-5,
        dtype=dtype, param_dtype=jnp.float32,
    )
    ours = BatchNorm(
        use_running_average=False, momentum=0.9, epsilon=1e-5,
        dtype=dtype, param_dtype=jnp.float32,
    )
    return flax_bn, ours


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batchnorm_train_matches_flax(dtype):
    """The module mirrors flax's op order: forward output and running-stat
    updates bit for bit, gradients to an ulp."""
    flax_bn, ours = _pair(dtype)
    x = jax.random.normal(jax.random.key(0), (8, 6, 6, 16)) * 2.0 + 1.0
    v1 = flax_bn.init(jax.random.key(1), x)
    v2 = ours.init(jax.random.key(1), x)
    assert jax.tree.structure(v1) == jax.tree.structure(v2)
    # shared weights so outputs are comparable
    variables = {"params": v1["params"], "batch_stats": v1["batch_stats"]}

    ya, muta = flax_bn.apply(variables, x, mutable=["batch_stats"])
    yb, mutb = ours.apply(variables, x, mutable=["batch_stats"])
    # flax's forward graph: bit-identical in both dtypes
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
    gb, gxb = jax.grad(loss(ours), argnums=(0, 1))(variables["params"], x)
    # grads agree to ~1 ulp (autodiff reassociates one mul differently vs
    # flax's in-place `mul *=` graph); the forward is bit-exact and the
    # training-trajectory pin is test_golden.py, which must stay unchanged
    np.testing.assert_allclose(
        np.asarray(gxa, np.float32), np.asarray(gxb, np.float32),
        rtol=3e-6, atol=5e-7,
    )
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-6, atol=5e-6)


def test_batchnorm_eval_matches_flax():
    flax_bn = nn.BatchNorm(use_running_average=True, epsilon=1e-5)
    ours = BatchNorm(use_running_average=True, epsilon=1e-5)
    x = jax.random.normal(jax.random.key(2), (4, 5, 5, 8))
    v = flax_bn.init(jax.random.key(3), x)
    v["batch_stats"]["mean"] = jnp.linspace(-1, 1, 8)
    v["batch_stats"]["var"] = jnp.linspace(0.5, 2, 8)
    ya = flax_bn.apply(v, x)
    yb = ours.apply(v, x)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))


def test_batchnorm_sync_axis(mesh8):
    """SyncBN path: cross-device pmean statistics inside shard_map equal
    global-batch statistics."""
    from jax.sharding import PartitionSpec as P

    bn = BatchNorm(use_running_average=False, axis_name="data")
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


def test_resnet_param_tree_is_flaxs():
    """A block built on `nn.BatchNorm` has the ResNet's own parameter and
    `batch_stats` trees, leaf for leaf: checkpoints and the exporter's
    torchvision names do not know which module normalised."""
    from functools import partial

    from moco_tpu.models.resnet import BasicBlock

    conv = partial(nn.Conv, use_bias=False)
    x = jnp.zeros((2, 8, 8, 4))
    trees = [
        BasicBlock(filters=8, strides=2, conv=conv,
                   norm=partial(norm, use_running_average=False)).init(jax.random.key(0), x)
        for norm in (nn.BatchNorm, BatchNorm)
    ]
    assert jax.tree.structure(trees[0]) == jax.tree.structure(trees[1])
    for a, b in zip(*map(jax.tree.leaves, trees), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
