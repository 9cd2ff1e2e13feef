"""ShuffleBN collective tests on the 8-fake-device mesh (SURVEY §4 item 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from moco_tpu.parallel import DATA_AXIS, batch_shuffle, batch_unshuffle
from moco_tpu.parallel.collectives import all_gather_batch, ring_shuffle
from jax import shard_map


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def test_shuffle_unshuffle_is_identity(mesh8):
    x = np.arange(32 * 3, dtype=np.float32).reshape(32, 3)
    key = jax.random.key(0)

    def f(x, key):
        shuf, perm = batch_shuffle(x, key, DATA_AXIS)
        return batch_unshuffle(shuf, perm, DATA_AXIS)

    out = _shard_map(f, mesh8, (P(DATA_AXIS), P()), P(DATA_AXIS))(x, key)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_shuffle_is_global_permutation(mesh8):
    x = np.arange(32, dtype=np.float32).reshape(32, 1)
    key = jax.random.key(1)

    def f(x, key):
        shuf, _ = batch_shuffle(x, key, DATA_AXIS)
        return shuf

    out = np.asarray(_shard_map(f, mesh8, (P(DATA_AXIS), P()), P(DATA_AXIS))(x, key))
    # same multiset of rows globally...
    assert sorted(out.ravel().tolist()) == sorted(x.ravel().tolist())
    # ...but the per-device grouping changed: at least one device must hold a
    # row that originated on a different device (BN decorrelation property).
    orig_groups = x.reshape(8, 4, 1)
    new_groups = out.reshape(8, 4, 1)
    assert not np.array_equal(orig_groups, new_groups)
    moved = sum(
        1
        for d in range(8)
        if set(new_groups[d].ravel()) != set(orig_groups[d].ravel())
    )
    assert moved >= 6  # with a random 32-perm, essentially all groups change


def test_all_gather_batch_concatenates_in_rank_order(mesh8):
    x = np.arange(16, dtype=np.float32).reshape(16, 1)
    f = _shard_map(
        lambda x: all_gather_batch(x, DATA_AXIS), mesh8, (P(DATA_AXIS),), P(DATA_AXIS)
    )
    out = np.asarray(f(x))  # each device holds full copy; sharded out gives back x8 rows
    assert out.shape == (16 * 8, 1)
    np.testing.assert_array_equal(out[:16], x)


def test_ring_shuffle_roundtrip(mesh8):
    x = np.arange(32, dtype=np.float32).reshape(32, 1)

    def f(x):
        y = ring_shuffle(x, DATA_AXIS)
        return ring_shuffle(y, DATA_AXIS, inverse=True)

    out = _shard_map(f, mesh8, (P(DATA_AXIS),), P(DATA_AXIS))(x)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_shuffle_roundtrip_on_2d_mesh(mesh8):
    """ShuffleBN generalized to arbitrary mesh shapes (ISSUE 15): the
    gather+permute shuffle runs over the combined (data, fsdp) group and
    roundtrips exactly, and the global row order matches the combined
    row-major device index."""
    from moco_tpu.parallel.mesh import create_mesh_2d

    mesh2d = create_mesh_2d(4, devices=list(mesh8.devices.flat))
    axes = ("data", "fsdp")
    x = np.arange(32 * 3, dtype=np.float32).reshape(32, 3)
    key = jax.random.key(0)

    def f(x, key):
        shuf, perm = batch_shuffle(x, key, axes)
        return batch_unshuffle(shuf, perm, axes)

    out = _shard_map(f, mesh2d, (P(axes), P()), P(axes))(x, key)
    np.testing.assert_array_equal(np.asarray(out), x)

    g = _shard_map(lambda v: all_gather_batch(v, axes), mesh2d,
                   (P(axes),), P(axes))
    gathered = np.asarray(g(x))
    np.testing.assert_array_equal(gathered[:32], x)


def test_chunked_gather_bitwise_equals_plain(mesh8):
    """The FAST-style chunked gather (ISSUE 15) restitches to exactly the
    monolithic gather's rows — pure scheduling, zero numerics."""
    x = np.asarray(
        jax.random.normal(jax.random.key(3), (32, 5)), np.float32)

    def plain(v):
        return all_gather_batch(v, DATA_AXIS)

    def chunked(v):
        return all_gather_batch(v, DATA_AXIS, chunks=2)

    a = np.asarray(_shard_map(plain, mesh8, (P(DATA_AXIS),), P(DATA_AXIS))(x))
    b = np.asarray(
        _shard_map(chunked, mesh8, (P(DATA_AXIS),), P(DATA_AXIS))(x))
    np.testing.assert_array_equal(a, b)


def test_batch_axis_index_matches_gather_order(mesh8):
    """The combined row-major index IS the position a device's tiled
    gather shard lands at — the invariant every v3 label offset and aug
    sample-key derivation rides on."""
    from moco_tpu.parallel.collectives import batch_axis_index
    from moco_tpu.parallel.mesh import create_mesh_2d

    mesh2d = create_mesh_2d(4, devices=list(mesh8.devices.flat))
    axes = ("data", "fsdp")
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(v):
        idx = batch_axis_index(axes)
        g = all_gather_batch(idx[None, None].astype(np.float32), axes)
        return g

    out = np.asarray(_shard_map(f, mesh2d, (P(axes),), P(axes))(x))
    np.testing.assert_array_equal(out[:8].ravel(), np.arange(8))


def test_ring_shuffle_mixes_group_membership(mesh8):
    """The point of ShuffleBN is changing group COMPOSITION, not which
    device computes a group: every post-shuffle BN group must contain
    samples from (at least) two different pre-shuffle groups — a whole-shard
    rotation would fail this (membership preserved ⇒ BN leak intact)."""
    x = np.arange(32, dtype=np.float32).reshape(32, 1)
    out = np.asarray(
        _shard_map(
            lambda x: ring_shuffle(x, DATA_AXIS), mesh8, (P(DATA_AXIS),), P(DATA_AXIS)
        )(x)
    )
    orig_groups = [set(g.ravel()) for g in x.reshape(8, 4)]
    for d in range(8):
        new_group = set(out.reshape(8, 4)[d].ravel())
        sources = {
            i for i, og in enumerate(orig_groups) if og & new_group
        }
        assert len(sources) >= 2, f"group {d} drawn from a single source {sources}"
        assert new_group != orig_groups[d]
