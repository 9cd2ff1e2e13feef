"""ISSUE 26: the initial `TrainState` is the output of compiled programs, and
leaf for leaf the one the eager construction gives. The eager construction is
written out here (`model.init`, the q → k copies, `init_queue`, `tx.init`), as
`create_train_state` / `create_v3_train_state` had it before."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu.config import get_preset
from moco_tpu.ops.queue import init_queue
from moco_tpu.train_state import TrainState, create_train_state
from moco_tpu.train_step import build_encoder, build_fused_step, build_optimizer, build_train_step
from moco_tpu.v3_step import create_v3_train_state, encoder_subtree

IMG, B, K = 32, 16, 256


def tiny(**kw):
    return get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, image_size=IMG, num_negatives=K, batch_size=B,
        compute_dtype="float32", **kw)


@dataclasses.dataclass(frozen=True)
class Case:
    config: object
    queue_dtype: object = jnp.float32


CASES = {
    "v1_queue": Case(tiny()),
    "v2_mlp_head_queue": Case(tiny(variant="v2", mlp_head=True, aug_plus=True, cos=True,
                                   temperature=0.2)),
    "v2_bfloat16_queue": Case(tiny(variant="v2", mlp_head=True), queue_dtype=jnp.bfloat16),
    "v3_vit_no_queue": Case(get_preset("imagenet-moco-v3-vits").replace(
        image_size=IMG, batch_size=B, compute_dtype="float32", warmup_epochs=0)),
    "v3_resnet_no_queue": Case(tiny(variant="v3", optimizer="lars", lr=0.3, weight_decay=1e-6,
                                    momentum_ema=0.99)),
}


def compiled_state(case, model, tx, seed=0):
    shape = (B, IMG, IMG, 3)
    if case.config.variant == "v3":
        return create_v3_train_state(jax.random.key(seed), model, tx, shape)
    return create_train_state(jax.random.key(seed), model, tx, shape, K, case.config.embed_dim,
                              queue_dtype=case.queue_dtype)


def eager_state(case, model, tx, seed=0):
    rng, dummy = jax.random.key(seed), jnp.zeros((B, IMG, IMG, 3), jnp.float32)
    if case.config.variant == "v3":
        init_key, state_key = jax.random.split(rng)
        variables = model.init(init_key, dummy, train=False, predict=True)
        to_k, queue, queue_ptr = encoder_subtree, None, None
    else:
        init_key, queue_key, state_key = jax.random.split(rng, 3)
        variables = model.init(init_key, dummy, train=False)
        queue, queue_ptr = init_queue(queue_key, K, case.config.embed_dim, case.queue_dtype)

        def to_k(tree):
            return tree

    params_q, batch_stats_q = variables["params"], variables.get("batch_stats", {})
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params_q=params_q,
        params_k=jax.tree.map(jnp.copy, to_k(params_q)),
        batch_stats_q=batch_stats_q,
        batch_stats_k=jax.tree.map(jnp.copy, to_k(batch_stats_q)),
        opt_state=tx.init(params_q),
        queue=queue,
        queue_ptr=queue_ptr,
        rng=state_key,
    )


@pytest.fixture(scope="module", params=list(CASES))
def built(request):
    case = CASES[request.param]
    model = build_encoder(case.config)
    tx, sched = build_optimizer(case.config, steps_per_epoch=4)
    return case, model, tx, sched, compiled_state(case, model, tx), eager_state(case, model, tx)


def is_key(leaf):
    return jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key)


def raw(leaf):
    """A leaf's bits: key arrays by their data, bfloat16 and the rest as numpy
    holds them."""
    return np.asarray(jax.random.key_data(leaf) if is_key(leaf) else leaf)


def test_same_tree_shapes_and_dtypes_as_the_eager_construction(built):
    *_, state, eager = built
    assert jax.tree.structure(state) == jax.tree.structure(eager)
    got, want = jax.tree.leaves(state), jax.tree.leaves(eager)
    assert len(got) > 10
    assert [(x.shape, x.dtype) for x in got] == [(x.shape, x.dtype) for x in want]
    assert all(isinstance(x, jax.Array) for x in got)
    # where the state lives does not change: nothing is committed to a device
    assert [x.committed for x in got] == [x.committed for x in want]
    assert not any(x.committed for x in got)


def ulps(x, y):
    return int(np.abs(x.view(np.int32).astype(np.int64) - y.view(np.int32).astype(np.int64)).max())


def test_every_leaf_is_bitwise_the_eager_one(built):
    case, *_, state, eager = built
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(state)]
    differ = {path: ulps(raw(x), raw(y))
              for path, x, y in zip(paths, jax.tree.leaves(state), jax.tree.leaves(eager))
              if raw(x).tobytes() != raw(y).tobytes()}
    # one known leaf: the ViT's `cls_token` is `normal(key) * 1e-6`, and the
    # compiler folds that factor into the draw's own sqrt(2): one ulp on a third
    # of its 384 numbers, in q and in k's copy. The draws are the same.
    assert all("cls_token" in path and n == 1 for path, n in differ.items()), differ
    assert len(differ) <= 2 and (not differ or case.config.arch.startswith("vit"))
    if case.config.variant != "v3":
        assert state.queue.dtype == case.queue_dtype and state.queue.shape == (K, case.config.embed_dim)
        assert int(state.queue_ptr) == 0
    else:
        assert state.queue is None and state.queue_ptr is None
        assert "predictor" in state.params_q and "predictor" not in state.params_k
    assert int(state.step) == 0
    # k starts as q's copy
    q_side = encoder_subtree(state.params_q) if case.config.variant == "v3" else state.params_q
    assert all(raw(a).tobytes() == raw(b).tobytes()
               for a, b in zip(jax.tree.leaves(state.params_k), jax.tree.leaves(q_side), strict=True))


def test_no_two_leaves_share_a_buffer(built):
    """The step donates the whole state: two leaves on one buffer fail at the
    first step."""
    *_, state, _ = built
    leaves = jax.tree.leaves(state)
    pointers = {(jax.random.key_data(x) if is_key(x) else x).unsafe_buffer_pointer() for x in leaves}
    assert len(pointers) == len(leaves)


def test_one_donated_fused_step_runs_on_it(built):
    from moco_tpu.data.augment import aug_config_for, build_two_crops_sharded, v3_aug_configs, with_dtype
    from moco_tpu.data.datasets import full_extents
    from moco_tpu.parallel.gradsync import GradSync
    from moco_tpu.parallel.mesh import create_mesh

    case, model, tx, sched, first, _ = built
    config = case.config
    mesh = create_mesh(devices=jax.devices()[:1])
    # a second call of the constructor: the first state serves the other tests
    state = GradSync.for_mesh(config, mesh).attach(compiled_state(case, model, tx), mesh)
    step_fn = build_train_step(config, model, tx, mesh, 4, sched, state=state)
    aug = v3_aug_configs(IMG) if config.variant == "v3" else aug_config_for(config)
    fused = build_fused_step(step_fn, build_two_crops_sharded(with_dtype(aug, "float32"), mesh),
                             jax.random.key(1))
    stage = IMG + IMG // 8
    imgs = jnp.asarray(np.random.RandomState(0).randint(0, 256, (B, stage, stage, 3), dtype=np.uint8))
    before = raw(jax.tree.leaves(first.params_q)[-1]).copy()
    new_state, metrics = fused(state, imgs, full_extents(B, stage, stage), 0)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1
    assert all(x.is_deleted() for x in jax.tree.leaves(state.params_q))     # donated, all of it
    assert not np.array_equal(raw(jax.tree.leaves(new_state.params_q)[-1]), before)
    if config.variant != "v3":
        assert int(new_state.queue_ptr) == B and new_state.queue.dtype == case.queue_dtype


def test_the_initialiser_is_found_again_for_an_equal_model(built):
    """The model and the shapes are the static arguments of one jitted
    function, so a second call with an equal model compiles nothing."""
    from moco_tpu.utils.cache import CompileCounters

    case, _, tx, *_ = built
    counters = CompileCounters()
    try:
        again = compiled_state(case, build_encoder(case.config), tx, seed=3)
        jax.block_until_ready(again)
        assert counters.snapshot()["n"] == 0
    finally:
        counters.close()
    assert raw(again.rng).tobytes() != raw(built[4].rng).tobytes()      # another key, another state
