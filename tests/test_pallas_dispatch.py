"""ISSUE 32: the routed layer's row movers (`ops/pallas_dispatch.py`: `dispatch`,
`combine`, each the other's transpose) against the XLA body of
`models/sdar.py::Experts.one_pass` that they replace on the TPU: a gather with
its masks and cast, and a float32 scatter-add. On the CPU the kernels run
interpreted, outside any `shard_map`; inside one they are only traced."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from moco_tpu import models
from moco_tpu.models import sdar
from moco_tpu.ops import pallas_dispatch as pd

TOKENS, HIDDEN, TOP_K, EXPERTS, HELD, EMPTY = 512, 256, 4, 8, 4, 2
# (first, n) of a pass over the sorted assignments: a buffer that is full, a
# spilled pass whose buffer ends in unassigned rows, one pass that holds all
PASSES = {"full_buffer": (0, 512), "spilled_pass_with_a_tail": (512, 512),
          "one_pass_with_a_tail": (0, 1024)}


def routing(first, n, seed=0):
    """Sorted assignments as `Experts` makes them, from choices that hold a
    token with no held expert (0), with one (1), with three (2), with all
    `TOP_K` (3), and a held expert nobody chose (`EMPTY`)."""
    keys = jax.random.split(jax.random.key(seed), 2)
    others = np.array([e for e in range(EXPERTS) if e != EMPTY])
    expert = others[np.argsort(np.asarray(jax.random.uniform(keys[0], (TOKENS, EXPERTS - 1))),
                               -1)[:, :TOP_K]]
    expert[0] = [4, 5, 6, 7]
    expert[1] = [4, 0, 6, 7]
    expert[2] = [0, 5, 1, 3]
    expert[3] = [3, 1, 0, 3]   # every choice held (a kernel does not ask for distinct experts)
    weight = jax.random.uniform(keys[1], (TOKENS, TOP_K), minval=0.05)
    weight = weight / jnp.sum(weight, -1, keepdims=True)
    flat = jnp.where(expert < HELD, expert, HELD).reshape(-1)
    order = jnp.pad(jnp.argsort(flat, stable=True), (0, first + n))
    sizes = np.bincount(np.asarray(flat), minlength=HELD + 1)[:HELD]
    assigned = int(sizes.sum())
    take = order[first:first + n]
    token = (take // TOP_K).astype(jnp.int32)
    valid = (first + jnp.arange(n) < assigned)[:, None]
    w = jnp.where(valid, weight.reshape(-1)[take][:, None], 0)
    count = jnp.int32(np.clip(assigned - first, 0, n))
    return dict(token=token, valid=valid, w=w, count=count, sizes=sizes, assigned=assigned)


def test_the_routing_holds_what_the_cases_name():
    r = routing(0, 1024)
    per_token = np.bincount(np.asarray(r["token"][: r["assigned"]]), minlength=TOKENS)
    assert list(per_token[:4]) == [0, 1, 3, TOP_K]
    assert r["sizes"][EMPTY] == 0 and all(r["sizes"][e] > 0 for e in range(HELD) if e != EMPTY)
    assert 512 < r["assigned"] < 1024                       # a full buffer, then a spill with a tail
    assert int(routing(512, 512)["count"]) == r["assigned"] - 512


def xla_dispatch(src, token, valid, dtype):
    return jnp.where(valid, src.astype(dtype)[token], 0)


def xla_combine(y, w, token):
    return jnp.zeros((TOKENS, y.shape[1]), jnp.float32).at[token].add(y.astype(jnp.float32) * w)


@functools.cache
def results(case, dtype):
    """x, out and the three cotangents, by the kernels and by the XLA body, on
    the same inputs; `du32`: the gather's transpose summed in float32."""
    first, n = PASSES[case]
    r = routing(first, n)
    token, valid, w, count = r["token"], r["valid"], r["w"], r["count"]
    keys = jax.random.split(jax.random.key(7), 4)
    src = jax.random.normal(keys[0], (TOKENS, HIDDEN), jnp.float32)
    y = jnp.where(valid, jax.random.normal(keys[1], (n, HIDDEN), jnp.float32), 0).astype(dtype)
    g_out = jax.random.normal(keys[2], (TOKENS, HIDDEN), jnp.float32)
    g_x = jax.random.normal(keys[3], (n, HIDDEN), jnp.float32).astype(dtype)

    def kernels():
        lst = pd.listing(token, count, w, TOKENS)
        x, x_vjp = jax.vjp(
            lambda s: pd.dispatch(s, token, count, lst, dtype=dtype, interpret=True), src)
        out, out_vjp = jax.vjp(
            lambda y, w: pd.combine(y, w, token, count, lst, interpret=True), y, w)
        dy, dw = out_vjp(g_out)
        return dict(x=x, out=out, dy=dy, dw=dw, du=x_vjp(g_x)[0])

    def xla():
        x, x_vjp = jax.vjp(lambda s: xla_dispatch(s, token, valid, dtype), src)
        out, out_vjp = jax.vjp(lambda y, w: xla_combine(y, w, token), y, w)
        dy, dw = out_vjp(g_out)
        du32 = jnp.zeros((TOKENS, HIDDEN), jnp.float32).at[token].add(
            jnp.where(valid, g_x, 0).astype(jnp.float32))
        return dict(x=x, out=out, dy=dy, dw=dw, du=x_vjp(g_x)[0], du32=du32)

    as_np = lambda d: {k: np.asarray(v, np.float32) for k, v in d.items()}
    return as_np(kernels()), as_np(jax.jit(xla)()), as_np(kernels())


# the largest gap over the largest element of the XLA body's result. x and dy
# are a copied row, masked, scaled and cast: bit for bit. out and du are float32
# sums of at most `TOP_K` terms in another order, dw one of `HIDDEN` terms
TOLERANCE = {"x": 0.0, "dy": 0.0, "out": 3e-7, "du": 3e-7, "dw": 2e-6}


@pytest.mark.parametrize("which", list(TOLERANCE))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_the_kernels_agree_with_the_xla_body(case, dtype, which):
    got, want, _ = results(case, dtype)
    # XLA's transpose of a bfloat16 gather adds in bfloat16; the kernel adds in
    # float32 and rounds once, so it is held to the float32 sum
    have, oracle = got[which], want["du32" if which == "du" else which]
    assert have.shape == oracle.shape and np.abs(oracle).max() > 0
    assert np.abs(have - oracle).max() <= TOLERANCE[which] * np.abs(oracle).max()


@pytest.mark.parametrize("case", list(PASSES))
def test_the_float32_combine_is_kept_forward_and_transposed(case):
    """float32 inputs, float32 sums: within float32 rounding of a sum of at
    most eight terms, where a bfloat16 sum would read 2^-9 and more."""
    got, want, _ = results(case, "float32")
    for which, oracle in (("out", "out"), ("du", "du")):
        gap = np.abs(got[which] - want[oracle]).max() / np.abs(want[oracle]).max()
        assert gap <= 8 * 2.0 ** -24, (which, gap)
    assert got["out"].dtype == np.float32


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_du_is_no_further_from_the_float32_sum_than_xlas(dtype):
    got, want, _ = results("one_pass_with_a_tail", dtype)
    assert np.abs(got["du"] - want["du32"]).max() <= np.abs(want["du"] - want["du32"]).max() + 1e-6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PASSES))
def test_the_same_inputs_twice_give_the_same_bits(case, dtype):
    once, _, twice = results(case, dtype)
    for which in once:
        assert np.array_equal(once[which], twice[which]), which


@pytest.mark.parametrize("case", list(PASSES))
def test_rows_past_the_last_assignment_are_zero_rows_and_no_tokens(case):
    first, n = PASSES[case]
    count = int(routing(first, n)["count"])
    got, _, _ = results(case, "bfloat16")
    assert not got["x"][count:].any() and not got["dy"][count:].any()
    assert got["x"][:count].any(-1).all()
    assert not got["out"][0].any() and not got["du"][0].any()      # the token nobody here serves


@pytest.mark.parametrize("case", list(PASSES))
def test_the_listing_walks_every_block_in_a_static_number_of_visits(case):
    first, n = PASSES[case]
    r = routing(first, n)
    lst = jax.tree.map(np.asarray, pd.listing(r["token"], r["count"], r["w"], TOKENS))
    chunks, tiles = n // pd.CHUNK, TOKENS // pd.TOKEN_TILE
    assert lst.tile.shape == (chunks + tiles,)               # whatever the router sent
    assert pd._tokens_of(lst) == TOKENS
    live = lst.base >= 0
    assert np.array_equal(np.unique(lst.tile[live & (lst.first == 1)]), np.arange(tiles))
    assert (lst.first[live].sum() == tiles) and not lst.first[~live].any()
    assert np.all(np.diff(lst.tile) >= 0) and lst.chunk.min() >= 0 and lst.chunk.max() < chunks
    # every assigned entry lies in a chunk that its token's block visits
    tok, count = lst.tok.reshape(-1), int(r["count"])
    assert np.all(np.diff(tok) >= 0) and np.all(tok[count:] == TOKENS)
    visited = {(t, c) for t, c, ok in zip(lst.tile, lst.chunk, live) if ok}
    assert all((tok[i] // pd.TOKEN_TILE, i // pd.CHUNK) in visited for i in range(count))
    assert np.array_equal(np.sort(lst.rows), np.arange(n))


REAL = sdar.SDAR_SIZES["sdar_30b_a3b"]
TINY = sdar.SDAR_SIZES["sdar_tiny"]


@pytest.mark.parametrize("case, tokens, hidden, n, backend, plan", [
    ("the_cells_first_pass", 16384, REAL["hidden"], 32768, "tpu", "kernels"),
    ("the_cells_small_spill_pass", 16384, REAL["hidden"], 4096, "tpu", "kernels"),
    ("cpu_backend", 16384, REAL["hidden"], 32768, "cpu", "xla"),
    ("gpu_backend", 16384, REAL["hidden"], 32768, "gpu", "xla"),
    ("sdar_tiny", 128, TINY["hidden"], 512, "tpu", "xla"),
    ("half_lane_hidden", 16384, 2048 + 64, 32768, "tpu", "xla"),
    ("ragged_buffer", 16384, 2048, 32768 + 8, "tpu", "xla"),
    ("ragged_tokens", 16384 + 64, 2048, 32768, "tpu", "xla"),
    ("one_tile", 128, 128, 128, "tpu", "kernels"),
    ("half_a_tile_of_rows", 128, 128, 64, "tpu", "xla"),
])
def test_the_dispatch_rule(case, tokens, hidden, n, backend, plan):
    assert pd.dispatch_plan(tokens, hidden, n, backend=backend) == plan


def test_this_backend_takes_the_xla_body_and_the_layer_follows_the_rule(monkeypatch):
    """No knob: `Experts` asks the rule, and the rule asks the backend and the
    shapes. The `moe` block of the `setup` event is the same answer."""
    cell = dict(dispatch="xla", rows=32768, spill_rows=4096, passes=3, tokens=32 * 512)
    assert models.dispatch_path("sdar_30b_a3b", 32, 512, held=16) == cell       # the tests' CPU
    assert models.dispatch_path("ouro_2p6b", 16, 512) is None                   # no router
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert models.dispatch_path("sdar_30b_a3b", 32, 512, held=16) == dict(cell, dispatch="kernels")
    # the whole layer holds every assignment in one pass
    assert models.dispatch_path("sdar_30b_a3b", 32, 512) == dict(
        dispatch="kernels", rows=32 * 512 * 8, spill_rows=0, passes=0, tokens=32 * 512)
    assert models.dispatch_path("sdar_tiny", 8, 16, held=4)["dispatch"] == "xla"
    calls = []

    def fake_dispatch(src, token, count, lst, *, dtype):
        calls.append(("dispatch", src.shape, src.dtype, token.shape, jnp.dtype(dtype)))
        return jnp.zeros((token.shape[0], src.shape[1]), dtype)

    def fake_combine(y, w, token, count, lst):
        calls.append(("combine", y.shape, y.dtype, w.shape, w.dtype))
        return jnp.zeros((256, y.shape[1]), jnp.float32)

    monkeypatch.setattr(sdar, "dispatch", fake_dispatch)
    monkeypatch.setattr(sdar, "combine", fake_combine)
    u = jax.ShapeDtypeStruct((256, 128), jnp.float32)

    def tree(held):
        module = sdar.Experts(8, held, 4, 32, jnp.bfloat16)
        return jax.eval_shape(lambda u: module.init_with_output(jax.random.key(0), u)[1], u)

    # a share of 2 of 8: a small spill pass of 64 rows is half a tile of the
    # buffer, so every pass of the layer takes the XLA body
    assert sdar.pass_sizes(256, 4, 8, 2) == (512, 64, 1)
    shared = tree(2)
    assert calls == []
    # the whole layer: one pass of 1024 rows. The layer's own type in and out of
    # the buffer, float32 weights
    assert sdar.pass_sizes(256, 4, 8, 8) == (1024, 0, 0)
    whole = tree(8)
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert calls == [("dispatch", (256, 128), bf16, (1024,), bf16),
                     ("combine", (1024, 128), bf16, (1024, 1), f32)]
    # and the parameter tree is the XLA body's
    monkeypatch.undo()
    assert jax.tree.structure(whole) == jax.tree.structure(tree(8))
    assert jax.tree.structure(shared) == jax.tree.structure(tree(2))


def test_outputs_carry_vma_under_a_two_device_shard_map_with_the_check_on():
    """Trace only, forward and both transposes, as the step's region does."""
    from jax.sharding import PartitionSpec as P

    from moco_tpu.parallel.mesh import DATA_AXIS, create_mesh

    mesh = create_mesh(devices=jax.devices()[:2])
    tokens, n = 2 * 256, 2 * 512
    src = jnp.zeros((tokens, 128), jnp.float32)
    y = jnp.zeros((n, 128), jnp.bfloat16)
    seen = []

    def region(src, y, ct_x, ct_out):
        tok = jnp.argsort(src[:, 0]).astype(jnp.int32)          # varies as the data do
        token = jnp.concatenate([tok, tok])
        count = jnp.sum(src[:, 0] > -1).astype(jnp.int32)
        w = jnp.abs(y[:, :1]).astype(jnp.float32)

        def layer(src, y, w):
            lst = pd.listing(token, count, w, src.shape[0])
            return (pd.dispatch(src, token, count, lst, dtype=jnp.bfloat16),
                    pd.combine(y, w, token, count, lst))

        (x, out), vjp = jax.vjp(layer, src, y, w)
        du, dy, dw = vjp((ct_x, ct_out))
        seen.extend(jax.typeof(a).vma for a in (x, out, du, dy, dw))
        return x, out, du, dy, dw

    spec = P(DATA_AXIS)
    sharded = jax.shard_map(region, mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 5,
                            check_vma=True)
    args = (src, y, y, src)
    out = jax.eval_shape(sharded, *args)
    assert [a.shape for a in out] == [y.shape, src.shape, src.shape, y.shape, (n, 1)]
    assert [a.dtype for a in out] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16,
                                      jnp.float32]
    assert seen == [frozenset({DATA_AXIS})] * 5
    jaxpr = str(jax.make_jaxpr(sharded)(*args))
    assert jaxpr.count("pallas_call") == 4 and "check_vma=True" in jaxpr


@pytest.mark.parametrize("n", [32768, 4096])
def test_the_cells_shapes_lower_for_the_tpu_forward_and_backward(n):
    """`[16384, 2048]` float32 rows to a bfloat16 buffer of the first pass's
    and of the small spill pass's size and back, exported for the TPU platform
    from the CPU: a tracing or typing break of the four Mosaic programs fails
    here, not on the chip."""
    tokens, hidden = 16384, REAL["hidden"]
    src = jax.ShapeDtypeStruct((tokens, hidden), jnp.float32)
    y = jax.ShapeDtypeStruct((n, hidden), jnp.bfloat16)
    token = jax.ShapeDtypeStruct((n,), jnp.int32)
    count = jax.ShapeDtypeStruct((), jnp.int32)
    w = jax.ShapeDtypeStruct((n, 1), jnp.float32)

    def both(src, y, w, token, count, ct_x, ct_out):
        def layer(src, y, w):
            lst = pd.listing(token, count, w, tokens)
            return (pd.dispatch(src, token, count, lst, dtype=jnp.bfloat16),
                    pd.combine(y, w, token, count, lst))

        (x, out), vjp = jax.vjp(layer, src, y, w)
        return (x, out, *vjp((ct_x, ct_out)))

    exported = jax.export.export(jax.jit(both), platforms=["tpu"])(src, y, w, token, count, y, src)
    text = exported.mlir_module()
    assert text.count("tpu_custom_call") == 4
    for name in ("moe_gather", "moe_gather_weighted", "moe_combine", "moe_gather_transpose"):
        assert f'kernel_name = "{name}"' in text or name in text
    assert [a.shape for a in exported.out_avals] == [y.shape, src.shape, src.shape, y.shape,
                                                     w.shape]
    assert [a.dtype for a in exported.out_avals] == [jnp.bfloat16, jnp.float32, jnp.float32,
                                                     jnp.bfloat16, jnp.float32]


def test_a_skewed_router_takes_more_passes_through_the_kernels_and_drops_nothing(monkeypatch):
    """`test_sdar_encoder.py`'s skewed router at a width the kernels take: every
    token sends all four choices to the four held experts, twice what the first
    pass holds, so the small spill pass and a whole pass run under `cond`,
    `scan` and `checkpoint`, forward and backward, through `dispatch` and
    `combine` (interpreted); the result is the plain sum over the chosen
    experts, and the XLA body's."""
    tokens, hidden, experts, held, top_k, width = 256, 128, 16, 4, 4, 32
    assert sdar.pass_sizes(tokens, top_k, experts, held) == (512, 64, 1)
    module = sdar.Experts(experts, held, top_k, width)
    u = jax.random.normal(jax.random.key(3), (tokens, hidden))
    p = module.init(jax.random.key(4), u)["params"]
    p["router"]["kernel"] = p["router"]["kernel"].at[:, :held].add(
        50.0 * jnp.sign(u.mean(0))[:, None])
    u = u + 2.0 * jnp.sign(u.mean(0))        # every token leans the same way

    def plain(p, u):
        r = jax.nn.softmax(u @ lax.stop_gradient(p["router"]["kernel"]), -1)
        w, e = lax.top_k(r, top_k)
        w = w / w.sum(-1, keepdims=True)
        y = jnp.einsum("etf,efd->etd",
                       jax.nn.silu(jnp.einsum("td,edf->etf", u, p["gate"]))
                       * jnp.einsum("td,edf->etf", u, p["up"]), p["down"])
        return jnp.einsum("etd,te->td", y, jnp.sum(jax.nn.one_hot(e, experts) * w[..., None],
                                                   1)[:, :held])

    def run():
        out, stats = module.apply({"params": p}, u, mutable=[sdar.MOE_STATS])
        grad = jax.grad(lambda p, u: jnp.sum(module.apply({"params": p}, u) ** 2),
                        argnums=(0, 1))(p, u)
        return out, stats[sdar.MOE_STATS]["held_counts"], grad

    xla = run()
    calls = []

    def counted(kernel, name):
        def call(*args, **kwargs):
            calls.append((name, args[1].shape[0]))
            return kernel(*args, **kwargs, interpret=True)
        return call

    with monkeypatch.context() as patch:
        # whole tiles at the test's spill of 64 rows: the rule's sizes scaled down
        patch.setattr(pd, "ROW_TILE", 64)
        patch.setattr(pd, "CHUNK", 64)
        patch.setattr(sdar, "dispatch_plan", lambda *a, **k: "kernels")
        patch.setattr(sdar, "dispatch", counted(pd.dispatch, "dispatch"))
        patch.setattr(sdar, "combine", counted(pd.combine, "combine"))
        for jitted in (pd._gather, pd._sum_by_token, pd.listing):
            jitted.clear_cache()
        out, counts, grad = run()
    for jitted in (pd._gather, pd._sum_by_token, pd.listing):
        jitted.clear_cache()
    assert int(counts.sum()) == top_k * tokens               # nothing held elsewhere
    assert {("dispatch", 512), ("combine", 512), ("dispatch", 64), ("combine", 64)} == set(calls)
    np.testing.assert_allclose(out, plain(p, u), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out, xla[0], rtol=1e-5, atol=1e-6)
    want = jax.grad(lambda p, u: jnp.sum(plain(p, u) ** 2), argnums=(0, 1))(p, u)
    assert not np.any(grad[0]["router"]["kernel"]) and np.any(grad[1])
    for a, b, c in zip(*(jax.tree.leaves(g) for g in (grad, want, xla[2]))):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4 * float(jnp.abs(b).max()))
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()))
