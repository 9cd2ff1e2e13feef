"""ISSUE 33, the program's side: the routed token encoder with learned sparse
attention (`models/keye.py`) at a small size on the CPU, and its kernels
(`ops/pallas_select.py`, `ops/pallas_attention.py::masked_attention`) in
interpret mode against their oracles. The selection is exact; with `index_topk`
at the view's length the encoder IS `models/sdar.py`'s at `block_length` 1; the
indexer's and a share's router's leaves are constants of the step; the scopes
`index` and `select` are siblings of `attn`."""

import re
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from moco_tpu import models
from moco_tpu.models import keye, sdar
from moco_tpu.ops import pallas_attention as pa
from moco_tpu.ops import pallas_select as ps
from moco_tpu.telemetry import scopes
from test_sdar_encoder import build_fused

Z = keye.KEYE_SIZES["keye_tiny"]
LENGTH = 64


def tiny_config(**over):
    from moco_tpu.config import get_preset

    return get_preset("text-moco-v2-keye").replace(
        arch="keye_tiny", num_experts=4, vocab_size=64, seq_len=LENGTH, batch_size=4,
        num_negatives=256, compute_dtype="float32", health_stride=1, **over)


def expected_sizes(length, topk):
    return np.minimum(topk, np.arange(length) + 1)


# -- the selection ---------------------------------------------------------------


def test_every_query_selects_its_best_causal_keys_and_ties_go_to_the_lower_position():
    topk, length = Z["index_topk"], LENGTH
    scores = jax.random.normal(jax.random.key(0), (2, length, length))
    # planted equal scores: query 40 rates keys 3, 9, 20 and 33 alike and above
    # every other, query 50 rates EVERY key alike, and -0.0 is 0.0
    scores = scores.at[0, 40, jnp.array([3, 9, 20, 33])].set(7.0).at[0, 40, 34:].set(9.0)
    scores = scores.at[1, 50].set(0.0).at[1, 50, ::2].set(-0.0)
    live = np.asarray(keye.top_k_selection(scores, topk))
    assert live.dtype == np.int8 and not np.triu(live, 1).any()           # causal
    assert (live.sum(-1) == expected_sizes(length, topk)).all()             # exact sizes
    causal = np.tril(np.ones((length, length), bool))
    assert (live[:, :topk].astype(bool) == causal[:topk]).all()            # the first see every key
    assert live[0, 40, [3, 9, 20, 33]].all()                               # the planted best
    assert (np.flatnonzero(live[1, 50]) == np.arange(topk)).all()          # all equal: the lowest
    # a row's selection is its top-k by a stable descending sort
    s = np.where(causal, np.asarray(scores), -np.inf)
    for b, t in ((0, 40), (0, 63), (1, 30), (1, 50)):
        best = np.argsort(-np.where(s[b, t] == 0, 0.0, s[b, t]), kind="stable")[:topk]
        assert set(np.flatnonzero(live[b, t])) == set(best)


@pytest.mark.parametrize("length", [1024, 2048])
@pytest.mark.parametrize("topk", [256, 200, 1024])
def test_the_selection_kernel_is_the_oracle_bit_for_bit(topk, length):
    """`select_top_k` in interpret mode against `lax.top_k`: random scores, scores
    with many equal values (ties inside and across the threshold, zeros of both
    signs), `topk` a multiple of the block, not one, and the view's length; at
    2 048 tokens a block reaches under the diagonal by one to four key chunks.
    The second batch row's last block rates every key alike: its threshold ties
    past its room, and the tie-break takes each row's lowest positions."""
    raw = jax.random.normal(jax.random.key(1), (2, length, length)) * 3
    tied = (jnp.round(raw) / 2).at[:, :, ::7].set(-0.0).at[:, 700:, 5::11].set(0.0)
    tied = tied.at[1, -ps.ROWS:].set(0.5)
    for scores in (raw, tied):
        got = np.asarray(ps.select_top_k(scores, topk, interpret=True))
        assert (got == np.asarray(keye.top_k_selection(scores, topk))).all()
        assert (got.sum(-1) == expected_sizes(length, topk)).all()
    for t in range(length - ps.ROWS, length):
        assert (np.flatnonzero(got[1, t]) == np.arange(min(topk, t + 1))).all()


@pytest.mark.parametrize("test", ["not_under", "over", "equal_before"])
def test_the_selections_count_reduces_once_a_pass_as_once_a_chunk(test):
    """`_count` adds a pass's hits into lane partial sums and reduces across the
    lanes after the pass's last chunk: the same counts as reduced after every
    chunk, and as numpy's, on a block of `ROWS` queries by 4 key chunks of small
    keys (many equal), thresholds that differ by row, over the first 1 to 4
    chunks (a program each, so the chunks counted are traced, as in the kernel)."""
    rows, chunks, lanes = ps.ROWS, 4, ps.TILE
    keys = jax.random.randint(jax.random.key(2), (chunks, rows, ps.CHUNK), -8, 8, jnp.int32)
    at_row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    thr = jnp.broadcast_to(at_row % 16 - 8, (rows, lanes))
    cand = jnp.broadcast_to(at_row * 13 % (chunks * ps.CHUNK), (rows, lanes))
    preds = {"not_under": lambda key, at, thr, cand: key >= thr,
             "over": lambda key, at, thr, cand: key > thr,
             "equal_before": lambda key, at, thr, cand: (key == thr) & (at < cand)}
    pred = preds[test]

    def kernel(key_ref, thr_ref, cand_ref, o_ref):
        under = pl.program_id(0) + 1

        def hit(key, at):
            return pred(key, at, thr_ref[...], cand_ref[...])

        once = ps._count(key_ref, hit, 0, under)
        every = ps._count(key_ref, hit, 0, 1)
        for c in range(1, chunks):
            every = every + jnp.where(c < under, ps._count(key_ref, hit, c, c + 1), 0)
        o_ref[0, 0], o_ref[0, 1] = once, every

    whole = pl.BlockSpec((chunks, rows, ps.CHUNK), lambda i: (0, 0, 0))
    lane_block = pl.BlockSpec((rows, lanes), lambda i: (0, 0))
    got = np.asarray(pl.pallas_call(
        kernel, grid=(chunks,), in_specs=[whole, lane_block, lane_block],
        out_specs=pl.BlockSpec((1, 2, rows, lanes), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks, 2, rows, lanes), jnp.float32),
        interpret=True)(keys, thr, cand))
    at = (np.arange(chunks)[:, None, None] * ps.CHUNK + np.arange(ps.CHUNK)).repeat(rows, 1)
    hits = np.asarray(pred(keys, at, thr[:, :1][None], cand[:, :1][None]))
    for under in range(1, chunks + 1):
        want = hits[:under].sum((0, 2))[:, None]
        assert (got[under - 1] == want).all()          # both forms, on every lane
    assert len({int(got[i, 0].sum()) for i in range(chunks)}) > 1


def test_the_scores_kernel_is_the_einsum_under_the_diagonal():
    b, length, heads, dim = 2, 1024, Z["index_heads"], 64
    q, k, w = (jax.random.normal(jax.random.key(i), shape) for i, shape in enumerate(
        ((b, length, heads, dim), (b, length, dim), (b, length, heads))))
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    got, want = ps.index_scores(q, k, w, interpret=True), keye.causal_scores(q, k, w)
    causal = np.tril(np.ones((length, length), bool))
    np.testing.assert_allclose(np.where(causal, got, 0), np.where(causal, want, 0),
                               rtol=1e-5, atol=1e-4)
    # a tile wholly above the diagonal costs nothing and reads zero
    assert not np.asarray(got)[:, :ps.Q_ROWS, ps.KEY_COLS:].any()


def test_the_dispatch_rule_of_the_selection():
    assert ps.select_plan(8192, 2048, 64, "tpu") == "kernels"
    assert ps.select_plan(8192, 2048, 64, "cpu") == "xla"
    assert ps.select_plan(LENGTH, 16, 8, "tpu") == "xla"            # keye_tiny: no whole key column
    assert ps.select_plan(8192, 2048, 64) == "xla"                  # the tests' CPU
    assert models.attention_path("keye_tiny", LENGTH) == {
        "path": "einsum", "tiles": 1, "tiles_skipped": 0, "qk_prep": "xla",
        "select": {"topk": 16, "path": "xla"}}


def test_the_cell_takes_the_kernels_on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert models.attention_path("keye_vl2_30b_a3b", 8192) == {
        "path": "tiled", "tiles": 4096, "tiles_skipped": 1920, "qk_prep": "fused",
        "select": {"topk": 2048, "path": "kernels"}}
    # a mask of positions takes the whole-row kernel up to its longest view, the
    # tiled pair beyond it; a selection always takes the tiled pair
    assert models.attention_path("sdar_30b_a3b", 1024)["path"] == "fused"
    assert models.attention_path("sdar_30b_a3b", 8192)["path"] == "tiled"
    assert models.attention_path("ouro_2p6b", 4096) == {
        "path": "tiled", "tiles": 1024, "tiles_skipped": 448, "qk_prep": "rotary"}
    assert pa.attention_plan(512, 128, 1, masked=True)["path"] == "tiled"
    assert pa.attention_plan(384, 128, 1, masked=True)["path"] == "einsum"   # no whole q tile


# -- the attention kernels under a mask that is an operand ------------------------


def _mask(kind, b, length, seed):
    causal = np.tril(np.ones((length, length), bool))
    if kind == "causal":
        return jnp.asarray(causal[None].astype(np.int8))             # one for every row
    live = causal & np.asarray(jax.random.bernoulli(jax.random.key(seed), 0.3,
                                                    (1 if kind == "shared" else b, length, length)))
    if kind == "dead_tiles":       # the later half never looks at the second quarter
        live[:, length // 2:, length // 4: length // 2] = False
    if kind == "dead_start":       # the last chunk's rows see nothing in the chunks before it
        live[:, -pa.KEY_CHUNK:, :-pa.KEY_CHUNK] = False
    at = np.arange(length)
    live[:, at, np.maximum(at - 3, 0)] = True                        # every query sees a key
    if kind == "late_max":
        live[:, at, at] = True                                       # the planted key, below
    return jnp.asarray(live.astype(np.int8))


def _planted(q, k, heads, kv, dim):
    """k where each query's own position holds 0.6 times its first head of the
    group: that head's largest live score (about 6.8 where the others are about
    N(0, 1)) comes in the last key chunk the row reaches, after every other."""
    group = heads // kv
    firsts = q.reshape(*q.shape[:2], heads, dim)[:, :, ::group]         # [b, L, kv, dim]
    return 0.6 * firsts.reshape(k.shape)


def _lse_oracle(q, k, live, heads, kv, dim):
    """log sum exp(q k^T / sqrt(D)) over each row's live keys: `[b, heads, 1, L]`."""
    b, length = q.shape[:2]
    qh = q.reshape(b, length, kv, heads // kv, dim)
    s = jnp.einsum("btgnd,bsgd->bgnts", qh, k.reshape(b, length, kv, dim),
                   precision="highest") / np.sqrt(dim)
    s = jnp.where(live[:, None, None].astype(bool), s, -jnp.inf)
    return jax.nn.logsumexp(s, -1).reshape(b, heads, 1, length)


@pytest.mark.parametrize("length, kind", [(256, "random"), (1024, "random"),
                                          (1024, "dead_tiles"), (1024, "causal"),
                                          (512, "random"),          # one key chunk
                                          (1024, "shared"),         # one random mask for every row
                                          (1536, "late_max"), (1536, "dead_start")])
def test_masked_attention_is_the_einsum_under_the_same_mask(length, kind):
    """The output, its log-sum-exp (what the backward kernel reads) and the
    gradients. `late_max`: each row's largest score of one head arrives in the
    last key chunk, so the running statistics are rescaled there; `dead_start`:
    the last chunk's rows see no key of the chunks before theirs, so their
    running maximum is `MASKED` until the chunk on the diagonal."""
    b, heads, kv, dim = 2, 32, 4, 128
    q, k, v, g = (jax.random.normal(jax.random.key(i), (b, length, n * dim)) for i, n in
                  enumerate((heads, kv, kv, heads)))
    if kind == "late_max":
        k = _planted(q, k, heads, kv, dim)
    live = _mask(kind, b, length, 7)

    def kernel(q, k, v):
        return pa.masked_attention(q, k, v, live, heads=heads, kv_heads=kv, interpret=True)

    def split(x, n):
        return x.reshape(b, length, n, dim)

    def oracle(q, k, v, live=live):
        return sdar.einsum_attention(split(q, heads), split(k, kv), split(v, kv), 1,
                                     live).reshape(b, length, heads * dim)

    (got, vjp), (want, vjp_oracle) = jax.vjp(kernel, q, k, v), jax.vjp(oracle, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, o in zip(vjp(g), vjp_oracle(g)):
        np.testing.assert_allclose(a, o, rtol=2e-4, atol=2e-4)
    lse = pa._tiled_forward(q, k, v, live, heads, kv, True, True)[1]
    want_lse = _lse_oracle(q, k, live, heads, kv, dim)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-6, atol=2e-5)
    if kind == "late_max":   # a group's first head finds each row's largest score on its own position
        firsts = q.reshape(b, length, heads, dim)[:, :, ::heads // kv]
        s = jnp.einsum("btgd,bsgd->bgts", firsts, k.reshape(b, length, kv, dim))
        s = jnp.where(live[:, None].astype(bool), s, -jnp.inf)
        assert (jnp.argmax(s, -1) == jnp.arange(length)).all()
    if kind == "causal":     # the mask of positions as an operand is the mask of positions
        np.testing.assert_allclose(got, oracle(q, k, v, None), rtol=2e-5, atol=2e-5)


# -- the encoder -------------------------------------------------------------------


def _encoders(topk_all=False):
    """`keye_tiny` (with `index_topk` at the view's length where asked), the SDAR
    encoder of the same sizes at `block_length` 1, and keye's parameters."""
    z = dict(Z, index_topk=LENGTH) if topk_all else dict(Z)
    selecting = sdar.SDAREncoder(tuple(sorted(z.items())), z["layers"], 4, 64, num_classes=8,
                                 attention=keye.selecting_attention)
    plain = sdar.SDAREncoder(tuple(sorted(z.items())), z["layers"], 4, 64, num_classes=8)
    ids = jax.random.randint(jax.random.key(3), (2, LENGTH), 0, 64)
    params = selecting.init(jax.random.key(4), ids)["params"]
    return selecting, plain, params, ids


def _without_indexer(params):
    def strip(layer):
        return {k: v for k, v in layer.items() if k != "indexer"}

    return {name: strip(v) if name.startswith("layer_") else v for name, v in params.items()}


def test_with_topk_at_the_views_length_the_encoder_is_sdars_at_block_length_one():
    """Ties the family to the shared layer: where every causal key is selected the
    indexer chooses nothing, and the encoder's outputs and gradients are
    `models/sdar.py`'s with the same weights."""
    selecting, plain, params, ids = _encoders(topk_all=True)
    shared = _without_indexer(params)

    def loss(model, p):
        return jnp.sum(jnp.square(model.apply({"params": p}, ids)))

    a, ga = jax.value_and_grad(lambda p: loss(selecting, p))(params)
    b, gb = jax.value_and_grad(lambda p: loss(plain, p))(shared)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    for x, y in zip(jax.tree.leaves(_without_indexer(ga)), jax.tree.leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)
    # and the selection passes the indexer no gradient
    assert not any(np.asarray(g).any() for g in jax.tree.leaves(ga["layer_0"]["indexer"]))


def test_the_selection_changes_the_encoder_and_counts_what_it_selected():
    selecting, plain, params, ids = _encoders()
    out, counted = selecting.apply({"params": params}, ids, mutable=list(keye.STAT_COLLECTIONS))
    assert np.abs(np.asarray(out) - np.asarray(plain.apply(
        {"params": _without_indexer(params)}, ids))).max() > 1e-4
    health = keye.health(counted, ids.size)
    assert float(health["h_sel_keys_per_query"]) == pytest.approx(
        expected_sizes(LENGTH, Z["index_topk"]).mean())
    assert float(health["h_sel_live_tile_share"]) == 1.0        # one tile a view here
    assert "h_moe_assign_per_token" in health


def test_live_tile_share_counts_tiles_on_or_under_the_diagonal():
    live = np.zeros((1, 384, 384), np.int8)
    live[0, np.arange(384), np.arange(384)] = 1          # the diagonal alone: 3 of 6 tiles
    assert float(keye.live_tile_share(jnp.asarray(live))) == pytest.approx(0.5)
    live[0, 300, 5] = 1
    assert float(keye.live_tile_share(jnp.asarray(live))) == pytest.approx(4 / 6)


def test_the_family_comes_through_the_door():
    assert models.is_token_encoder("keye_tiny") and models.has_router("keye_tiny")
    assert models.token_sizes("keye_vl2_30b_a3b")["index_topk"] == 2048
    assert models.constant_modules("keye_tiny", 4) == ("router", "indexer")
    assert models.constant_modules("keye_tiny") == ("indexer",)           # the whole layer
    assert models.constant_modules("sdar_tiny", 4) == ("router",)
    assert models.constant_modules("sdar_tiny") == () == models.constant_modules("ouro_tiny")
    collections, reduce = models.token_counters("keye_tiny")
    assert collections == (sdar.MOE_STATS, keye.SEL_STATS) and reduce is keye.health
    assert models.dispatch_path("keye_tiny", 2, LENGTH, 4)["dispatch"] == "xla"
    encoder = models.build_token_encoder("keye_tiny", 8, layers=1, held=4, vocab=64)
    assert isinstance(encoder, sdar.SDAREncoder) and encoder.attention is keye.selecting_attention
    with pytest.raises(ValueError, match="unknown keye arch"):
        keye.build("keye_huge")


# -- the step ----------------------------------------------------------------------


def _leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree)


def _paths(tree, prefix=""):
    for k, v in tree.items():
        yield from _paths(v, f"{prefix}{k}/") if isinstance(v, dict) else [prefix + k]


@pytest.fixture(scope="module")
def two_steps():
    fused, state, rows, lengths = build_fused(tiny_config(remat=False), jax.devices()[:1])
    before = jax.device_get(state.params_q)
    state, first = fused(state, rows, lengths, 0)
    state, _ = fused(state, rows, lengths, 1)
    return before, jax.device_get(state.params_q), jax.device_get(state.params_k), first


def test_the_indexers_and_a_shares_routers_leaves_are_constants_of_the_step(two_steps):
    before, q, k, _ = two_steps
    constant = [p for p in _paths(before) if "/indexer/" in p or "/router/" in p]
    assert len(constant) == Z["layers"] * (5 + 1)
    for path in _paths(before):
        moved = np.abs(_leaf(before, path) - _leaf(q, path)).max() > 0
        assert moved == (path not in constant), path
        # the momentum copy follows: it moves with what trains, and copies a constant
        assert (np.abs(_leaf(before, path) - _leaf(k, path)).max() > 0) == moved, path


def test_the_step_counts_the_selection(two_steps):
    metrics = two_steps[3]
    assert float(metrics["h_sel_keys_per_query"]) == pytest.approx(
        expected_sizes(LENGTH, Z["index_topk"]).mean())
    assert 0 < float(metrics["h_sel_live_tile_share"]) <= 1
    assert 0.6 < float(metrics["h_moe_assign_per_token"]) < 1.6


def _one_step(config, kept=sdar.KEPT):
    """The loss, the updated parameters and the queue after one fused step, and
    how often the step's jaxpr holds the indexer's `lax.top_k` (the router's has
    another `k`); `kept` stands in for `sdar.KEPT` while the step is built."""
    with mock.patch.object(sdar, "KEPT", kept):
        fused, state, rows, lengths = build_fused(config, jax.devices()[:1])
        jaxpr = str(jax.make_jaxpr(fused)(state, rows, lengths, 0))
        state, metrics = fused(state, rows, lengths, 0)
    selections = len(re.findall(rf"top_k\[[^\]]*\bk={Z['index_topk']}\b", jaxpr))
    return jax.device_get((metrics["loss"], state.params_q, state.queue)), selections


def test_a_rematerialised_layer_keeps_the_selection_and_changes_no_number():
    """`remat=True` under the policy against `KEPT` emptied (a plain `nn.remat`,
    the program before ISSUE 34): the same loss, parameters and enqueued keys bit
    for bit, from a gradient that selects twice a layer (key forward, query
    forward) and not a third time inside the backward pass; and against
    `remat=False` to float32 rounding. (On this backend attention takes the
    einsums, which name nothing: of `KEPT` the selection alone is carried here;
    the kernels' pair is `test_pallas_attention.py`'s.)"""
    kept, picks = _one_step(tiny_config(remat=True))
    plain, picks_plain = _one_step(tiny_config(remat=True), kept=())
    whole, picks_whole = _one_step(tiny_config(remat=False))
    assert (picks, picks_plain, picks_whole) == (2 * Z["layers"], 3 * Z["layers"], 2 * Z["layers"])
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_setup_events_attn_block_says_what_a_rematerialised_layer_keeps(monkeypatch):
    """`attn.kept`: the names of `sdar.KEPT` that the built program carries and
    their bytes a layer from the shapes; absent without remat, for a path that
    names nothing, and for a family whose remat has no policy."""
    live = {"names": [pa.KEPT_LIVE], "bytes_per_layer": 4 * LENGTH * LENGTH}
    assert models.attention_path("keye_tiny", LENGTH, 4, True)["kept"] == live
    assert "kept" not in models.attention_path("keye_tiny", LENGTH, 4, False)
    assert "kept" not in models.attention_path("sdar_tiny", 16, 4, True)       # einsums: no name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = models.attention_path("keye_vl2_30b_a3b", 8192, 2, True, "bfloat16")
    assert sdar.KEPT == (pa.KEPT_OUT, pa.KEPT_LSE, pa.KEPT_LIVE)
    # o in bfloat16, the log-sum-exp of 32 heads in float32, a byte a pair: 270 532 608
    assert cell["kept"] == {"names": list(sdar.KEPT),
                            "bytes_per_layer": 2 * 8192 * (4096 * 2 + 32 * 4 + 8192)}
    assert "kept" not in models.attention_path("sdar_30b_a3b", 512, 32, True, "bfloat16")  # whole-row
    # a mask of positions on the tiled pair: the kernel's two results, no selection
    assert models.attention_path("sdar_30b_a3b", 8192, 2, True, "bfloat16")["kept"] == {
        "names": [pa.KEPT_OUT, pa.KEPT_LSE], "bytes_per_layer": 2 * 8192 * (4096 * 2 + 32 * 4)}
    assert "kept" not in models.attention_path("ouro_2p6b", 4096, 2, True, "bfloat16")   # no policy


def components(op_name):
    return re.findall(r"[A-Za-z_0-9]+", op_name)


def test_index_and_select_are_siblings_of_attn_under_both_encoder_passes():
    fused, state, rows, lengths = build_fused(tiny_config(), jax.devices()[:1])
    text = fused.lower(state, rows, lengths, 0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert scopes.SPARSE_SCOPES == (scopes.INDEX, scopes.SELECT)
    for scope in scopes.SPARSE_SCOPES:
        for parent in (scopes.K_FWD, scopes.Q_FWD_BWD):
            under = [n for n in names if scope in components(n) and parent in components(n)]
            assert under, (scope, parent)
            assert not [n for n in under if scopes.ATTN in components(n)], (scope, "inside attn")
    # and every instruction of the encoder keeps a nested scope: SDAR's five or these two
    nested = set(scopes.ENCODER_SCOPES) | set(scopes.SPARSE_SCOPES)
    bare = [n for n in names if (scopes.K_FWD in components(n) or scopes.Q_FWD_BWD in components(n))
            and not nested & set(components(n)) and scopes.LOSS_QUEUE not in components(n)]
    assert not [n for n in bare if "dot_general" in n or "top_k" in n], bare[:5]


def test_the_trainers_mfu_counts_the_family_at_the_selected_pairs():
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry.mfu import model_fwd_flops, train_step_flops

    cut = get_preset("text-moco-v2-keye").replace(num_hidden_layers=4, num_experts=16,
                                                   vocab_size=18992)
    per_view = model_fwd_flops("keye_vl2_30b_a3b", 0, embed_dim=128, mlp_head=True, seq_len=8192,
                               num_hidden_layers=4, num_experts=16)
    # 32 768 token-layers x (projections 37.7 + selected pairs 29.4 + router 0.5 +
    # experts 9.4 + the indexer's 12.9 MFLOP)
    assert per_view == pytest.approx(32768 * 89.9e6, rel=0.01)
    assert train_step_flops(cut) == pytest.approx(4 * 2 * per_view)


def test_the_cli_runs_the_long_text_preset(tmp_path):
    import json

    from moco_tpu import train

    train.main(["--preset", "text-moco-v2-keye", "--arch", "keye_tiny", "--num-experts", "4",
                "--vocab-size", "64", "--seq-len", "64", "--batch-size", "8",
                "--num-negatives", "64", "--compute-dtype", "float32", "--epochs", "1",
                "--steps-per-epoch", "3", "--ckpt-dir", "", "--fake-devices", "1",
                "--health-stride", "1", "--telemetry-dir", str(tmp_path)])
    records = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 3 and steps[0]["health"]["sel_keys_per_query"] == pytest.approx(
        expected_sizes(64, 16).mean())
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"]["select"] == {"topk": 16, "path": "xla"}
    # the preset rematerialises its layers: the selection of a device's views of 64 stays
    # (8 views over the devices this process came up with, whatever `--fake-devices` asks)
    assert setup["attn"]["kept"] == {"names": [pa.KEPT_LIVE],
                                     "bytes_per_layer": 8 // jax.device_count() * 64 * 64}


def test_step_program_lowers_for_tpu_with_its_kernels():
    """The long-view program at the published widths exports for the TPU platform
    from the CPU: in each of 4 layers the scores, the selection and the attention
    kernel in the key forward and the query forward (8 each). Their rematerialised
    twin inside the backward pass is gone since the layer keeps the attention's
    output and log-sum-exp and the selection by name (`sdar.KEPT`; 12 each under a
    plain `nn.remat`); the attention's backward (4), `norm_rotary` as SDAR's (24
    and 8: q and k are made again) and the routed layer's row movers at their
    three call sites stay. `test_sdar_encoder.py`'s census is the control: the
    policy touches no other family."""
    from step_lowering import census_for_tpu

    census = census_for_tpu("cell:keye-vl2-30b-a3b-ep8", 1, None, batch_size=2)
    assert {k: census[k] for k in ("index_scores", "select_top_k", "masked_attention_fwd",
                                   "masked_attention_bwd", "qk_norm_rotary",
                                   "qk_norm_rotary_bwd")} == {
        "index_scores": 8, "select_top_k": 8, "masked_attention_fwd": 8,
        "masked_attention_bwd": 4, "qk_norm_rotary": 24, "qk_norm_rotary_bwd": 8}
    assert census["moe_gather"] == 36 and "_fwd_kernel" not in census
