"""The routed token encoder with latent attention and shared experts
(`models/kanana.py`) at a small size on the CPU, and its attention kernels
(`ops/pallas_mla.py`) in interpret mode against their einsum oracle: the
interleaved rotary is its formula, the sigmoid router's bias moves the choice
and never a weight, the leading dense layer and the scopes stand where the
equations put them, the family comes through the door, and the cell's step
lowers for the TPU with the new kernels."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moco_tpu import models
from moco_tpu.models import kanana, sdar
from moco_tpu.ops import pallas_attention as pa
from moco_tpu.ops import pallas_mla as pm
from moco_tpu.telemetry import scopes
from test_sdar_encoder import build_fused

Z = kanana.KANANA_SIZES["kanana_tiny"]
LENGTH = 32


def tiny_config(**over):
    from moco_tpu.config import get_preset

    return get_preset("text-moco-v2-kanana").replace(
        arch="kanana_tiny", num_hidden_layers=3, num_experts=4, vocab_size=64, seq_len=LENGTH,
        batch_size=4, num_negatives=256, compute_dtype="float32", health_stride=1, **over)


# -- rotary and the router ------------------------------------------------------------


def test_the_interleaved_rotary_is_its_formula():
    """Pairs (2i, 2i + 1) turned by `pos * theta ** (-2i / D)`, written out by
    hand in numpy, and unlike rotate-half on the same input."""
    x = np.asarray(jax.random.normal(jax.random.key(0), (2, 7, 3, 8)), np.float64)
    got = np.asarray(kanana.interleaved_rotary(jnp.asarray(x, jnp.float32), 1e6))
    want = np.empty_like(x)
    for pos in range(7):
        for i in range(4):
            angle = pos * 1e6 ** (-2 * i / 8)
            a, b = x[:, pos, :, 2 * i], x[:, pos, :, 2 * i + 1]
            want[:, pos, :, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, pos, :, 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[:, 0], x[:, 0].astype(np.float32))      # position 0: no turn
    assert np.abs(got - np.asarray(sdar.rotary(jnp.asarray(x, jnp.float32), 1e6))).max() > 0.1


def _router_layer(held=16, z=Z, bias_scale=1.0, seed=0):
    u = jax.random.normal(jax.random.key(seed), (96, z["hidden"]))
    module = sdar.Experts(z["experts"], held, z["top_k"], z["expert_width"], rule="sigmoid",
                          scale=z["routed_scale"], shared=z["shared_width"])
    params = module.init(jax.random.key(seed + 1), u)["params"]
    params["router"]["correction_bias"] = params["router"]["correction_bias"] * bias_scale
    return module, params, u


def _choices(module, params, u):
    _, taps = module.apply({"params": params}, u, mutable=[sdar.MOE_CHOICES])
    return np.asarray(taps[sdar.MOE_CHOICES]["chosen"])


def test_the_bias_moves_the_choice_and_never_the_weight():
    """`T(u)` is the top 6 of `s + b`; the weights come from `s` alone,
    renormalised over the chosen and scaled by 2.448: they sum to 2.448 over
    every token's six. A larger bias changes which experts are chosen; with the
    choice held, the bias changes no weight."""
    module, params, u = _router_layer()
    chosen = _choices(module, params, u)
    unbiased = dict(params, router=dict(params["router"],
                                        correction_bias=jnp.zeros_like(params["router"]["correction_bias"])))
    assert (np.sort(chosen, -1) != np.sort(_choices(module, unbiased, u), -1)).any()
    s = jax.nn.sigmoid(u @ params["router"]["kernel"])
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.asarray(jax.lax.top_k(s + params["router"]["correction_bias"],
                                                             Z["top_k"])[1]), -1))
    w = np.take_along_axis(np.asarray(s), chosen, -1)
    w = Z["routed_scale"] * w / w.sum(-1, keepdims=True)
    np.testing.assert_allclose(w.sum(-1), Z["routed_scale"], rtol=1e-6)
    # the layer's result is the plain sum with those weights (the shared experts apart)
    shared = sdar.SwiGLU(Z["shared_width"]).apply({"params": params["shared"]}, u)
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", u, params["gate"]))
                   * jnp.einsum("td,edf->etf", u, params["up"]), params["down"])
    full = np.zeros((u.shape[0], Z["experts"]), np.float32)
    np.put_along_axis(full, chosen, w, -1)
    plain = jnp.einsum("etd,te->td", y, full) + shared
    np.testing.assert_allclose(module.apply({"params": params}, u), plain, rtol=2e-4, atol=2e-5)
    # the bias has no gradient: it moves the choice, never a weight
    g = jax.grad(lambda p: jnp.sum(module.apply({"params": p}, u) ** 2))(params)
    assert float(jnp.abs(g["router"]["correction_bias"]).max()) == 0.0


def test_held_rows_reserve_twice_the_passs_uniform_count():
    """2 * tokens * top_k * held / experts for the pass, not rounded per token, and
    never under one row a token; rounded up to the dispatch kernels' 128: the
    long-view and routed cells keep their 32 768 rows bit for bit (top_k * held /
    experts = 1 there), this family's cell gets 16 384, one row a token, where
    twice uniform is 12 288 and a round per token gave 32 768."""
    assert sdar.held_rows(32 * 512, 8, 128, 16) == 32768 == sdar.held_rows(2 * 8192, 8, 128, 16)
    assert 2 * 4 * 4096 * 6 * 8 // 128 == 12288
    assert sdar.held_rows(4 * 4096, 6, 128, 8) == 16384 == 4 * 4096                # the floor
    assert sdar.held_rows(4 * 4096, 6, 128, 16) == 24576 == 2 * 4 * 4096 * 6 * 16 // 128
    assert sdar.held_rows(4 * 4096, 6, 128, 128) == 4 * 4096 * 6          # the whole layer
    assert sdar.held_rows(100, 6, 128, 8) == 128                           # rounded to a tile
    assert models.dispatch_path("kanana_2_30b_a3b", 4, 4096, 8)["rows"] == 16384


# -- the kernels against their oracle ------------------------------------------------


def _mla_inputs(b, length, heads, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    shapes = [(b, length, heads * 128), (b, length, heads * 64), (b, length, heads * 128),
              (b, length, 64), (b, length, heads * 128), (b, length, heads * 128)]
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]


def _oracle(heads):
    def attend(qn, qp, kn, kp, v):
        b, length = qn.shape[:2]

        def split(x, d):
            return x.reshape(b, length, heads, d)

        return kanana.latent_einsum_attention(split(qn, 128), split(qp, 64), split(kn, 128), kp,
                                              split(v, 128)).reshape(b, length, heads * 128)
    return attend


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_latent_kernels_are_the_einsum_path_forward_and_backward(dtype):
    """q.k 192 wide (128 + a 64-wide rotary part), v 128, ONE rotary key head for
    all 8 heads, causal, 1 024 tokens: two forward q tiles over two key chunks,
    four backward q tiles, two groups of heads in the backward (the rotary key's
    gradient summed over both): output and all five gradients."""
    heads = 8
    *inputs, g = _mla_inputs(1, 1024, heads, dtype)

    def kernel(*a):
        return pm.causal_latent_attention(*a, heads=heads, interpret=True)

    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(_oracle(heads), *inputs)
        got, got_vjp = jax.vjp(kernel, *inputs)
        pairs = [(got, want)] + list(zip(got_vjp(g), want_vjp(g)))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, (a, b) in zip(["o", "q_nope", "q_pe", "k_nope", "k_pe", "v"], pairs):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (name, np.abs(a - b).max())


def test_the_rotary_key_is_one_head_and_every_query_head_reads_it():
    """A change in k_pe moves every head's output; a change in one head's q_pe
    moves that head's output alone."""
    heads = 4
    *inputs, _ = _mla_inputs(1, 512, heads, jnp.float32, seed=3)

    def run(*a):
        return np.asarray(pm.causal_latent_attention(*a, heads=heads, interpret=True)).reshape(
            512, heads, 128)

    base = run(*inputs)
    kp = inputs[3].at[:, 5:].add(1.0)
    moved = np.abs(run(*inputs[:3], kp, inputs[4]) - base).max((0, 2))
    assert (moved > 1e-3).all()
    qp = inputs[1].at[:, :, 64:128].add(1.0)                 # head 1's rotary part
    moved = np.abs(run(inputs[0], qp, *inputs[2:]) - base).max((0, 2))
    assert moved[1] > 1e-3 and (np.delete(moved, 1) == 0).all()


def test_the_plan_of_the_latent_path():
    assert pm.mla_plan(4096, 128, 64, 128, "tpu") == {"path": "tiled", "tiles": 1024,
                                                      "tiles_skipped": 1024 - 36 * 16}
    assert pm.mla_plan(4096, 128, 64, 128, "cpu")["path"] == "einsum"
    assert pm.mla_plan(1000, 128, 64, 128, "tpu")["path"] == "einsum"     # not whole chunks
    assert pm.mla_plan(4096, 16, 8, 16, "tpu")["path"] == "einsum"        # not whole lane tiles
    assert pm.mla_plan(4096, 128, 32, 128, "tpu")["path"] == "einsum"     # rotary parts do not pair


def test_the_attn_block_reports_the_widths_the_rank_and_what_remat_keeps(monkeypatch):
    block = models.attention_path("kanana_tiny", LENGTH, 4, True)
    assert block == {"path": "einsum", "tiles": 1, "tiles_skipped": 0, "qk_prep": "xla",
                     "qk_dim": 24, "v_dim": 16, "latent_rank": 32}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = models.attention_path("kanana_2_30b_a3b", 4096, 4, True, "bfloat16")
    assert (cell["path"], cell["qk_dim"], cell["v_dim"], cell["latent_rank"]) == ("tiled", 192, 128, 512)
    # the 128-wide o in bfloat16 and the log-sum-exp of 32 heads in float32
    assert cell["kept"] == {"names": [pa.KEPT_OUT, pa.KEPT_LSE],
                            "bytes_per_layer": 4 * 4096 * 32 * (128 * 2 + 4)}
    assert sdar.KEPT[:2] == (pa.KEPT_OUT, pa.KEPT_LSE)


# -- the module and the door -----------------------------------------------------------


def test_the_family_comes_through_the_door():
    assert models.is_token_encoder("kanana_tiny") and models.has_router("kanana_tiny")
    assert models.constant_modules("kanana_tiny", 4) == ("router", "correction_bias")
    assert models.constant_modules("kanana_tiny") == ("correction_bias",)      # the whole layer
    assert models.token_counters("kanana_tiny") == ((sdar.MOE_STATS,), sdar.health)
    assert models.held_vocab("kanana_2_30b_a3b", 16032) == 16032
    encoder = models.build_token_encoder("kanana_tiny", 8, layers=3, held=4, vocab=64)
    assert isinstance(encoder, sdar.SDAREncoder) and encoder.attention is kanana.latent_attention
    assert encoder.ffn is kanana.feed_forward
    with pytest.raises(ValueError, match="unknown kanana arch"):
        kanana.build("kanana_huge")


def test_the_whole_published_model_builds_and_holds_30_billion_parameters():
    """`build_backbone` builds the stack whole (48 layers, 128 experts each after
    the dense one, the whole vocabulary): about 30 B parameters, by shapes alone."""
    model = models.build_backbone("kanana_2_30b_a3b")
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == pytest.approx(30.0e9, rel=0.03)
    layer = shapes["params"]["layer_47"]
    assert layer["moe"]["gate"].shape == (128, 2048, 768)
    assert layer["attn"]["q"]["kernel"].shape == (2048, 32 * 192)
    assert layer["attn"]["kv_b"]["kernel"].shape == (512, 32 * 256)
    assert "mlp" in shapes["params"]["layer_0"] and "moe" not in shapes["params"]["layer_0"]


def test_the_leading_layer_is_dense_and_the_others_routed_with_shared_experts():
    encoder = models.build_token_encoder("kanana_tiny", 8, layers=3, held=4, vocab=64)
    ids = jax.random.randint(jax.random.key(0), (2, LENGTH), 0, 64)
    variables = encoder.init(jax.random.key(1), ids)
    p = variables["params"]
    assert set(p["layer_0"]) == {"norm1", "attn", "norm2", "mlp"}
    assert set(p["layer_1"]) == set(p["layer_2"]) == {"norm1", "attn", "norm2", "moe"}
    assert set(p["layer_1"]["moe"]) == {"router", "gate", "up", "down", "shared"}
    assert set(p["layer_1"]["attn"]) == {"q", "kv_a", "kv_a_norm", "kv_b", "o"}
    _, counted = encoder.apply(variables, ids, mutable=[sdar.MOE_STATS])
    assert set(counted[sdar.MOE_STATS]) == {"layer_1", "layer_2"}           # the routed layers count
    health = sdar.health(counted, 2 * LENGTH)
    # uniform routing sends 3 * 4 / 16 = 0.75 assignments a token to the four held
    assert 0.3 < float(health["h_moe_assign_per_token"]) < 1.5


def _paths(tree, prefix=""):
    for k, v in tree.items():
        yield from _paths(v, f"{prefix}{k}/") if isinstance(v, dict) else [prefix + k]


def test_the_routers_leaves_are_constants_of_the_step_and_the_rest_trains():
    fused, state, rows, lengths = build_fused(tiny_config(remat=False), jax.devices()[:1])
    before = jax.device_get(state.params_q)
    state, metrics = fused(state, rows, lengths, 0)
    state, _ = fused(state, rows, lengths, 1)
    after = jax.device_get(state.params_q)
    constant = [p for p in _paths(before) if "/router/" in p]
    assert len(constant) == 2 * 2                  # kernel and correction bias, two routed layers
    for path in _paths(before):
        a, b = before, after
        for part in path.split("/"):
            a, b = a[part], b[part]
        assert (np.abs(np.asarray(a) - np.asarray(b)).max() > 0) == (path not in constant), path
    assert np.isfinite(float(metrics["loss"]))


def components(op_name):
    return re.findall(r"[A-Za-z_0-9]+", op_name)


def test_the_latent_scope_is_inside_attn_and_the_shared_experts_beside_the_routed():
    fused, state, rows, lengths = build_fused(tiny_config(), jax.devices()[:1])
    text = fused.lower(state, rows, lengths, 0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert scopes.LATENT_SCOPES == (scopes.MLA_LATENT, scopes.MOE_SHARED)
    for parent in (scopes.K_FWD, scopes.Q_FWD_BWD):
        latent = [n for n in names if scopes.MLA_LATENT in components(n) and parent in components(n)]
        assert latent and all(scopes.ATTN in components(n) for n in latent), parent
        shared = [n for n in names if scopes.MOE_SHARED in components(n) and parent in components(n)]
        assert shared and not [n for n in shared
                               if set(components(n)) & set(scopes.ENCODER_SCOPES)], parent
        mlp = [n for n in names if scopes.MLP in components(n) and parent in components(n)]
        assert mlp and all("layer_0" in components(n) for n in mlp), parent


def test_the_trainers_mfu_counts_the_family():
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry.mfu import model_fwd_flops, train_step_flops

    cut = get_preset("text-moco-v2-kanana").replace(num_hidden_layers=5, num_experts=8,
                                                     vocab_size=16032)
    per_view = model_fwd_flops("kanana_2_30b_a3b", 0, embed_dim=128, mlp_head=True, seq_len=4096,
                               num_hidden_layers=5, num_experts=8)
    # 4 096 tokens x (five layers' latent attention, 94.6 MFLOP a token with the
    # scores and mix over the causal half; the dense SwiGLU 75.5; four routed
    # layers' router, shared and held experts, 22.9 each)
    assert per_view == pytest.approx(4096 * 640.5e6, rel=0.002)
    assert train_step_flops(cut) == pytest.approx(4 * 4 * per_view)
    assert train_step_flops(cut) == pytest.approx(42.0e12, rel=0.003)


def test_the_cli_runs_the_preset(tmp_path):
    from moco_tpu import train

    train.main(["--preset", "text-moco-v2-kanana", "--arch", "kanana_tiny", "--num-hidden-layers", "3",
                "--num-experts", "4", "--vocab-size", "64", "--seq-len", str(LENGTH),
                "--batch-size", "8", "--num-negatives", "64", "--compute-dtype", "float32",
                "--epochs", "1", "--steps-per-epoch", "3", "--ckpt-dir", "", "--fake-devices", "1",
                "--health-stride", "1", "--telemetry-dir", str(tmp_path)])
    records = [json.loads(line) for line in open(tmp_path / "events.jsonl")]
    steps = [r for r in records if r.get("kind") == "step"]
    assert len(steps) == 3 and "moe_assign_per_token" in steps[0]["health"]
    setup = next(r for r in records if r.get("event") == "setup")
    assert setup["attn"]["qk_dim"] == 24 and setup["attn"]["latent_rank"] == 32
    assert setup["moe"]["dispatch"] == "xla"


def test_step_program_lowers_for_tpu_with_its_kernels():
    """The cell's program at the published widths exports for the TPU platform
    from the CPU: in each of 5 layers the latent attention's forward kernel in the
    key forward and the query forward (10) and its backward (5): the layer keeps
    the kernel's output and log-sum-exp by name, so the backward pass runs no
    forward again; the routed layer's row movers in the four routed layers."""
    from step_lowering import census_for_tpu

    census = census_for_tpu("cell:kanana-2-30b-a3b-ep16", 1, None, batch_size=4)
    assert {k: census[k] for k in ("mla_attention_fwd", "mla_attention_bwd")} == {
        "mla_attention_fwd": 10, "mla_attention_bwd": 5}
    assert census["moe_gather"] > 0 and "_fwd_kernel" not in census
    assert "masked_attention_fwd" not in census and "qk_norm_rotary" not in census
