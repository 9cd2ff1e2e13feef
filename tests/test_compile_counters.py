"""ISSUE 25 (d): the compile counters the telemetry puts on every step record,
fed by `jax.monitoring` listeners that live from `RunTelemetry`'s construction
to its `close()`."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from moco_tpu.utils.cache import CompileCounters


@pytest.fixture()
def counters():
    c = CompileCounters()
    yield c
    c.close()


def listeners():
    from jax._src import monitoring

    return (len(monitoring.get_event_duration_listeners()), len(monitoring.get_event_listeners()),
            len(monitoring.get_scalar_listeners()))


def test_first_call_counts_one_second_none_new_shape_one(counters):
    @jax.jit
    def fused_step_like(x):
        return jnp.tanh(x) * 3.0

    x = jnp.ones((7, 5))       # made before the snapshot: its own small programs are not counted
    y = jnp.ones((9, 5))
    n0 = counters.snapshot()["n"]
    fused_step_like(x).block_until_ready()
    first = counters.snapshot()
    assert first["n"] == n0 + 1
    fused_step_like(x).block_until_ready()
    assert counters.snapshot()["n"] == n0 + 1
    fused_step_like(y).block_until_ready()
    third = counters.snapshot()
    assert third["n"] == n0 + 2
    assert third["backend_s"] > first["backend_s"] > 0
    assert third["trace_lower_s"] > 0
    recent = counters.drain_recent()
    assert len(recent) >= 2 and all("fused_step_like" in name for name in recent[-2:])
    assert counters.drain_recent() == []


def test_nested_traces_are_counted_once(counters):
    """A jitted function traced inside another's trace reports its own duration
    too; the counters keep the outermost interval only."""
    raw = []

    def listen(event, duration, **kwargs):
        if event.endswith("jaxpr_trace_duration"):
            raw.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        @jax.jit
        def inner(x):
            return jnp.tanh(x) @ x

        @jax.jit
        def outer(x):
            for _ in range(20):
                x = inner(x) + 1.0
            return x

        before = counters.snapshot()["trace_lower_s"]
        outer(jnp.ones((6, 6))).block_until_ready()
        counted = counters.snapshot()["trace_lower_s"] - before
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(raw) >= 2                      # outer's trace and inner's inside it
    assert 0 < counted < sum(raw) + 1.0       # bounded by the outermost trace plus the lowering
    assert counted >= max(raw)


def test_the_fused_steps_compiles_are_counted_by_name(counters):
    def fused_step(x):
        return x + 1

    def other(x):
        return x + 2

    jax.jit(fused_step)(jnp.zeros(3)).block_until_ready()
    jax.jit(other)(jnp.zeros(3)).block_until_ready()
    snap = counters.snapshot()
    assert snap["fused_step_n"] == 1 and snap["n"] >= 2
    # the step program's own seconds (trace + lower + backend), apart from the rest's
    assert 0 < snap["fused_step_s"] < snap["backend_s"] + snap["trace_lower_s"]
    assert set(snap) == {"n", "backend_s", "trace_lower_s", "cache_hits", "cache_misses",
                         "fused_step_n", "fused_step_s"}


def test_close_unregisters_and_is_idempotent():
    before = listeners()
    c = CompileCounters()
    assert listeners() == tuple(n + 1 for n in before)
    c.close()
    c.close()
    assert listeners() == before
    jax.jit(lambda x: x * 5.0)(jnp.ones(11)).block_until_ready()
    assert c.snapshot()["n"] == 0          # closed before anything compiled: nothing counted


def test_run_telemetry_registers_and_close_removes(tmp_path, mesh8):
    from moco_tpu.config import get_preset
    from moco_tpu.telemetry import RunTelemetry

    before = listeners()
    config = get_preset("cifar10-moco-v1").replace(telemetry_dir=str(tmp_path),
                                                    peak_flops_per_chip=1e12)
    tel = RunTelemetry(config, n_chips=1, n_procs=1, process_index=0, steps_per_epoch=10)
    assert listeners() == tuple(n + 1 for n in before)
    tel.close()
    assert listeners() == before


def step_records(path):
    with open(os.path.join(str(path), "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def tiny(tmp_path, name):
    from moco_tpu.config import get_preset

    return get_preset("cifar10-moco-v1").replace(
        arch="resnet_tiny", cifar_stem=True, image_size=32, num_negatives=256, batch_size=16,
        dataset="synthetic", epochs=1, steps_per_epoch=4, telemetry_dir=str(tmp_path / name),
        knn_monitor=False, ckpt_dir="", compute_dtype="float32", print_freq=100)


@pytest.mark.parametrize("variant", ["v1", "v3"])
def test_the_initial_state_is_two_programs(tmp_path, counters, variant):
    """ISSUE 26: `create_train_state` / `create_v3_train_state` compile the
    initialiser and `tx.init`, not one program a primitive of `model.init`
    (the tiny encoder's eager initialisation was 69 of them)."""
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import build_encoder, build_optimizer
    from moco_tpu.v3_step import create_v3_train_state

    jax.clear_caches()      # an equal model initialised earlier in this process would be found again
    config = tiny(tmp_path, "n").replace(variant=variant)
    model = build_encoder(config)
    tx, _ = build_optimizer(config, steps_per_epoch=4)
    key = jax.random.key(0)
    jax.block_until_ready(key)          # the key's own programs are the caller's
    shape = (config.batch_size, config.image_size, config.image_size, 3)
    n0 = counters.snapshot()["n"]
    counters.drain_recent()
    if variant == "v3":
        state = create_v3_train_state(key, model, tx, shape)
    else:
        state = create_train_state(key, model, tx, shape, config.num_negatives, config.embed_dim)
    assert counters.snapshot()["n"] - n0 == 2
    names = counters.drain_recent()
    assert len(names) == 2 and "init" in names[0] and "fused_step" not in "".join(names)
    assert len(jax.tree.leaves(state)) > 20


def test_a_second_train_in_the_process_counts_from_its_own_zero(tmp_path):
    """Both runs compile nothing they share with the counters of the other: the
    second finds every program in jax's in-memory caches and reads far fewer
    compiles than the first, not the first's carried on."""
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.telemetry import scopes
    from moco_tpu.train import train

    mesh = create_mesh(devices=jax.devices()[:1])
    before = listeners()
    blocks = []
    jax.clear_caches()      # whatever this process ran before, the first run starts from nothing
    for name in ("a", "b"):
        train(tiny(tmp_path, name), mesh)
        assert listeners() == before            # unregistered with the run's telemetry
        records = step_records(tmp_path / name)
        steps = [r for r in records if r.get("kind") == "step"]
        assert [r["step"] for r in steps] == [1, 2, 3, 4]
        assert all("compile" in r for r in steps)
        ns = [r["compile"]["n"] for r in steps]
        assert ns == sorted(ns)                 # cumulative
        setup = [r for r in records if r.get("event") == "setup"]
        assert len(setup) == 1 and "model_init" in setup[0]["spans"]
        assert {"create_train_state", "opt_init", "place_state", "build_step",
                "first_batch"} <= set(setup[0]["spans"]) <= set(scopes.SETUP_SPANS)
        end = [r for r in records if r.get("kind") == "run_end"][0]
        assert end["compile"]["n"] >= ns[-1]
        blocks.append(steps[-1]["compile"])
    first, second = blocks
    # the pass runs under its mesh, so the initial state is typed as the step's
    # outputs are: step 2 finds step 1's program
    assert first["fused_step_n"] == 1
    # what is left beside them since ISSUE 26: the two programs of the initial
    # state (`test_the_initial_state_is_two_programs`) and the seed's key
    assert 4 <= first["n"] < 12, first
    assert second["n"] < first["n"]

    def beside_the_step_s(block):
        return block["backend_s"] + block["trace_lower_s"] - block["fused_step_s"]

    # the second run finds the initialiser in jit's cache; the step program,
    # a new closure, it traces and compiles again
    assert beside_the_step_s(second) < beside_the_step_s(first)
    # nothing compiled after step 2 in either run: no `compile` event
    for name in ("a", "b"):
        assert not [r for r in step_records(tmp_path / name) if r.get("event") == "compile"]


def swapping_builder(real_builder):
    """A wrapper of the step as the benchmark's `StepHook` is: in its first call
    it puts in parameters and a queue made by `jax.jit`s that name no mesh."""
    calls = {"n": 0}

    def build(step_fn, two_crops_fn, data_key):
        fused = real_builder(step_fn, two_crops_fn, data_key)

        def call(state, imgs, extents, step):
            if calls["n"] == 0:
                fresh = jax.jit(lambda t: jax.tree.map(lambda x: x * 0.5 + 0.01, t))
                state = state.replace(
                    params_q=fresh(state.params_q),
                    params_k=jax.jit(lambda t: jax.tree.map(jnp.copy, t))(state.params_k),
                    queue=jax.jit(jnp.copy)(state.queue))
            calls["n"] += 1
            return fused(state, imgs, extents, step)
        return call
    return build


@pytest.mark.parametrize("case, loads", [("swapped_in_first_call", 1), ("v3", 1), ("fsdp_2", 1),
                                         ("zero_2", 2)])
def test_the_step_program_is_loaded_once(tmp_path, monkeypatch, case, loads):
    """ISSUE 36: under the pass's mesh the first call of the fused step sees the
    state type every later call sees, so jit traces, lowers and loads it once:
    with leaves a wrapper makes inside the first call, for the v3 step and with
    the state placed on two devices by fsdp. ZeRO-1 on two devices loads it
    twice: the step returns `params_q` sharded over `data` like the optimizer
    state it went in beside, where they went in replicated, so step 2 brings
    another placement."""
    from moco_tpu import train_step
    from moco_tpu.config import get_preset
    from moco_tpu.parallel.mesh import create_mesh
    from moco_tpu.train import train

    config = tiny(tmp_path, case).replace(steps_per_epoch=3)
    n_devices = 2 if case.endswith("_2") else 1
    if case == "swapped_in_first_call":
        monkeypatch.setattr(train_step, "build_fused_step",
                            swapping_builder(train_step.build_fused_step))
    elif case == "v3":
        config = config.replace(variant="v3")
    elif case == "fsdp_2":
        config = get_preset("imagenet-moco-v3-vits").replace(
            arch="vit_tiny", compute_dtype="float32", image_size=32, batch_size=16, embed_dim=16,
            dataset="synthetic", warmup_epochs=0, epochs=1, steps_per_epoch=3, sharding="fsdp",
            knn_monitor=False, ckpt_dir="", print_freq=100, telemetry_dir=config.telemetry_dir)
    else:
        config = config.replace(zero_sharding=True)
    train(config, create_mesh(devices=jax.devices()[:n_devices]))
    steps = [r for r in step_records(tmp_path / case) if r.get("kind") == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3]
    assert [r["compile"]["fused_step_n"] for r in steps] == [1, loads, loads]
