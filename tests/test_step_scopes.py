"""ISSUE 25 (a): the names inside the step program. For the tiny v2 and v3
proxies on a two-device mesh, the compiled fused step's HLO metadata holds
every top-level scope, every collective scope nested beneath them, and every
instruction the program's own code gave rise to lies under one of the five."""

import re

import jax
import pytest

from moco_tpu.telemetry import scopes

COLLECTIVES = {"v2": (scopes.SHUFFLE_BN, scopes.KEY_GATHER, scopes.GRAD_SYNC),
               "v3": (scopes.KEY_GATHER, scopes.GRAD_SYNC)}
# the collective scope nests beneath this top-level one
PARENT = {("v2", scopes.SHUFFLE_BN): scopes.K_FWD, ("v2", scopes.KEY_GATHER): scopes.K_FWD,
          ("v2", scopes.GRAD_SYNC): scopes.OPT_EMA, ("v3", scopes.KEY_GATHER): scopes.LOSS_QUEUE,
          ("v3", scopes.GRAD_SYNC): scopes.OPT_EMA}
_HLO: dict = {}


def tiny_config(variant: str):
    from moco_tpu.config import get_preset

    if variant == "v3":
        return get_preset("imagenet-moco-v3-vits").replace(
            arch="vit_tiny", image_size=32, batch_size=8, compute_dtype="float32")
    return get_preset("imagenet-moco-v2").replace(
        arch="resnet_tiny", cifar_stem=True, image_size=32, num_negatives=256, batch_size=8,
        compute_dtype="float32")


def op_names(variant: str) -> list:
    """`op_name` of every instruction of the compiled step on two devices."""
    if variant not in _HLO:
        from moco_tpu.parallel.mesh import create_mesh
        from moco_tpu.utils.benchkit import build_v2_fused_bench

        mesh = create_mesh(devices=jax.devices()[:2])
        fused, state, imgs, extents = build_v2_fused_bench(tiny_config(variant), mesh)
        text = fused.lower(state, imgs, extents, 0).compile().as_text()
        _HLO[variant] = re.findall(r'op_name="([^"]*)"', text)
    return _HLO[variant]


def components(op_name: str) -> list:
    return re.findall(r"[A-Za-z0-9_]+", op_name)


def test_scope_names_are_distinct_and_plain():
    names = scopes.STEP_SCOPES + scopes.COLLECTIVE_SCOPES
    assert len(set(names)) == len(names) == 8
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names)


@pytest.mark.parametrize("scope", scopes.STEP_SCOPES)
@pytest.mark.parametrize("variant", ["v2", "v3"])
def test_compiled_step_holds_every_top_level_scope(variant, scope):
    own = [n for n in op_names(variant) if n.startswith("jit(fused_step)")]
    assert any(scope in components(n) for n in own), scope


@pytest.mark.parametrize("variant,scope", [(v, s) for v in COLLECTIVES for s in COLLECTIVES[v]])
def test_collective_scopes_nest_beneath_their_top_level_scope(variant, scope):
    hits = [components(n) for n in op_names(variant) if scope in components(n)
            and n.startswith("jit(fused_step)")]
    assert hits, scope
    parent = PARENT[variant, scope]
    for parts in hits:
        assert parent in parts[: parts.index(scope)], parts


@pytest.mark.parametrize("variant", ["v2", "v3"])
def test_every_instruction_of_the_programs_own_code_is_under_a_scope(variant):
    """Past the call path (`jit(...)`, `shard_map`) an `op_name` names the
    program's own code, and one of the five is in it. What stops at the call path
    the compiler made at a call's boundary (hoisted constants and their
    broadcasts, layout changes of a region's operands): nothing of the program's."""
    unscoped, seen = [], 0
    for name in op_names(variant):
        if not name.startswith("jit(fused_step)"):
            continue   # a called computation's instruction (a reduction's adder): relative path
        parts = [p for p in name.split("/") if not re.fullmatch(r"(jit|pjit)\(.*\)|shard_map", p)]
        if not parts or re.fullmatch(r"[a-z_\-]+\.\d+", parts[0]):   # `broadcast.215`: XLA's own
            continue
        seen += 1
        if not any(p in scopes.STEP_SCOPES for p in components(name)):
            unscoped.append(name)
    assert seen > 500 and not unscoped, unscoped[:10]


@pytest.mark.parametrize("variant", ["v2", "v3"])
def test_backward_and_loss_paths_read_as_the_reducer_expects(variant):
    """A backward operation's path wraps the scope in `transpose(jvp(...))`, and
    `loss_queue` sits inside `q_fwd_bwd`'s path: innermost recognised name wins."""
    names = [n for n in op_names(variant) if n.startswith("jit(fused_step)")]
    backward = [n for n in names if "transpose(" in n and scopes.Q_FWD_BWD in components(n)]
    assert backward
    inner = [components(n) for n in names if scopes.LOSS_QUEUE in components(n)
             and scopes.Q_FWD_BWD in components(n)]
    assert inner and all(p.index(scopes.Q_FWD_BWD) < p.index(scopes.LOSS_QUEUE) for p in inner)
