"""The fused step of a configuration, lowered for the TPU platform from the CPU.

`lowered_for_tpu(config, mesh)` builds what `train.train()` builds (encoder,
optimizer, abstract state, the step, the views, `build_fused_step`) with
`jax.default_backend` patched to "tpu", which is all that the two kernel
gates ask (`data/augment.py::_use_pallas_blur`, `ops/pallas_attention.py::
attention_plan`), and exports it with `platforms=["tpu"]`: the program the
chip would be handed, without the chip. A tracing or typing break of a step
program fails in tier-1, not in a chip call. `kernel_census` counts its
Mosaic calls by kernel name; `census_for_tpu` is the two for a configuration
named as the tests' cases name it.
"""

import json
import os
import re
import unittest.mock as mock
from collections import Counter

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS_PER_EPOCH = 1000


def cell_config(name: str, **over):
    """`perfbench/configs/<name>.json`'s `trainer` group laid over its preset,
    as `perfbench/harness.py::trainer_config` does (the file is read, not
    imported: the benchmark stays outside the tests of the program)."""
    import dataclasses

    from moco_tpu.config import get_preset

    with open(os.path.join(REPO, "perfbench", "configs", name + ".json"), encoding="utf-8") as f:
        file = json.load(f)
    preset = get_preset(file["preset"])
    fields = {f.name for f in dataclasses.fields(preset)}
    trainer = {k: (tuple(v) if isinstance(v, list) else v)
               for k, v in file["trainer"].items() if k in fields}
    return preset.replace(**{**trainer, **over})


def named_config(name: str, **over):
    """A `PretrainConfig` preset, or `cell:<file>` for a benchmark configuration."""
    from moco_tpu.config import get_preset

    if name.startswith("cell:"):
        return cell_config(name[5:], **over)
    return get_preset(name).replace(**over)


def lowered_for_tpu(config, mesh) -> str:
    from moco_tpu.data.augment import (aug_config_for, build_token_views_sharded,
                                       build_two_crops_sharded, token_view_config_for,
                                       with_dtype)
    from moco_tpu.models import is_token_encoder
    from moco_tpu.train_state import create_train_state
    from moco_tpu.train_step import (build_encoder, build_fused_step, build_optimizer,
                                     build_train_step)

    batch, local = config.batch_size, config.batch_size // mesh.size
    tokens = is_token_encoder(config.arch)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = build_encoder(config)
        tx, sched = build_optimizer(config, STEPS_PER_EPOCH)
        if config.variant == "v3":
            from moco_tpu.v3_step import create_v3_train_state

            state = jax.eval_shape(lambda: create_v3_train_state(
                jax.random.key(0), model, tx,
                (local, config.image_size, config.image_size, 3)))
        else:
            state = jax.eval_shape(lambda: create_train_state(
                jax.random.key(0), model, tx,
                (local, config.seq_len) if tokens
                else (local, config.image_size, config.image_size, 3),
                config.num_negatives, config.embed_dim,
                input_dtype=jnp.int32 if tokens else jnp.float32))
        step_fn = build_train_step(config, model, tx, mesh, STEPS_PER_EPOCH, sched)
        if tokens:
            views = build_token_views_sharded(token_view_config_for(config), mesh)
            feed = (jax.ShapeDtypeStruct((batch, 2 * config.seq_len), jnp.int32),
                    jax.ShapeDtypeStruct((batch, 1), jnp.int32))
        else:
            views = build_two_crops_sharded(
                with_dtype(aug_config_for(config), config.compute_dtype), mesh)
            side = config.image_size + config.image_size // 8    # a staging canvas
            feed = (jax.ShapeDtypeStruct((batch, side, side, 3), jnp.uint8),
                    jax.ShapeDtypeStruct((batch, 3), jnp.int32))
        fused = build_fused_step(step_fn, views, jax.random.key(1))
        exported = jax.export.export(fused, platforms=["tpu"])(
            state, *feed, jax.ShapeDtypeStruct((), jnp.int32))
    return exported.mlir_module()


def kernel_census(module_text: str) -> Counter:
    """Mosaic calls by kernel name as `main` reaches them: a kernel inside an
    inner `jax.jit` is lowered once and counts once for every call of it."""
    functions = re.split(r"\n  func\.func ", module_text)[1:]
    names = [re.match(r"(?:public |private )?@([\w.]+)", f).group(1) for f in functions]
    assert names[0] == "main", names[:3]
    bodies = dict(zip(names, functions))
    kernels = {n: Counter(re.findall(r'kernel_name = "([^"]+)"', b)) for n, b in bodies.items()}
    assert sum(sum(k.values()) for k in kernels.values()) == module_text.count(
        "@tpu_custom_call"), "a Mosaic call without a kernel name"

    def reached(name: str) -> Counter:
        total = Counter(kernels[name])
        for callee, n in Counter(re.findall(r"call @([\w.]+)", bodies[name])).items():
            if callee in bodies:
                for kernel, k in reached(callee).items():
                    total[kernel] += n * k
        return total

    return reached("main")


def census_for_tpu(name: str, devices: int, mesh8, **over) -> Counter:
    """`over` cuts the batch to what traces fast: the program's structure does
    not depend on it."""
    from moco_tpu.parallel.mesh import create_mesh

    mesh = mesh8 if devices == 8 else create_mesh(1)
    return kernel_census(lowered_for_tpu(named_config(name, **over), mesh))
