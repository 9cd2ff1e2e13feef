"""Device mesh + process topology (layer L0/L1 of SURVEY.md §1).

The reference scales with one POSIX process per GPU launched by `mp.spawn`
and a NCCL process group (`main_moco.py:≈L114-155`). TPU-native equivalent:
a single controller process per *host* drives all local chips, the SPMD
program is compiled once over a `jax.sharding.Mesh`, and multi-host
bootstrap is `jax.distributed.initialize()` (replacing the tcp:// / env://
rendezvous of `torch.distributed.init_process_group`). Collectives are
compiled into the step program over ICI/DCN — there is no user-visible
process-group object.

MoCo's only parallelism is data parallelism (SURVEY.md §2.11), so the mesh
is 1-D over `DATA_AXIS`. TP/PP/EP are structurally absent from the reference
and deliberately not built (SURVEY.md §7 non-goals); pjit makes them
available later by re-sharding if ever needed.
"""

from __future__ import annotations

import os
import re
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The primary mesh axis. On the 1-D data-parallel mesh (the seed layout)
# the batch dim is sharded over it and params/queue/opt-state are
# replicated. ISSUE 15 adds a second, FSDP axis: on the 2-D mesh the batch
# shards over BOTH axes (data parallelism spans every device) while
# params/optimizer state shard over the fsdp axis only — the fast
# intra-pod axis on real hardware, so the per-step param all-gathers ride
# ICI while the (optionally quantized) inter-pod grad hop rides DCN.
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"

# PretrainConfig.sharding values (mirrored as literals in config.py, which
# must stay importable without jax)
SHARDING_MODES = ("dp", "fsdp", "fsdp_tp")


def force_cpu_devices(n: int = 8) -> None:
    """Force this process onto `n` fake CPU devices (test/simulation mode).

    Replaces the reference's "just run it on 8 V100s" validation story
    (SURVEY.md §4): `--xla_force_host_platform_device_count=N` gives N real
    XLA CPU devices in one process with real all_gather/psum/ppermute
    semantics. Must run before the first JAX backend query. (The env vars
    alone would do for a fresh process; the in-process config update also
    covers a caller that imported jax with another platform configured.)

    An explicit `n` REPLACES any count already present in XLA_FLAGS: an
    elastic resize relaunch (ISSUE 11) passes the NEW count via
    `--fake-devices` while the child env still carries the old
    incarnation's flags — respecting the stale value would silently pin
    every relaunch to the original mesh and make the resize a no-op.
    (Still before the first backend query, as ever: once the CPU client
    exists the count is baked.)
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            f"--xla_force_host_platform_device_count={n}", flags,
        )
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def distributed_init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bootstrap (replaces `dist.init_process_group`, SURVEY §5.8).

    On Cloud TPU all three args are auto-detected from the metadata server
    (pass nothing); explicit args support manual rendezvous. Callers invoke
    this only for multi-host jobs (the train driver's `--multihost` path) —
    `num_processes=1` is the explicit single-process no-op.
    """
    if num_processes == 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def create_mesh(num_devices: int | None = None, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the 1-D data-parallel mesh over all (or the first N) devices."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} present"
            )
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def create_mesh_2d(
    fsdp_size: int,
    num_devices: int | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """The 2-D (data, fsdp) mesh (ISSUE 15). `fsdp_size` devices form each
    param-shard group (the INNER, fast axis); the outer data axis carries
    plain replica parallelism across groups. Device order is preserved
    from the flat list, so a (1, N) mesh reduces over exactly the same
    device sequence as the 1-D mesh — the bitwise-parity anchor the fsdp
    tests pin."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} present"
            )
        devices = devices[:num_devices]
    n = len(devices)
    if fsdp_size < 1 or n % fsdp_size != 0:
        raise ValueError(
            f"fsdp axis size {fsdp_size} must divide the device count {n}"
        )
    return Mesh(
        np.asarray(devices).reshape(n // fsdp_size, fsdp_size),
        (DATA_AXIS, FSDP_AXIS),
    )


def default_fsdp_size(sharding: str, n_devices: int) -> int:
    """The fsdp-axis size a `sharding_axis_size=0` config resolves to:
    all devices for pure fsdp; for fsdp_tp the largest proper divisor
    (e.g. 4 devices → data 2 × fsdp 2, 8 → 2×4) — a placeholder for the
    real intra-pod group size, which `sharding_axis_size` pins on
    hardware whose topology is known."""
    if sharding == "fsdp":
        return n_devices
    for d in range(n_devices // 2, 0, -1):
        if n_devices % d == 0:
            return d
    return 1


def mesh_for_config(config, mesh: Mesh | None = None,
                    num_devices: int | None = None) -> Mesh:
    """The mesh `config.sharding` needs, rebuilt from `mesh`'s own devices
    when the provided one has the wrong axis set (the driver and tests
    hand in the plain 1-D mesh; fsdp runs fold it into the 2-D layout
    without changing the device order)."""
    mode = getattr(config, "sharding", "dp")
    devices = None
    if mesh is not None:
        devices = list(mesh.devices.flat)
    if mode == "dp":
        if mesh is not None and tuple(mesh.axis_names) == (DATA_AXIS,):
            return mesh
        return create_mesh(num_devices, devices=devices)
    n = len(devices) if devices is not None else len(
        jax.devices()[:num_devices] if num_devices else jax.devices())
    fsdp_size = int(getattr(config, "sharding_axis_size", 0)) or \
        default_fsdp_size(mode, n)
    if mode == "fsdp" and fsdp_size != n:
        raise ValueError(
            f"sharding='fsdp' shards over ALL {n} devices; "
            f"sharding_axis_size={fsdp_size} asks for a sub-group — that "
            "is the fsdp_tp hybrid, say so explicitly"
        )
    if (mesh is not None
            and tuple(mesh.axis_names) == (DATA_AXIS, FSDP_AXIS)
            and mesh.shape[FSDP_AXIS] == fsdp_size):
        return mesh
    return create_mesh_2d(fsdp_size, num_devices, devices=devices)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes the global batch shards over — ALL of them: on the
    2-D mesh data parallelism spans every device, the fsdp axis only
    changes where params live."""
    return tuple(str(a) for a in mesh.axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for replicated state (params, queue, opt state)."""
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh) -> NamedSharding:
    """Sharding for a batch: leading dim split over every mesh axis (the
    1-D data axis, or data×fsdp on the 2-D mesh — same global batch
    semantics either way)."""
    return NamedSharding(mesh, P(batch_axes(mesh)))


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-device batch (the reference's `batch_size / ngpus_per_node`,
    `main_moco.py:≈L230`). Global batch must divide evenly: the queue ring
    update requires `K % global_batch == 0` and XLA requires even sharding."""
    n = mesh.size
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by mesh size {n}")
    return global_batch // n
