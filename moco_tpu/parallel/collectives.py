"""ShuffleBN and gather collectives (TPU-native rebuild of `moco/builder.py`'s
`concat_all_gather` / `_batch_shuffle_ddp` / `_batch_unshuffle_ddp`, SURVEY §2.2).

All functions here are called INSIDE a `jax.shard_map`-mapped step over the
1-D data mesh, so `lax.all_gather(..., axis_name)` compiles to a single XLA
all-gather over ICI. Differences from the NCCL reference, by design:

- The reference generates the shuffle permutation on rank 0 and broadcasts it
  (`moco/builder.py:≈L72-98`, one NCCL broadcast per step). Here every device
  computes the SAME permutation from a shared, replicated PRNG key
  (`jax.random.permutation(key, B)`): deterministic ⇒ consistent ⇒ the
  broadcast disappears entirely (zero comm).
- `concat_all_gather` in the reference is explicitly non-differentiable (it
  is only used under `no_grad`). `lax.all_gather` IS differentiable, so
  callers that need the reference's stop-grad semantics wrap results in
  `lax.stop_gradient` (the train step does this for the key path).

Replication-typing note (jax 0.9): `lax.all_gather` output is typed
"varying" over the mapped axis even though its value is device-invariant
(there is no `all_gather_invariant` in this version). Consequently updates to
REPLICATED state (queue, params) that derive from gathered values must happen
at the outer jit level, outside the shard_map region — the train step is a
hybrid: `jit(outer)` does EMA/optimizer/queue updates under the automatic
partitioner, and the inner `shard_map` region does only the per-device work
(ShuffleBN, forwards, local grads + psum). This keeps `check_vma` on.

Why ShuffleBN exists (SURVEY §0.1): with per-device BatchNorm, the query and
its positive key would share BN statistics if they sat on the same device,
leaking which in-batch sample is the positive. Shuffling the key batch
across devices before the key encoder's forward decorrelates the BN groups;
unshuffling after restores q/k alignment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# Every collective here accepts either one axis name or a TUPLE of names
# (ISSUE 15: the 2-D data×fsdp mesh) — jax's collectives treat a tuple as
# one combined device group in row-major order of the names given, so the
# helpers below define the matching combined size/index once.


def batch_axis_size(axis_name) -> jax.Array | int:
    """Total device count of the (possibly multi-axis) batch group."""
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for ax in axis_name:
            n = n * lax.axis_size(ax)
        return n
    return lax.axis_size(axis_name)


def batch_axis_index(axis_name) -> jax.Array:
    """This device's rank within the combined batch group, row-major in
    the axis order given — by construction the position its tiled
    `all_gather` shard lands at (pinned by tests/test_collectives.py)."""
    if isinstance(axis_name, (tuple, list)):
        idx = jnp.int32(0)
        for ax in axis_name:
            idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
        return idx
    return lax.axis_index(axis_name)


def device_local(tree, axis_name):
    """Retype replicated leaves as device-varying over `axis_name` (a
    no-op at run time). Differentiating w.r.t. a REPLICATED input inside
    shard_map makes autodiff psum the cotangent over the axis by itself
    (the transpose of the implicit replicated→varying cast), so the
    "local" grads would arrive already summed and GradSync's pmean over
    them would be the identity: N× the DDP gradient on N devices
    (measured on jax 0.9.0, PR 21). Differentiating w.r.t. this view
    yields truly per-device grads — the reduce in `parallel/gradsync.py`
    is then the ONLY one. Leaves already varying over an axis (fsdp
    gathers) keep it."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)

    def cast(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


def all_gather_batch(x: jax.Array, axis_name, chunks: int = 1) -> jax.Array:
    """Gather local batch shards into the global batch along dim 0.

    Equivalent of `concat_all_gather` (`moco/builder.py:≈L167-180`) minus the
    stop-grad (callers add it where the reference ran under no_grad).

    `chunks > 1` is the FAST-style schedule (PAPERS.md): the local batch is
    split into `chunks` row slices, each gathered as its OWN collective,
    chained through `optimization_barrier` so they issue as a deterministic
    pipeline — chunk i can be on the wire while the compute feeding chunk
    i+1 still runs, instead of one monolithic end-of-phase gather. The
    reassembled result is BIT-IDENTICAL to the unchunked gather (rows are
    restitched device-major), so the knob is pure scheduling. A chunk
    count the local batch does not divide falls back to the monolithic
    gather (chunking is a hint, never a shape constraint).
    """
    if chunks <= 1 or x.shape[0] % chunks:
        return lax.all_gather(x, axis_name, axis=0, tiled=True)
    rows = x.shape[0] // chunks
    gathered = []
    prev = None
    for c in range(chunks):
        part = lax.slice_in_dim(x, c * rows, (c + 1) * rows, axis=0)
        if prev is not None:
            part, prev = lax.optimization_barrier((part, prev))
        g = lax.all_gather(part, axis_name, axis=0)  # [n, rows, ...]
        gathered.append(g)
        prev = g
    # [C, n, rows, ...] -> [n, C, rows, ...] -> [n * C * rows, ...]:
    # device-major, then original row order within each device's shard —
    # exactly the tiled gather's layout
    stacked = jnp.stack(gathered, axis=0)
    moved = jnp.swapaxes(stacked, 0, 1)
    return moved.reshape((-1,) + tuple(x.shape[1:]))


def batch_shuffle(
    x: jax.Array, key: jax.Array, axis_name, chunks: int = 1
) -> tuple[jax.Array, jax.Array]:
    """Shuffle the global batch across devices; return (local shard, perm).

    Rebuild of `_batch_shuffle_ddp` (`moco/builder.py:≈L72-98`):
      all-gather → same permutation everywhere (shared PRNG key instead of a
      rank-0 broadcast) → each device keeps its contiguous slice.

    `key` MUST be replicated across the mesh (derived by `fold_in` from the
    replicated train-state key) — divergent keys would silently desynchronise
    the shuffle; tests/test_collectives.py pins this.

    `axis_name` may be a tuple (the 2-D mesh — ISSUE 15 generalizes
    ShuffleBN to arbitrary mesh shapes); `chunks` applies the FAST-style
    chunked gather schedule (see `all_gather_batch`).
    """
    n = batch_axis_size(axis_name)
    idx = batch_axis_index(axis_name)
    x_all = all_gather_batch(x, axis_name, chunks)  # [B_global, ...]
    global_b = x_all.shape[0]
    perm = jax.random.permutation(key, global_b)
    local_idx = lax.dynamic_slice_in_dim(perm, idx * (global_b // n), global_b // n)
    return jnp.take(x_all, local_idx, axis=0), perm


def batch_unshuffle(x: jax.Array, perm: jax.Array, axis_name,
                    chunks: int = 1) -> jax.Array:
    """Undo `batch_shuffle` (rebuild of `_batch_unshuffle_ddp`,
    `moco/builder.py:≈L100-115`): gather the shuffled global batch, index it
    with this device's slice of the inverse permutation."""
    n = batch_axis_size(axis_name)
    idx = batch_axis_index(axis_name)
    x_all = all_gather_batch(x, axis_name, chunks)
    global_b = x_all.shape[0]
    inv = jnp.argsort(perm)
    local_idx = lax.dynamic_slice_in_dim(inv, idx * (global_b // n), global_b // n)
    return jnp.take(x_all, local_idx, axis=0)


def chained_psum(flats: list[jax.Array], axis_name: str) -> list[jax.Array]:
    """Per-bucket psums chained through `optimization_barrier` (ISSUE 6).

    Each element of `flats` is one flat gradient bucket. A plain loop of
    psums leaves XLA free to merge them back into one fused end-of-step
    all-reduce — exactly the serialization bucketing exists to break. The
    barrier ties bucket i+1's INPUT to bucket i's OUTPUT, so the reduces
    issue as a deterministic pipeline: bucket i can be on the wire while
    the backward that produces bucket i+1 is still running (DeAR,
    PAPERS.md). The barrier is a scheduling hint: numerics are those of
    the plain loop."""
    out = []
    prev = None
    for flat in flats:
        if prev is not None:
            flat, prev = lax.optimization_barrier((flat, prev))
        summed = lax.psum(flat, axis_name)
        out.append(summed)
        prev = summed
    return out


def quantized_psum_mean(
    segments: list[jax.Array], axis_name: str, n: int, wire_dtype: str
) -> tuple[list[jax.Array], list[jax.Array]]:
    """Compress→psum→dequant one bucket of flat f32 segments (one segment
    per gradient leaf); returns `(means, errors)` aligned with `segments`.

    `wire_dtype="int8"`: symmetric int8 with PER-SEGMENT scales, shared
    across devices via ONE `pmax` of the stacked per-segment absmaxes (a
    single tiny vector reduce per bucket). The scale must follow the leaf,
    not the bucket: a multi-MiB bucket spans layers whose gradient
    magnitudes differ by orders of magnitude, and one bucket-wide scale
    would quantize the small-magnitude layers to all-zeros on the wire
    every step — a hidden sync starvation error feedback only undoes one
    quantum at a time. Shared scales keep the dequantized mean
    bit-identical across devices (the DP-safety invariant). The whole
    bucket still rides ONE concatenated psum, on an int32 carrier: summing
    n int8 values overflows int8 for n >= 2, and XLA exposes no
    in-collective requantization (EQuARX does this inside the ring; the
    int8 PAYLOAD plus one f32 scale per leaf is what the byte accounting
    counts).

    `wire_dtype="bfloat16"`: cast→psum→f32, the legacy grad_allreduce path
    — but returning the local cast error so callers can carry error
    feedback, which the legacy path never had.

    `errors` are the LOCAL quantization residuals (input minus what the
    wire carried for this device) — the error-feedback accumulator
    re-injects them into the next step's gradient."""
    if wire_dtype == "int8":
        absmax = lax.pmax(
            jnp.stack([jnp.max(jnp.abs(s)) for s in segments]), axis_name
        )
        scales = jnp.maximum(absmax, jnp.float32(1e-30)) / 127.0
        qs = [
            jnp.clip(jnp.round(s / scales[i]), -127, 127).astype(jnp.int8)
            for i, s in enumerate(segments)
        ]
        flat = jnp.concatenate(qs) if len(qs) > 1 else qs[0]
        summed = lax.psum(flat.astype(jnp.int32), axis_name)
        means, errs, off = [], [], 0
        for i, (s, q) in enumerate(zip(segments, qs)):
            seg = summed[off:off + s.size]
            off += s.size
            means.append(seg.astype(jnp.float32) * scales[i] / n)
            errs.append(s - q.astype(jnp.float32) * scales[i])
        return means, errs
    if wire_dtype == "bfloat16":
        qs = [s.astype(jnp.bfloat16) for s in segments]
        flat = jnp.concatenate(qs) if len(qs) > 1 else qs[0]
        summed = lax.psum(flat, axis_name).astype(jnp.float32)
        means, errs, off = [], [], 0
        for s, q in zip(segments, qs):
            means.append(summed[off:off + s.size] / n)
            off += s.size
            errs.append(s - q.astype(jnp.float32))
        return means, errs
    raise ValueError(f"unknown quantized wire dtype {wire_dtype!r}")


def ring_shuffle(x: jax.Array, axis_name, inverse: bool = False) -> jax.Array:
    """Cheaper ShuffleBN variant: HALF-SHARD ring roll via two `ppermute`s.

    Rotating WHOLE local batches would be a functional no-op for ShuffleBN —
    BN statistics depend only on group MEMBERSHIP, and moving an intact
    group to another device leaves its composition (and thus the q↔k batch
    signature MoCo guards against) unchanged. Instead each device's new
    group is [tail half of shard i-2, head half of shard i-1]: every key-side
    BN group mixes samples from TWO different query-side groups and every
    query group is split across two key groups — partial decorrelation at
    2 half-shard ppermutes instead of a full all-gather. The gather+permute
    `batch_shuffle` stays the semantically faithful default
    (`shuffle_mode="permute"`). A tuple axis runs the ring over the
    combined row-major device group (ISSUE 15 mesh generalization).
    """
    n = batch_axis_size(axis_name)
    if x.shape[0] % 2:
        raise ValueError("ring_shuffle requires an even local batch")
    h = x.shape[0] // 2
    if h == 0 or n == 1:
        return x
    head, tail = x[:h], x[h:]
    if not inverse:
        # shuffled_i = [tail_{i-2}, head_{i-1}]
        recv_tail = lax.ppermute(tail, axis_name, [(i, (i + 2) % n) for i in range(n)])
        recv_head = lax.ppermute(head, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return jnp.concatenate([recv_tail, recv_head], axis=0)
    # inverse: device j's tail sits as part 0 on device j+2, its head as
    # part 1 on device j+1
    back_tail = lax.ppermute(head, axis_name, [(i, (i - 2) % n) for i in range(n)])
    back_head = lax.ppermute(tail, axis_name, [(i, (i - 1) % n) for i in range(n)])
    return jnp.concatenate([back_head, back_tail], axis=0)


def multihop_quantized_psum_mean(
    segments: list[jax.Array],
    inter_axis: str,
    intra_axis: str,
    n_inter: int,
    n_intra: int,
    wire_dtype: str,
) -> tuple[list[jax.Array], list[jax.Array]]:
    """DynamiQ-style topology-aware two-hop reduce (PAPERS.md; ISSUE 15).

    Hop 1 — EXACT f32 psum over `intra_axis` (the fast intra-pod links:
    compression there would spend accuracy where bandwidth is free).
    Hop 2 — compress→psum→dequant over `inter_axis` (the slow inter-pod
    links) through the SAME int8/bf16 machinery as the single-hop
    `quantized_psum_mean`, so the shared-scale / int32-carrier invariants
    carry over unchanged. Returns `(means, errors)` like the single-hop
    reduce.

    Error feedback across hops: quantization acts on the INTRA-SUMMED
    value, which every member of an intra group shares — so the raw
    residual is a per-GROUP quantity. Each device stores residual/n_intra:
    next step every member re-injects its share into its local gradient,
    and hop 1's exact sum reassembles the full residual, exactly once
    (carrying the whole residual on every member would amplify it
    n_intra-fold per step — a hidden positive feedback loop).
    """
    summed_intra = [lax.psum(s, intra_axis) for s in segments]
    means, group_errs = quantized_psum_mean(
        summed_intra, inter_axis, n_inter, wire_dtype
    )
    # the inter hop's mean divided by n_inter only; fold in the intra fan-in
    means = [m / n_intra for m in means]
    errs = [e / n_intra for e in group_errs]
    return means, errs
