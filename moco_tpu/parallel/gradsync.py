"""Communication-efficient gradient synchronization (ISSUE 6 tentpole).

The seed step synced gradients with ONE fused end-of-step `lax.pmean` over
every leaf (`train_step._pmean_grads`): the interconnect idles during
backprop, then the whole reduce serializes on the critical path. This module
replaces it with a selectable strategy behind `PretrainConfig.grad_sync`,
built once per step-build and invoked INSIDE the shard_map region (where the
data axis exists), with a small replicated-merge hook at the outer jit level
for the sparse mode:

  fused      — the seed behavior, kept as the exact-DP default: one tree-wide
               `pmean` (per-leaf dtype policy below). Bitwise identical to
               the pre-ISSUE-6 program.
  bucketed   — DeAR-style (PAPERS.md): grad leaves are packed into
               size-targeted buckets (`grad_sync_bucket_mb`), issued as
               SEPARATE per-bucket psums chained with `optimization_barrier`
               so the reduces issue in a deterministic sequence as their
               buckets' grads become ready — the scheduler can overlap each
               reduce with the rest of the backward instead of fusing
               everything into one end-of-step all-reduce. Numerically the
               same adds in the same element order: bitwise-equal to fused.
  quantized  — EQuARX-style (PAPERS.md): per-bucket compress→psum→dequant in
               int8 (per-LEAF pmax-shared scales so small-magnitude layers
               are not starved by a bucket-wide absmax; the psum rides an
               int32 carrier so partial sums cannot wrap — a native EQuARX
               collective reduces in int8 inside the ring, which XLA does
               not expose, so the int8 payload + one f32 scale per leaf is
               what the byte accounting counts) or bfloat16.
               A persistent PER-DEVICE error-feedback accumulator
               (`TrainState.gradsync["acc"]`) re-injects this step's
               quantization error into next step's gradient, which is what
               makes compressed DP converge (DP-safe: params stay replicated
               because the dequantized mean is identical everywhere).
  demo       — DeMo-style (PAPERS.md) decoupled momentum: each device keeps
               a LOCAL momentum accumulator fed by its LOCAL gradient; only
               the top-k fraction (`grad_sync_topk`) of that slow component
               is synchronized — as (values, indices) pairs whose merge rides
               a small all-gather — and only every `grad_sync_cadence` steps.
               The transmitted component is subtracted from the local
               momentum (the decoupling); the untransmitted residue keeps
               accumulating. Sync bytes drop by orders of magnitude
               (topk/cadence); convergence is gated by a bounded-divergence
               test, not parity.

Per-leaf dtype policy (the `_pmean_grads` "bfloat16" path folded in, with
the mixed-precision interaction made explicit — ISSUE 6 satellite):

  - `None` leaves pass through untouched (they are empty pytree nodes).
  - integer/bool leaves are SUMMED exactly in their native dtype, never
    averaged and never cast: a non-float leaf in a grads-shaped tree is a
    counter, and quantizing or averaging one silently corrupts it.
  - floating leaves reduce on the wire in their OWN dtype under the
    `"float32"` policy (a bf16 leaf is not silently up-cast, which would
    double its wire bytes), and in bfloat16 under the `"bfloat16"` policy —
    cast BACK to the leaf's original dtype afterwards (the old code cast
    everything to f32, which silently widened bf16 leaves).

State layout: the quantized/demo accumulator is per-device, but TrainState
is a replicated outer-level pytree — so each accumulator leaf carries a
leading device axis (`[n_dev, *param_shape]`, sharded over the data axis by
`zero.shard_pdevice_state`) and the shard_map region sees its own `[1, ...]`
slice. This makes the accumulator checkpointable through the ordinary Orbax
path (dialect 2, see checkpoint.TRAIN_STATE_DIALECTS) at the cost of tying
the checkpoint to the mesh size; restore falls back to fresh zeros when the
shapes (or an old dialect) don't match.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from moco_tpu.parallel.collectives import (
    chained_psum,
    device_local,
    multihop_quantized_psum_mean,
    quantized_psum_mean,
)
from moco_tpu.parallel.mesh import DATA_AXIS

GRAD_SYNC_MODES = ("fused", "bucketed", "quantized", "demo")
STATE_KEY = "acc"  # the one gradsync accumulator leaf-tree in TrainState


def leaf_wire_dtype(dtype, allreduce_dtype: str):
    """The on-wire reduce dtype for one leaf under the fused/bucketed
    policy. Raises on unknown policy strings (the `_pmean_grads` contract,
    pinned by tests/test_grad_allreduce.py)."""
    if allreduce_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown grad_allreduce_dtype {allreduce_dtype!r}")
    if not jnp.issubdtype(dtype, jnp.floating):
        return dtype  # exact-sum leaves: never cast
    if allreduce_dtype == "bfloat16":
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(dtype)  # float32 policy: the leaf's own dtype


class _LeafPlan:
    __slots__ = ("index", "shape", "size", "dtype", "is_float", "k")

    def __init__(self, index, shape, dtype, is_float, k=0):
        self.index = index
        self.shape = tuple(shape)
        self.size = int(math.prod(shape)) if shape else 1
        self.dtype = jnp.dtype(dtype)
        self.is_float = is_float
        self.k = k


class GradSync:
    """One gradient-sync strategy, built from config + mesh size.

    Usage (what both step builders do):
        gradsync = GradSync(config, mesh.size)
        # inside the shard_map region:
        payload, gs_new, probe = gradsync.region_reduce(grads, gs_state, step)
        # at the outer jit level:
        grads = gradsync.finalize(payload, step)
    """

    def __init__(self, config, mesh_size: int, axes=None, axis_sizes=None):
        self.mode = getattr(config, "grad_sync", "fused")
        if self.mode not in GRAD_SYNC_MODES:
            raise ValueError(
                f"unknown grad_sync {self.mode!r}; choose from {GRAD_SYNC_MODES}"
            )
        self.n = int(mesh_size)
        # the mesh axes the reduce runs over (ISSUE 15): the 1-D data axis
        # by default; the sharded step builders pass the 2-D mesh's
        # (data, fsdp) with per-axis sizes. With BOTH axes > 1 the
        # quantized mode becomes the DynamiQ-style multi-hop reduce:
        # exact psum over the inner (fast, intra-pod) axis, int8/bf16
        # compressed hop over the outer (slow, inter-pod) axis.
        self.axes = tuple(axes) if axes else (DATA_AXIS,)
        if axis_sizes is None:
            axis_sizes = (self.n,) if len(self.axes) == 1 else None
        if len(self.axes) > 1 and axis_sizes is None:
            raise ValueError("multi-axis GradSync needs axis_sizes")
        self.axis_sizes = tuple(int(s) for s in axis_sizes) if axis_sizes \
            else (self.n,)
        if math.prod(self.axis_sizes) != self.n:
            raise ValueError(
                f"axis_sizes {self.axis_sizes} do not multiply to the mesh "
                f"size {self.n}"
            )
        self.multihop = (
            self.mode == "quantized"
            and len(self.axes) == 2
            and all(s > 1 for s in self.axis_sizes)
        )
        self.allreduce_dtype = getattr(config, "grad_allreduce_dtype", "float32")
        if self.mode in ("fused", "bucketed"):
            # validate at build time, not first trace
            leaf_wire_dtype(jnp.float32, self.allreduce_dtype)
        self.bucket_bytes = int(
            float(getattr(config, "grad_sync_bucket_mb", 4.0)) * 2**20
        )
        self.quant_dtype = getattr(config, "grad_sync_quant_dtype", "int8")
        if self.mode == "quantized" and self.quant_dtype not in ("int8", "bfloat16"):
            raise ValueError(
                f"unknown grad_sync_quant_dtype {self.quant_dtype!r}; "
                "choose int8 or bfloat16"
            )
        self.cadence = int(getattr(config, "grad_sync_cadence", 1))
        self.topk = float(getattr(config, "grad_sync_topk", 0.01))
        self.demo_beta = float(getattr(config, "grad_sync_demo_beta", 0.9))
        self._plans: list[_LeafPlan] | None = None
        self._treedef = None

    @classmethod
    def for_mesh(cls, config, mesh):
        """The strategy bound to `mesh`'s OWN axes — the one constructor
        every consumer of a possibly-2-D mesh must use (step builder,
        driver telemetry, bench rows): a hand-rolled
        `GradSync(config, mesh.size)` on a 2-D mesh would run/describe the
        single-hop reduce while the step executes the multihop one, and
        every byte claim built on it would drift from what P8 audits."""
        axes = tuple(str(a) for a in mesh.axis_names)
        if len(axes) == 1:
            return cls(config, mesh.size)
        return cls(config, mesh.size, axes=axes,
                   axis_sizes=tuple(int(mesh.shape[a]) for a in axes))

    # -- planning (host-side, shapes only) ----------------------------------
    @property
    def needs_state(self) -> bool:
        return self.mode in ("quantized", "demo")

    def plan(self, tree) -> None:
        """Record per-leaf shapes/dtypes (and demo top-k sizes) from a
        grads-shaped tree; pure host arithmetic, safe on tracers."""
        leaves, treedef = jax.tree.flatten(tree)
        plans = []
        for i, leaf in enumerate(leaves):
            is_float = jnp.issubdtype(leaf.dtype, jnp.floating)
            p = _LeafPlan(i, leaf.shape, leaf.dtype, is_float)
            if is_float:
                p.k = max(1, int(math.ceil(p.size * self.topk)))
            plans.append(p)
        self._plans = plans
        self._treedef = treedef

    def _buckets(self) -> list[list[_LeafPlan]]:
        """Size-targeted buckets over the planned leaves, grouped by wire
        dtype, in REVERSE leaf order — backprop materializes the LAST
        layers' grads first, so reverse order approximates readiness order
        and lets early buckets reduce while early layers still backprop.

        Sized by WIRE bytes — what the collective actually carries — so
        `grad_sync_bucket_mb` means the same thing in every mode: a
        quantized int8 bucket packs ~4x the elements of a bucketed-f32 one
        (sizing by f32 bytes would quietly issue 4x more, smaller
        collectives than configured)."""
        buckets: list[list[_LeafPlan]] = []
        cur: list[_LeafPlan] = []
        cur_bytes = 0
        cur_key = None
        for p in reversed(self._plans):
            if self.mode == "quantized" and p.is_float:
                key = (True, self.quant_dtype)
                nbytes = p.size * (1 if self.quant_dtype == "int8" else 2)
            else:
                wire = (
                    leaf_wire_dtype(p.dtype, self.allreduce_dtype)
                    if self.mode == "bucketed"
                    else p.dtype
                )
                key = (p.is_float, str(wire))
                nbytes = p.size * wire.itemsize
            if cur and (key != cur_key or cur_bytes + nbytes > self.bucket_bytes):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(p)
            cur_bytes += nbytes
            cur_key = key
        if cur:
            buckets.append(cur)
        return buckets

    def describe(self, params) -> dict:
        """Static facts for telemetry/bench: mode, knobs, and the analytic
        per-device sync payload (bytes each device contributes to the wire
        per step, averaged over the demo cadence)."""
        self.plan(params)
        info = {"mode": self.mode,
                "sync_bytes_per_step": self.sync_bytes_per_step()}
        if self.mode in ("bucketed", "quantized"):
            info["bucket_mb"] = round(self.bucket_bytes / 2**20, 3)
            info["buckets"] = len(self._buckets())
        if self.mode == "quantized":
            info["quant_dtype"] = self.quant_dtype
        if self.multihop:
            # per-hop wire accounting (ISSUE 15; progcheck P8 verifies the
            # TOTAL against the traced program): the exact intra hop rides
            # the fast axis, the compressed hop the slow one
            info["multihop"] = {
                "intra_axis": self.axes[1], "intra_size": self.axis_sizes[1],
                "inter_axis": self.axes[0], "inter_size": self.axis_sizes[0],
                "intra_bytes_per_step": self._hop_bytes("intra"),
                "inter_bytes_per_step": self._hop_bytes("inter"),
            }
        if self.mode == "demo":
            info["cadence"] = self.cadence
            info["topk"] = self.topk
        return info

    def _hop_bytes(self, hop: str) -> int:
        """Per-device wire bytes of one multihop-quantized hop: `intra` =
        the exact f32 psum, `inter` = the compressed payload + scales."""
        assert self.multihop and self._plans is not None
        total = 0
        for p in self._plans:
            if not p.is_float:
                continue  # exact-sum leaves ride the single combined psum
            if hop == "intra":
                total += p.size * 4
            else:
                total += p.size * (1 if self.quant_dtype == "int8" else 2)
        if hop == "inter" and self.quant_dtype == "int8":
            total += 4 * sum(1 for p in self._plans if p.is_float)
        return total

    def sync_bytes_per_step(self) -> int:
        """Analytic per-device wire payload per step (see `describe`).
        Multihop quantized counts BOTH hops — the exact intra-pod psum is
        wire traffic too, just on the fast axis."""
        assert self._plans is not None, "call plan()/describe() first"
        total = 0
        for p in self._plans:
            if not p.is_float:
                total += p.size * p.dtype.itemsize
            elif self.mode == "quantized":
                total += p.size * (1 if self.quant_dtype == "int8" else 2)
                if self.multihop:
                    total += p.size * 4  # the exact intra-pod hop
            elif self.mode == "demo":
                # (value f32 + index i32) per selected element, / cadence
                total += int(p.k * 8 / self.cadence)
            else:
                total += p.size * leaf_wire_dtype(
                    p.dtype, self.allreduce_dtype
                ).itemsize
        if self.mode == "quantized" and self.quant_dtype == "int8":
            # one f32 scale per FLOAT LEAF (per-segment scales — see
            # collectives.quantized_psum_mean on scale starvation)
            total += 4 * sum(1 for p in self._plans if p.is_float)
        return total

    # -- state (quantized EF / demo local momentum) --------------------------
    def attach(self, state, mesh):
        """Return `state` with freshly-zeroed gradsync accumulator leaves
        (`[n_dev, *param_shape]`, sharded over the data axis). A no-op tree
        (`{}`) for the stateless modes."""
        if not self.needs_state:
            return state.replace(gradsync={})
        acc = jax.tree.map(
            lambda p: jnp.zeros((mesh.size,) + tuple(p.shape), jnp.float32),
            state.params_q,
        )
        return state.replace(gradsync=self.place_state({STATE_KEY: acc}, mesh))

    def place_state(self, gradsync_tree, mesh):
        """(Re-)place accumulator leaves in the per-device sharded layout —
        applied after a resume, which restores them replicated."""
        from moco_tpu.parallel.zero import shard_pdevice_state

        return shard_pdevice_state(gradsync_tree, mesh)

    # -- region side (inside shard_map over the data axis) -------------------
    def payload_specs(self, P):
        """out_specs prefix for the region payload (`P` is PartitionSpec)."""
        if self.mode == "demo":
            batch = self.reduce_axis
            return {"vals": P(batch), "idx": P(batch), "exact": P()}
        return P()

    @property
    def reduce_axis(self):
        """The axis-name argument the collectives take: the bare name on
        the 1-D mesh (bit-compatible with the pre-ISSUE-15 jaxprs), the
        tuple (one combined device group) on the 2-D mesh."""
        return self.axes[0] if len(self.axes) == 1 else self.axes

    def region_reduce(self, grads, gs_state, step, axis_name=None):
        """Reduce local grads inside the mapped region.

        Returns `(payload, new_gs_state, probe_pre)`:
        - `payload`: the reduced grads tree (fused/bucketed/quantized — typed
          replicated, out_spec P()) or the sparse (vals, idx, exact) trees
          for demo (out_spec per `payload_specs`).
        - `new_gs_state`: the per-device accumulator slices, `[1, ...]` local
          (out_spec P(DATA_AXIS)); `{}` for stateless modes.
        - `probe_pre`: a psum'd scalar depending only on the RAW local grads
          — the "grads are ready" marker the comm-phase fence drains first
          (telemetry/timing.py).
        """
        if axis_name is None:
            axis_name = self.reduce_axis
        self.plan(grads)
        leaves = jax.tree.flatten(grads)[0]
        probe_pre = self._probe_pre(leaves, axis_name)
        if self.mode == "fused":
            return self._reduce_fused(grads, axis_name), {}, probe_pre
        if self.mode == "bucketed":
            return self._reduce_bucketed(leaves, axis_name), {}, probe_pre
        acc_local = [
            a[0].reshape(-1)
            for a in jax.tree.flatten(gs_state[STATE_KEY])[0]
        ] if gs_state else None
        if acc_local is None or len(acc_local) != len(leaves):
            raise ValueError(
                f"grad_sync mode {self.mode!r} needs per-device accumulator "
                "state: call GradSync.attach(state, mesh) after creating the "
                "TrainState (the train driver does this)"
            )
        if self.mode == "quantized":
            return self._reduce_quantized(leaves, acc_local, axis_name)[:2] + (
                probe_pre,
            )
        return self._reduce_demo(leaves, acc_local, step, axis_name) + (probe_pre,)

    def _probe_pre(self, leaves, axis_name):
        for p in self._plans:
            if p.is_float:
                g0 = leaves[p.index].reshape(-1)[0].astype(jnp.float32)
                return lax.psum(g0, axis_name) / self.n
        return jnp.float32(0.0)

    def probe_post(self, grads):
        """Outer-level scalar reading of the REDUCED grads — draining it
        marks "reduce (and merge) finished"."""
        for p in self._plans or ():
            if p.is_float:
                leaf = jax.tree.flatten(grads)[0][p.index]
                return leaf.reshape(-1)[0].astype(jnp.float32)
        return jnp.float32(0.0)

    def _reduce_fused(self, grads, axis_name):
        """The seed `_pmean_grads`, under the explicit per-leaf policy: one
        tree-wide pmean of the float leaves (bitwise the pre-ISSUE-6
        program when everything is f32), exact psum for integer leaves."""
        def down(g):
            return g.astype(leaf_wire_dtype(g.dtype, self.allreduce_dtype))

        if all(p.is_float for p in self._plans):
            reduced = lax.pmean(jax.tree.map(down, grads), axis_name)
            return jax.tree.map(lambda r, g: r.astype(g.dtype), reduced, grads)
        leaves = jax.tree.flatten(grads)[0]
        out = [
            lax.pmean(down(leaves[p.index]), axis_name).astype(p.dtype)
            if p.is_float
            else lax.psum(leaves[p.index], axis_name)
            for p in self._plans
        ]
        return jax.tree.unflatten(self._treedef, out)

    def _reduce_bucketed(self, leaves, axis_name):
        buckets = self._buckets()
        flats = []
        for bucket in buckets:
            segs = [
                leaves[p.index]
                .reshape(-1)
                .astype(leaf_wire_dtype(p.dtype, self.allreduce_dtype))
                for p in bucket
            ]
            flats.append(jnp.concatenate(segs) if len(segs) > 1 else segs[0])
        summed = chained_psum(flats, axis_name)
        out = [None] * len(leaves)
        for bucket, s in zip(buckets, summed):
            red = s / self.n if bucket[0].is_float else s
            off = 0
            for p in bucket:
                out[p.index] = red[off:off + p.size].reshape(p.shape).astype(
                    p.dtype
                )
                off += p.size
        return jax.tree.unflatten(self._treedef, out)

    def _reduce_quantized(self, leaves, acc_local, axis_name):
        buckets = self._buckets()
        out = [None] * len(leaves)
        new_acc = [None] * len(leaves)
        prev = None
        for bucket in buckets:
            if not bucket[0].is_float:
                for p in bucket:
                    out[p.index] = lax.psum(leaves[p.index], axis_name)
                    new_acc[p.index] = acc_local[p.index]
                continue
            segs = [
                (leaves[p.index].reshape(-1).astype(jnp.float32)
                 + acc_local[p.index])
                for p in bucket
            ]
            if prev is not None:
                # sequence the buckets like the bucketed mode: a
                # deterministic issue order the scheduler can pipeline
                segs, prev = lax.optimization_barrier((segs, prev))
            if self.multihop:
                # DynamiQ topology-aware path (2-D mesh, both axes > 1):
                # exact on the fast inner axis, compressed on the slow
                # outer one
                means, errs = multihop_quantized_psum_mean(
                    segs, self.axes[0], self.axes[1],
                    self.axis_sizes[0], self.axis_sizes[1], self.quant_dtype,
                )
            else:
                means, errs = quantized_psum_mean(
                    segs, axis_name, self.n, self.quant_dtype
                )
            prev = means[0]
            for p, mean, err in zip(bucket, means, errs):
                out[p.index] = mean.reshape(p.shape).astype(p.dtype)
                new_acc[p.index] = err
        reduced = jax.tree.unflatten(self._treedef, out)
        acc_tree = jax.tree.unflatten(
            self._treedef,
            [a.reshape((1,) + p.shape) for a, p in zip(new_acc, self._plans)],
        )
        return reduced, {STATE_KEY: acc_tree}

    def _reduce_demo(self, leaves, acc_local, step, axis_name):
        fplans = [p for p in self._plans if p.is_float]
        m = [
            self.demo_beta * acc_local[p.index]
            + leaves[p.index].reshape(-1).astype(jnp.float32)
            for p in fplans
        ]

        def sync_branch(ms):
            vals, idxs, residue = [], [], []
            for p, mm in zip(fplans, ms):
                _, i = lax.top_k(jnp.abs(mm), p.k)
                v = mm[i]
                vals.append(v)
                idxs.append(i.astype(jnp.int32))
                # decouple: the transmitted component leaves the local
                # momentum; the residue keeps accumulating
                residue.append(mm.at[i].add(-v))
            return vals, idxs, residue

        def skip_branch(ms):
            # device-local like the sync branch's outputs: cond demands
            # equal types, varying axes included
            return (
                device_local([jnp.zeros((p.k,), jnp.float32) for p in fplans],
                             axis_name),
                device_local([jnp.zeros((p.k,), jnp.int32) for p in fplans],
                             axis_name),
                ms,
            )

        if self.cadence <= 1 or not fplans:
            vals, idxs, residue = sync_branch(m)
        else:
            vals, idxs, residue = lax.cond(
                step % self.cadence == 0, sync_branch, skip_branch, m
            )
        exact = [
            lax.psum(leaves[p.index], axis_name)
            for p in self._plans
            if not p.is_float
        ]
        new_acc = [None] * len(self._plans)
        fi = 0
        for p in self._plans:
            if p.is_float:
                new_acc[p.index] = residue[fi].reshape((1,) + p.shape)
                fi += 1
            else:
                new_acc[p.index] = jnp.zeros((1,) + p.shape, jnp.float32)
        payload = {
            "vals": [v[None] for v in vals],
            "idx": [i[None] for i in idxs],
            "exact": exact,
        }
        acc_tree = jax.tree.unflatten(self._treedef, new_acc)
        return payload, {STATE_KEY: acc_tree}

    # -- audit surface (ISSUE 9; tools/progcheck) ----------------------------
    def audit_region_program(self, params, mesh):
        """The gradsync reduce as a STANDALONE region program, for static
        auditing: returns `(fn, args, payload_shape)` where `fn` is the
        shard_map'd `(grads, gs_state, step) -> (payload, new_state)` over
        a grads-shaped tree matching `params`, `args` are abstract
        ShapeDtypeStructs for it, and `payload_shape` is the payload's
        eval_shape (progcheck maps the demo vals/idx leaves to wire bytes
        from it). Tracing this isolates exactly the collectives this
        strategy issues — the wire-bytes check (P8) compares their jaxpr
        payload against `sync_bytes_per_step()`, so the analytic telemetry
        claim is machine-checked instead of trusted."""
        import jax
        from jax.sharding import PartitionSpec as P

        self.plan(params)

        def region(grads, gs_state, step):
            payload, new_state, _probe = self.region_reduce(
                grads, gs_state, step
            )
            return payload, new_state

        state_spec = P(self.reduce_axis) if self.needs_state else P()
        fn = jax.shard_map(
            region, mesh=mesh,
            in_specs=(P(), state_spec, P()),
            out_specs=(self.payload_specs(P), state_spec),
        )
        grads_sds = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(tuple(p.shape), p.dtype), params
        )
        if self.needs_state:
            state_sds = {STATE_KEY: jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(
                    (mesh.size,) + tuple(p.shape), jnp.float32
                ),
                params,
            )}
        else:
            state_sds = {}
        args = (grads_sds, state_sds, jax.ShapeDtypeStruct((), jnp.int32))
        payload_shape = jax.eval_shape(fn, *args)[0]
        return fn, args, payload_shape

    # -- outer side (replicated merge; jit level, no manual axes) ------------
    def finalize(self, payload, step):
        """Turn the region payload into the grads tree the optimizer sees.

        Identity for fused/bucketed/quantized. For demo the region returns
        per-device (values, indices) pairs typed varying (the same hybrid
        split the queue/EMA updates use — collectives.py replication note),
        so the replicated merge happens HERE at the outer jit level: the
        partitioner's all-gather of the small [n_dev, k] pairs is the only
        communication, and only inside the taken cadence branch."""
        if self.mode != "demo":
            return payload
        assert self._plans is not None, "region_reduce must trace first"
        fplans = [p for p in self._plans if p.is_float]

        def merge(sp):
            vals, idxs = sp
            out = []
            for p, v, i in zip(fplans, vals, idxs):
                flat = (
                    jnp.zeros((p.size,), jnp.float32)
                    .at[i.reshape(-1)]
                    .add(v.reshape(-1))
                    / self.n
                )
                out.append(flat.reshape(p.shape).astype(p.dtype))
            return out

        def zeros(sp):
            return [jnp.zeros(p.shape, p.dtype) for p in fplans]

        if self.cadence <= 1 or not fplans:
            deltas = merge((payload["vals"], payload["idx"]))
        else:
            deltas = lax.cond(
                step % self.cadence == 0, merge, zeros,
                (payload["vals"], payload["idx"]),
            )
        out = [None] * len(self._plans)
        fi = ei = 0
        for p in self._plans:
            if p.is_float:
                out[p.index] = deltas[fi]
                fi += 1
            else:
                out[p.index] = payload["exact"][ei]
                ei += 1
        return jax.tree.unflatten(self._treedef, out)
