"""Host→device input pipeline (rebuild of `DataLoader` + `DistributedSampler`
usage in `main_moco.py:≈L228-278`; parallel/overlapped staging is ISSUE 3).

- `epoch_permutation` replaces `DistributedSampler.set_epoch`: a
  deterministic per-epoch shuffle of the whole dataset, seeded identically on
  every host; each host then takes its contiguous shard (`process_index`), so
  shards are disjoint and exhaustive — the same guarantee the reference gets
  from `DistributedSampler`.
- `Prefetcher` is a staged pipeline replacing the reference's 32 worker
  processes + `pin_memory` H2D overlap:

    coordinator thread: per batch, fan out N contiguous sub-slices to the
    staging workers → workers decode INTO disjoint rows of a pooled canvas
    (`get_batch_into` when the dataset supports it — the native path's C++
    threads then write the final bytes in place) → the coordinator issues
    the device transfer itself (per-device-shard puts as aligned sub-slices
    complete, else one sharded put) → the ready queue holds DEVICE arrays.

  So JPEG decode, canvas assembly AND the H2D transfer all hide under the
  consumer's running train step; `__iter__` only pops finished device
  batches. Batches are BIT-IDENTICAL to single-worker staging (contiguous
  sub-slices of the same index order, written to disjoint rows —
  test-enforced), and per-sub-slice retry/backoff preserves the chaos/fault
  semantics of ISSUE 1: a transient read fault in one worker retries just
  that sub-slice, without reordering or duplicating batches.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from moco_tpu.parallel.mesh import DATA_AXIS
from moco_tpu.resilience.chaos import active_chaos
from moco_tpu.telemetry.trace import null_tracer
from moco_tpu.utils.logging import log_event


def epoch_permutation(n: int, epoch: int, seed: int, global_batch: int) -> np.ndarray:
    """Deterministic epoch shuffle, truncated to whole batches (the
    reference's `drop_last=True`)."""
    rng = np.random.RandomState((seed * 100003 + epoch) % (2**31))
    perm = rng.permutation(n)
    usable = (n // global_batch) * global_batch
    return perm[:usable]


def host_shard(indices: np.ndarray, global_batch: int) -> np.ndarray:
    """This host's slice of every global batch (multi-host data sharding)."""
    nproc = jax.process_count()
    if nproc == 1:
        return indices
    if global_batch % nproc != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {nproc}"
        )
    pid = jax.process_index()
    per_host = global_batch // nproc
    batches = indices.reshape(-1, global_batch)
    return batches[:, pid * per_host : (pid + 1) * per_host].reshape(-1)


class _CloseRequested(Exception):
    """Internal: the consumer called close() while a staging worker was in
    retry backoff — the worker exits quietly instead of surfacing the
    transient error as if the run had failed."""


# jax on CPU may return a zero-copy ALIAS of a numpy array from device_put
# (device memory is host memory); recycling a pooled canvas that a live
# jax.Array aliases would corrupt staged batches. Whether a given put
# aliases depends on the allocation's alignment, so it cannot be probed
# reliably — on CPU backends every pooled buffer is COPIED before the put.
# Real accelerators DMA a copy, and the canvas is recycled only after
# block_until_ready on the staged arrays (`_assemble_device`), so the hot
# path never pays this (checked on the chip: chip_smoke's staged-batch ==
# host-source phase).
_HOST_IS_DEVICE: bool | None = None


def _host_memory_is_device_memory() -> bool:
    global _HOST_IS_DEVICE
    if _HOST_IS_DEVICE is None:
        _HOST_IS_DEVICE = jax.devices()[0].platform == "cpu"
    return _HOST_IS_DEVICE


class _Canvas:
    """One preallocated staging buffer: batch rows + extents + labels. The
    rows are uint8 image canvases with `[h, w, rot]` extents, or `int32`
    token rows with `[length]` extents: the first batch says which."""

    def __init__(self, batch: int, img_shape: tuple, img_dtype, label_dtype,
                 extent_shape: tuple = (3,)):
        self.imgs = np.empty((batch,) + tuple(img_shape), img_dtype)
        self.extents = np.empty((batch,) + tuple(extent_shape), np.int32)
        self.labels = np.empty((batch,), label_dtype)


class _BatchCollector:
    """Per-batch completion channel: workers report each finished (or
    failed) sub-slice; the coordinator drains one event per chunk so it
    can start per-shard H2D for finished rows while other workers still
    decode."""

    def __init__(self):
        self.events: queue.Queue = queue.Queue()

    def done_ok(self, chunk_id: int) -> None:
        self.events.put((chunk_id, None))

    def done_err(self, err: BaseException) -> None:
        self.events.put((-1, err))


class Prefetcher:
    """Iterate `(images_u8, labels)` device-sharded batches with parallel
    background staging and overlapped H2D. Token rows (`int32 [B, L]` with
    `[B, 1]` lengths as extents) ride the same canvases, counters and spans.

    `workers` > 1 requires the standard 3-tuple batch protocol
    (`images, labels, extents`); `workers=1` keeps the generic single-call
    staging path (any tuple shape). `depth` is the ready-queue capacity in
    DEVICE batches (staged ahead of the consumer). `trim_h2d` slices the
    canvas to the batch's max extent (rounded up to 64) before transfer —
    single-host only, since hosts would otherwise disagree on the global
    shape — cutting transfer bytes and downstream augment FLOPs for
    content that does not fill the canvas. `stats` is an optional
    `InputPipelineStats` receiving staging telemetry."""

    def __init__(self, dataset, indices: np.ndarray, batch_per_host: int, mesh: Mesh,
                 depth: int = 2, retries: int = 3, backoff_secs: float = 0.5,
                 join_timeout: float = 5.0, workers: int = 1, stats=None,
                 trim_h2d: bool = False, tracer=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        # span layer (ISSUE 8): the coordinator stamps one `stage_batch`
        # span per batch; its staging workers and the per-shard H2D puts
        # continue it as detail spans. The null tracer keeps the hot path
        # branch-free when tracing is off. The coordinator THREAD has no
        # span stack of its own, so its batch spans parent under whatever
        # span the CONSTRUCTING thread held (the driver's context).
        self._tracer = tracer if tracer is not None else null_tracer()
        self._trace_parent = self._tracer.current_context()
        self.dataset = dataset
        self.indices = indices
        self.batch = batch_per_host
        self.mesh = mesh
        # leading dim split over every mesh axis (ISSUE 15: the 2-D
        # data×fsdp mesh still spans the global batch across all devices)
        from moco_tpu.parallel.mesh import batch_axes

        self.sharding = NamedSharding(mesh, P(batch_axes(mesh)))
        self.num_batches = len(indices) // batch_per_host
        self.retries = retries
        self.backoff_secs = backoff_secs
        self._join_timeout = join_timeout
        self.workers = max(1, min(int(workers), batch_per_host or 1))
        self.trim_h2d = bool(trim_h2d) and jax.process_count() == 1
        self._stats = stats
        if stats is not None:
            stats.note_workers(self.workers)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: BaseException | None = None
        self._err_delivered = False
        self._free: queue.Queue = queue.Queue()  # recycled _Canvas pool
        self._tasks: queue.Queue = queue.Queue()
        self._wthreads: list[threading.Thread] = []
        if self.workers > 1:
            self._wthreads = [
                threading.Thread(target=self._worker_loop, daemon=True,
                                 name=f"staging-w{w}")
                for w in range(self.workers)
            ]
            for t in self._wthreads:
                t.start()
        self._thread = threading.Thread(target=self._coordinator, daemon=True,
                                        name="staging-coord")
        self._thread.start()

    # -- staging workers -----------------------------------------------------
    def _worker_loop(self):
        while not self._stop.is_set():
            try:
                task = self._tasks.get(timeout=0.1)
            except queue.Empty:
                continue
            b, lo, hi, idx, canvas, collector, trace_ctx = task
            try:
                # detail span continuing the coordinator's stage_batch span
                # (explicit parent: thread-locals don't cross threads)
                with self._tracer.span("decode_slice", cat="input",
                                       detail=True, parent=trace_ctx,
                                       batch=b, lo=lo, hi=hi):
                    self._read_slice_into(b, idx, canvas, lo, hi,
                                          trace_ctx=trace_ctx)
                collector.done_ok(lo)
            except BaseException as e:  # routed, not swallowed: the
                # coordinator re-raises (or exits quietly on close)
                collector.done_err(e)

    def _read_slice_into(self, b: int, idx: np.ndarray, canvas: _Canvas,
                         lo: int, hi: int, trace_ctx=None):
        """Decode `idx` into canvas rows [lo, hi) with the same
        retry-with-backoff policy as `_read_batch` — per SUB-SLICE, so a
        transient fault in one worker retries only its rows while the rest
        of the batch proceeds; batch order and content are unaffected.
        Worker-busy telemetry books only the decode attempts themselves,
        NOT the backoff sleeps — `worker_busy_frac` must read LOW during a
        flaky-storage episode (workers idle-waiting), or it would steer an
        operator away from the storage problem."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                plan = active_chaos()
                if plan is not None:
                    plan.maybe_loader_error(b)
                if hasattr(self.dataset, "get_batch_into"):
                    canvas.labels[lo:hi] = self.dataset.get_batch_into(
                        idx, canvas.imgs[lo:hi], canvas.extents[lo:hi]
                    )
                else:
                    # the in-memory path (a dataset without canvases)
                    with self._tracer.span("gather", cat="input",
                                           detail=True, parent=trace_ctx,
                                           batch=b, lo=lo, hi=hi):
                        imgs, labels, extents = self.dataset.get_batch(idx)
                    canvas.imgs[lo:hi] = imgs
                    canvas.labels[lo:hi] = labels
                    canvas.extents[lo:hi] = extents
            except OSError as e:
                if self._stats is not None:
                    self._stats.note_worker_busy(time.perf_counter() - t0)
                attempt += 1
                if attempt > self.retries:
                    raise
                delay = self.backoff_secs * (2 ** (attempt - 1))
                log_event(
                    "loader",
                    f"batch {b} rows [{lo}:{hi}) read failed "
                    f"({type(e).__name__}: {e}); retry {attempt}/"
                    f"{self.retries} in {delay:.2f}s",
                )
                if self._stop.wait(delay):
                    raise _CloseRequested() from e
                continue
            if self._stats is not None:
                self._stats.note_worker_busy(time.perf_counter() - t0)
            return

    # -- coordinator ---------------------------------------------------------
    def _coordinator(self):
        # any dataset error (corrupt file, missing path) must reach the
        # consumer — a silently-dead thread would hang training on q.get()
        try:
            for b in range(self.num_batches):
                t0 = time.perf_counter()
                with self._tracer.span("stage_batch", cat="input",
                                       parent=self._trace_parent,
                                       batch=b) as sp:
                    if self.workers > 1:
                        item = self._stage_batch_parallel(b, sp)
                    else:
                        item = self._stage_to_device(self._read_batch(b))
                if item is None:  # close() during staging
                    return
                if not self._put(item):
                    return
                if self._stats is not None:
                    nbytes = sum(
                        getattr(a, "nbytes", 0) for a in item
                    )
                    self._stats.note_staged(
                        time.perf_counter() - t0, self._q.qsize(), nbytes,
                        images=self.batch,
                    )
        except _CloseRequested:
            # consumer closed while a read was in retry backoff: the read
            # was still within its retry budget, so recording it as a
            # worker error would make close() crash a run that finished
            # all its steps
            return
        except Exception as e:
            self._err = e
        self._put(None)

    def _read_batch(self, b: int):
        """One staged batch via a single dataset call (workers=1 path, any
        tuple shape), with retry-with-backoff on transient read errors
        (flaky NFS/GCS, chaos-injected faults). OSError covers both real
        storage faults and `TransientDataError`; anything else is a
        programming/data-layout error and fails fast as before."""
        attempt = 0
        while True:
            try:
                plan = active_chaos()
                if plan is not None:
                    plan.maybe_loader_error(b)
                return self.dataset.get_batch(
                    self.indices[b * self.batch : (b + 1) * self.batch]
                )
            except OSError as e:
                attempt += 1
                if attempt > self.retries:
                    raise
                delay = self.backoff_secs * (2 ** (attempt - 1))
                log_event(
                    "loader",
                    f"batch {b} read failed ({type(e).__name__}: {e}); "
                    f"retry {attempt}/{self.retries} in {delay:.2f}s",
                )
                if self._stop.wait(delay):
                    raise _CloseRequested() from e

    def _get_canvas(self) -> _Canvas | None:
        """Pop a pooled canvas; None on close()."""
        while not self._stop.is_set():
            try:
                return self._free.get(timeout=0.1)
            except queue.Empty:
                continue
        return None

    def _chunks(self) -> tuple[list[tuple[int, int]], bool]:
        """(balanced contiguous row ranges, aligned) — one range per worker.
        `aligned` means every range covers whole per-device shards, which
        lets H2D start per shard as its rows complete."""
        n_dev = len(self.sharding.addressable_devices)
        w = self.workers
        if n_dev > 1 and self.batch % n_dev == 0 and w <= n_dev and n_dev % w == 0:
            per = n_dev // w
            shard_rows = self.batch // n_dev
            return [(c * per * shard_rows, (c + 1) * per * shard_rows)
                    for c in range(w)], True
        return [
            (self.batch * c // w, self.batch * (c + 1) // w) for c in range(w)
        ], False

    def _stage_batch_parallel(self, b: int, span=None):
        """Fan one batch out to the staging workers; start per-shard H2D as
        aligned sub-slices complete; return the assembled device tuple (or
        None when close() interrupted the batch). `span` is the batch's
        `stage_batch` trace span — its context rides each worker task so
        the decode-slice detail spans parent under it."""
        if not hasattr(self, "_pool_built"):
            # the first batch doubles as shape discovery for the canvas
            # pool: stage it through the single-call path (bit-identical by
            # protocol — the sub-slice fan-out concatenates to exactly this)
            item = self._read_batch(b)
            if len(item) != 3:
                raise TypeError(
                    "multi-worker staging requires the (images, labels, "
                    f"extents) batch protocol; got a {len(item)}-tuple"
                )
            imgs, labels, extents = item
            for _ in range(2):  # double-buffered canvas pool
                self._free.put(
                    _Canvas(self.batch, imgs.shape[1:], imgs.dtype,
                            labels.dtype, np.shape(extents)[1:])
                )
            self._pool_built = True
            return self._stage_to_device(item)
        canvas = self._get_canvas()
        if canvas is None:
            return None
        batch_idx = self.indices[b * self.batch : (b + 1) * self.batch]
        collector = _BatchCollector()
        chunks, aligned = self._chunks()
        trace_ctx = span.context() if span is not None else None
        for lo, hi in chunks:
            self._tasks.put((b, lo, hi, batch_idx[lo:hi], canvas, collector,
                             trace_ctx))
        early = (self._early_put_plan()
                 if aligned and not self.trim_h2d else None)
        chunk_hi_of = dict(chunks)
        shard_arrays: dict = {}
        pending = len(chunks)
        err: BaseException | None = None
        while pending:
            try:
                chunk_lo, cerr = collector.events.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    return None
                continue
            pending -= 1
            if cerr is not None:
                err = cerr
                continue
            if early is not None and err is None:
                # overlapped H2D: this sub-slice's rows cover whole device
                # shards — put them now, under the remaining workers'
                # decode time
                chunk_hi = chunk_hi_of[chunk_lo]
                for dev, (r0, r1) in early:
                    if r0 >= chunk_lo and r1 <= chunk_hi:
                        # detail span: the coordinator thread holds the
                        # stage_batch span, so parenting is automatic
                        with self._tracer.span("h2d_shard", cat="input",
                                               detail=True, batch=b,
                                               rows=f"{r0}:{r1}"):
                            shard_arrays[dev] = jax.device_put(
                                self._host_view(canvas.imgs[r0:r1]), dev
                            )
        if err is not None:
            self._free.put(canvas)
            raise err
        item = self._assemble_device(canvas, shard_arrays, early)
        self._free.put(canvas)
        return item

    def _early_put_plan(self):
        """[(device, (row0, row1)), ...] when per-shard H2D is possible:
        single host, every shard an even contiguous row range."""
        if jax.process_count() > 1:
            return None
        n_dev = len(self.sharding.addressable_devices)
        if n_dev <= 1 or self.batch % n_dev != 0:
            return None
        shard_rows = self.batch // n_dev
        idx_map = self.sharding.addressable_devices_indices_map((self.batch,))
        plan = []
        for dev, index in idx_map.items():
            sl = index[0] if isinstance(index, tuple) else index
            r0 = 0 if sl.start is None else sl.start
            r1 = self.batch if sl.stop is None else sl.stop
            if r1 - r0 != shard_rows:
                return None
            plan.append((dev, (r0, r1)))
        # row order == device-assignment order for a 1-axis batch sharding,
        # which is the order make_array_from_single_device_arrays expects
        plan.sort(key=lambda p: p[1][0])
        return plan

    def _host_view(self, arr: np.ndarray) -> np.ndarray:
        """The array to hand to device_put: copied first when the backend
        aliases host memory (CPU zero-copy) — a recycled canvas must never
        be visible through a live jax.Array."""
        if _host_memory_is_device_memory():
            return np.array(arr)
        return arr

    def _trim(self, imgs: np.ndarray, extents: np.ndarray) -> np.ndarray:
        """Slice the canvas to the batch's max extent, rounded up to 64
        rows/cols (MXU-friendly, and it bounds the number of distinct
        compiled shapes): content never fills less than the trimmed area,
        padding beyond it is edge-replication the on-device crop never
        samples. extents are unchanged — they describe content, not canvas."""
        if imgs.ndim == 2:  # token rows: the longest document's length
            tl = min(imgs.shape[1], int(-(-int(extents[:, 0].max()) // 64) * 64))
            return imgs if tl == imgs.shape[1] else imgs[:, :tl]
        H, W = imgs.shape[1], imgs.shape[2]
        th = min(H, int(-(-int(extents[:, 0].max()) // 64) * 64))
        tw = min(W, int(-(-int(extents[:, 1].max()) // 64) * 64))
        if th == H and tw == W:
            return imgs
        return imgs[:, :th, :tw]

    def _assemble_device(self, canvas: _Canvas, shard_arrays: dict, early):
        imgs = canvas.imgs
        if self.trim_h2d:
            imgs = self._trim(imgs, canvas.extents)
        if early and len(shard_arrays) == len(early):
            img_arr = jax.make_array_from_single_device_arrays(
                (self.batch,) + imgs.shape[1:],
                self.sharding,
                [shard_arrays[dev] for dev, _ in early],
            )
        else:
            img_arr = self._to_device(self._host_view(imgs), self.sharding)
        labels = self._to_device(self._host_view(canvas.labels), self.sharding)
        extents = self._to_device(
            self._host_view(canvas.extents), self.sharding
        )
        item = (img_arr, labels, extents)
        # the transfer must COMPLETE before the canvas is recycled
        # (kImmutableUntilTransferCompletes semantics on real devices)
        jax.block_until_ready(item)
        return item

    def _stage_to_device(self, item):
        """Full-tuple transfer on the staging side (workers=1 path and the
        shape-discovery first batch): the H2D still hides under the
        consumer's running step, it just isn't per-shard-overlapped."""
        if len(item) == 3 and self.trim_h2d:
            imgs, labels, extents = item
            item = (self._trim(np.asarray(imgs), np.asarray(extents)),
                    labels, extents)
        staged = tuple(self._to_device(a, self.sharding) for a in item)
        jax.block_until_ready(staged)
        return staged

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def qsize(self) -> int:
        """Ready-queue depth (device batches staged ahead of the consumer)."""
        return self._q.qsize()

    def close(self):
        """Unblock and join the staging threads (consumers that break out of
        the iterator early MUST call this or the threads + `depth` staged
        batches leak for the life of the process). A worker error the
        iterator never reached (early break) is re-raised here — data
        corruption must not vanish just because the consumer left first."""
        self._stop.set()
        while not self._q.empty():
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=self._join_timeout)
        for t in self._wthreads:
            t.join(timeout=self._join_timeout)
        if self._thread.is_alive() or any(t.is_alive() for t in self._wthreads):
            log_event(
                "loader",
                f"staging thread still alive {self._join_timeout:.1f}s after "
                "close() — a dataset read is wedged; leaking the (daemon) "
                "thread(s) rather than blocking shutdown",
            )
        if self._err is not None and not self._err_delivered:
            self._err_delivered = True
            raise self._err

    def close_quietly(self) -> None:
        """close(), demoting a pending worker error to a loud log. For driver
        loops: the error necessarily belongs to a staged-ahead batch the
        consumer never used (errors on consumed batches surface through the
        iterator), so on an early stop (total_steps, preemption) it must not
        void a run whose every consumed step succeeded — and on an unwind it
        must not REPLACE the exception already in flight."""
        try:
            self.close()
        except Exception as e:
            log_event(
                "loader",
                f"staged-read error for a batch the consumer never used "
                f"(stopped early) — logged, not raised: {e!r}",
            )

    def _to_device(self, arr, sharding):
        if jax.process_count() > 1:
            # multi-host: each host holds only its slice of the global batch;
            # assemble a global array from per-process shards
            return jax.make_array_from_process_local_data(sharding, arr)
        return jax.device_put(arr, sharding)

    def __iter__(self) -> Iterator:
        """Pop finished device batches, booking credit stalls: time the
        consumer spends blocked on an EMPTY ready queue is the pipeline
        (in-process or service) failing to keep the device fed — the
        obsd `input_credit_stall_rate` input (ISSUE 14)."""
        while True:
            if self._stats is not None and self._q.empty():
                t0 = time.perf_counter()
                item = self._q.get()
                self._stats.note_credit_stall(time.perf_counter() - t0)
            else:
                item = self._q.get()
            if item is None:
                if self._err is not None:
                    self._err_delivered = True
                    raise self._err
                return
            # already device-resident (staging-side H2D): just relay
            yield item

    def __len__(self):
        return self.num_batches


def stage_eval_batch(item, batch: int, sharding=None, pad_label=None):
    """Pad a (possibly short) `(imgs, labels, extents)` batch to `batch` rows
    and place the arrays (device_put with `sharding`, or plain jnp).
    `pad_label` fills the label tail (e.g. -1 = never-matching); labels stay
    host-side numpy when `pad_label` is None (caller slices `[:valid]`).
    Shared by the kNN encoder and the lincls validator so their batch
    staging cannot drift apart. Padding rows are BROADCAST views of the
    last row until the single concatenate copy — `np.repeat` materialized a
    full duplicate-image block first, doubling the tail-batch allocation."""
    import jax.numpy as jnp

    imgs, labels, extents = item
    valid = imgs.shape[0]
    if valid < batch:
        pad = batch - valid
        imgs = np.concatenate(
            [imgs, np.broadcast_to(imgs[-1:], (pad,) + imgs.shape[1:])]
        )
        extents = np.concatenate(
            [extents, np.broadcast_to(extents[-1:], (pad,) + extents.shape[1:])]
        )
        if pad_label is not None:
            labels = np.concatenate(
                [labels, np.full(pad, pad_label, labels.dtype)]
            )
    if sharding is not None:
        imgs = jax.device_put(imgs, sharding)
        extents = jax.device_put(np.ascontiguousarray(extents), sharding)
    else:
        imgs = jnp.asarray(imgs)
        extents = jnp.asarray(extents)
    return imgs, labels, extents


def epoch_loader(
    dataset, epoch: int, seed: int, global_batch: int, mesh: Mesh,
    skip_batches: int = 0, retries: int = 3, backoff_secs: float = 0.5,
    depth: int = 2, workers: int = 1, stats=None, trim_h2d: bool = False,
    tracer=None,
) -> Prefetcher:
    """One epoch of sharded batches (sampler.set_epoch + DataLoader in one).

    `skip_batches` drops the first N global batches at the index level (no
    decode, no H2D) — used by mid-epoch resume to fast-forward to the first
    unconsumed batch of the interrupted epoch. `retries`/`backoff_secs`
    configure the transient-read retry policy; `depth`/`workers`/`stats`/
    `trim_h2d` configure the staging pipeline (config: `prefetch_depth`,
    `staging_workers`, `h2d_trim`)."""
    perm = epoch_permutation(len(dataset), epoch, seed, global_batch)
    local = host_shard(perm, global_batch)
    per_host = global_batch // jax.process_count()
    if skip_batches:
        local = local[skip_batches * per_host:]
    return Prefetcher(dataset, local, per_host, mesh,
                      depth=depth, retries=retries, backoff_secs=backoff_secs,
                      workers=workers, stats=stats, trim_h2d=trim_h2d,
                      tracer=tracer)
