"""Flat dataclass configs + the five BASELINE.json presets (SURVEY §5.6).

The reference's config system is one flat argparse namespace per driver
(`main_moco.py:≈L28-100`, re-declared with different defaults in
`main_lincls.py:≈L40-90`); the v1→v2 switch is three booleans and a
temperature on the CLI. We keep that shape — a flat dataclass per driver,
argparse front-end in the drivers — and name the five BASELINE configs as
presets.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass
class PretrainConfig:
    # experiment
    name: str = "moco"
    variant: str = "v2"               # "v1" | "v2" | "v3"
    seed: int = 0
    # model (reference flags -a/--arch, --moco-dim/k/m/t, --mlp)
    arch: str = "resnet50"            # resnet18/34/50/101/152 | vit_small/base/large/huge
                                      # | sdar_30b_a3b (a routed token encoder:
                                      # widths in models/sdar.py::SDAR_SIZES)
                                      # | ouro_2p6b (a looped dense token encoder:
                                      # models/ouro.py::OURO_SIZES)
                                      # | keye_vl2_30b_a3b (routed, learned sparse
                                      # attention: models/keye.py::KEYE_SIZES)
    embed_dim: int = 128              # --moco-dim
    num_negatives: int = 65536        # --moco-k (ignored for v3)
    momentum_ema: float = 0.999       # --moco-m (v3: base for cosine ramp, 0.99)
    temperature: float = 0.07         # --moco-t (v2 runs use 0.2)
    mlp_head: bool = False            # --mlp
    cifar_stem: bool = False
    shuffle_mode: str = "permute"     # ShuffleBN flavor: "permute" (faithful
                                      # all-gather + shared-RNG perm) | "ring"
                                      # (single ppermute rotation, cheaper)
    compute_dtype: str = "float32"    # "bfloat16" on TPU
    sync_bn: bool = False             # per-device BN is the MoCo default
    remat: bool = False               # per-block rematerialization (ViT
                                      # blocks / ResNet residual blocks):
                                      # trades recompute for HBM traffic
    zero_sharding: bool = False       # ZeRO-1: shard optimizer state over
                                      # the data axis (HBM/N footprint, one
                                      # all-gather of updates per step;
                                      # identical numerics — parallel/zero)
    # scale-out sharding (ISSUE 15; parallel/fsdp.py — see README
    # "Sharding modes" for the mode table and composition matrix)
    sharding: str = "dp"              # "dp" (seed layout: 1-D mesh, params
                                      # replicated — bitwise the pre-ISSUE-15
                                      # program) | "fsdp" (v3 only: params +
                                      # optimizer state sharded 1/N over the
                                      # fsdp mesh axis, all-gather-on-use,
                                      # grads reduce-scattered through
                                      # GradSync) | "fsdp_tp" (2-D hybrid:
                                      # shard over the fast inner axis,
                                      # replicate over the slow outer one;
                                      # quantized grad_sync upgrades to the
                                      # DynamiQ-style multi-hop reduce)
    sharding_axis_size: int = 0       # fsdp-axis (inner/fast) device count
                                      # for fsdp_tp; 0 = derive (all devices
                                      # for fsdp, largest proper divisor for
                                      # fsdp_tp). Must divide the device
                                      # count.
    collective_chunks: int = 1        # FAST-style chunked scheduling for
                                      # the ShuffleBN / v3 key-gather
                                      # all-to-alls: split each gather into
                                      # N barrier-chained chunk collectives
                                      # that pipeline with compute.
                                      # Bit-identical reassembly; 1 = one
                                      # monolithic gather (seed behavior)
    # gradient sync (ISSUE 6; parallel/gradsync.py — see README "Gradient
    # sync modes" for the mode table and convergence caveats)
    grad_sync: str = "fused"          # "fused" (exact DP, one tree pmean —
                                      # the seed program, bitwise) |
                                      # "bucketed" (per-bucket psums chained
                                      # with optimization_barrier: reduce
                                      # overlaps backprop, bitwise-equal
                                      # numerics) | "quantized" (int8/bf16
                                      # compress→psum→dequant per bucket +
                                      # per-device error feedback) | "demo"
                                      # (DeMo-style local momentum, top-k
                                      # sparse sync at a cadence)
    grad_sync_bucket_mb: float = 4.0  # bucketed/quantized: target bucket
                                      # payload (MiB of wire bytes per
                                      # all-reduce issue)
    grad_sync_quant_dtype: str = "int8"  # quantized wire dtype: "int8"
                                      # (shared-scale symmetric, int32
                                      # carrier) | "bfloat16"
    grad_sync_cadence: int = 1        # demo: sync every N steps (off-steps
                                      # carry no gradient payload — only
                                      # the constant probe-scalar psum)
    grad_sync_topk: float = 0.01      # demo: fraction of each leaf's
                                      # momentum synced per sync step
    grad_sync_demo_beta: float = 0.9  # demo: local momentum decay
    grad_allreduce_dtype: str = "float32"  # fused/bucketed wire-dtype
                                      # policy: "bfloat16" halves the grad
                                      # all-reduce's ICI bytes (EQuARX-style
                                      # in its simplest lossy form, NO error
                                      # feedback — grad_sync="quantized" is
                                      # the EF-corrected version; the master
                                      # update still runs in f32). Per-leaf
                                      # policy: float leaves reduce in bf16
                                      # and cast back to their OWN dtype,
                                      # integer leaves are summed exactly,
                                      # never cast (gradsync.leaf_wire_dtype)
    # a token encoder's share of its published stack (models/sdar.py,
    # models/ouro.py; 0 = the arch's own number). Named as the model's config.json names them:
    # a benchmark configuration lists the ones it cuts under `reduced`
    num_hidden_layers: int = 0        # blocks kept (the period is one block)
    num_experts: int = 0              # routed experts HELD here, the first n;
                                      # the router keeps the arch's width and
                                      # experts per token, and this chip
                                      # computes its own experts' part
    vocab_size: int = 0               # the vocabulary's first n ids; the last
                                      # of them is the mask id of the views
    seq_len: int = 512                # tokens a view (token archs only)
    # data
    dataset: str = "synthetic"        # synthetic | cifar10 | imagefolder | synthetic_tokens
    data_dir: str = ""
    image_size: int = 224
    aug_plus: bool = False            # --aug-plus (v2 aug stack)
    crop_min: float = 0.0             # v3 --crop-min (0 = variant default:
                                      # 0.08 for ViT, the R50 recipe uses 0.2)
    num_workers: int = 0              # host-side loader threads (-j);
                                      # 0 = dataset default (8)
    stage_size: int = 0               # ImageFolder staging-canvas shorter
                                      # side; 0 = dataset default (512 —
                                      # stages typical ImageNet photos at
                                      # ORIGINAL resolution so the on-device
                                      # RRC samples original pixels)
    # input pipeline (ISSUE 3: parallel sharded staging, decode-once cache,
    # overlapped H2D — see README "Input pipeline" for tuning)
    prefetch_depth: int = 2           # device batches staged ahead of the
                                      # consumer (Prefetcher queue capacity;
                                      # each slot pins one batch of HBM)
    staging_workers: int = 4          # host staging threads per Prefetcher:
                                      # each decodes a disjoint sub-slice of
                                      # the per-host batch into a pooled
                                      # canvas (bit-identical to 1 worker)
    input_cache_mb: int = 0           # decode-once canvas cache budget in
                                      # MiB (LRU over uint8 canvases +
                                      # extents; 0 = off). Sound because the
                                      # randomized augmentation runs ON
                                      # DEVICE over the staging canvas, so
                                      # the decoded canvas is deterministic
                                      # per image — epochs >= 2 pay memcpy
                                      # instead of JPEG decode
    h2d_trim: bool = False            # slice each staged batch to its max
                                      # content extent (rounded up to 64)
                                      # before the device transfer: fewer
                                      # H2D bytes + cheaper on-device aug
                                      # for content that underfills the
                                      # canvas. Single-host only; each new
                                      # trimmed shape compiles once
    # disaggregated input service (ISSUE 14 — see README "Input service")
    input_service: str = ""           # "host:port,host:port" staging-server
                                      # data endpoints: epoch batches are
                                      # fetched from standalone decode
                                      # servers (ServiceClient) instead of
                                      # decoded in-process — bit-identical
                                      # to in-process staging on the same
                                      # seed/epoch. "" = in-process.
                                      # Rejected with h2d_trim: trimming
                                      # is a client-side canvas slice whose
                                      # shape grid the remote shard frames
                                      # do not carry — progcheck P9's
                                      # bounded-compile-set contract stays
                                      # with the in-process path
    input_prestage: str = ""          # pre-staged epoch cache directory
                                      # (tools/prestage.py output) served
                                      # by the IN-PROCESS Prefetcher: the
                                      # dataset becomes mmap row gathers —
                                      # decode-once for the whole cluster.
                                      # (Staging servers take the same
                                      # directory via --prestage.)
    input_request_timeout_s: float = 30.0
                                      # one service shard round-trip bound
                                      # before the client tears the link
                                      # and re-lands the shard elsewhere.
                                      # Size ABOVE the slowest honest
                                      # shard decode: a timeout restarts
                                      # the decode from scratch on the
                                      # next server, so a bound below it
                                      # exhausts retries deterministically
    # optimization (reference: SGD momentum .9, wd 1e-4, lr .03, batch 256)
    optimizer: str = "sgd"            # sgd | adamw | lars
    lr: float = 0.03                  # absolute lr; 0.0 = derive from base_lr
    base_lr: float = 0.0              # lr-per-256: effective lr is
                                      # base_lr × batch/256 (moco-v3 semantics,
                                      # `main_moco.py` there: `args.lr *
                                      # args.batch_size / 256`), resolved at
                                      # step-build time so a --batch-size
                                      # override rescales the lr with it
    batch_size: int = 256             # GLOBAL batch
    epochs: int = 200
    warmup_epochs: int = 0            # v3: 40
    schedule: tuple[int, ...] = (120, 160)  # --schedule milestones (v1 path)
    cos: bool = False                 # --cos
    sgd_momentum: float = 0.9
    weight_decay: float = 1e-4
    momentum_ramp: bool = False       # v3 cosine m→1 ramp
    # bookkeeping / observability (SURVEY §5.1, §5.5)
    print_freq: int = 10              # -p
    tb_dir: str = ""                  # tensorboard scalar logdir ("" = off)
    profile_dir: str = ""             # jax.profiler trace logdir ("" = off)
    profile_start: int = 10           # trace window [start, stop) in steps
    profile_stop: int = 20
    debug_nans: bool = False          # jax_debug_nans + finite-loss guard (§5.2)
    # structured run telemetry (telemetry/; ISSUE 2) — machine-readable
    # step-phase timing, MFU, HBM tracking, pod-aggregated JSONL events
    telemetry_dir: str = ""           # events.jsonl + heartbeat.json land
                                      # here ("" = telemetry off; no step-
                                      # loop overhead when off)
    telemetry_flush_steps: int = 50   # buffered-record flush cadence, in
                                      # step records
    heartbeat_secs: float = 1.0       # min seconds between heartbeat.json
                                      # writes (beaten every step, time-
                                      # gated; the supervisor's hang-
                                      # detection granularity — independent
                                      # of the flush cadence above)
    telemetry_stride: int = 16        # device-fence sampling stride: every
                                      # N steps block_until_ready measures
                                      # the device-compute phase and HBM is
                                      # sampled; all other steps stay fully
                                      # async (0 = never fence)
    peak_flops_per_chip: float = 0.0  # MFU denominator override; 0 = look
                                      # up device_kind in the bf16 peak
                                      # table (telemetry/mfu.py; unknown
                                      # hardware ⇒ MFU omitted, never
                                      # fabricated)
    # distributed tracing + on-demand profiling (telemetry/trace.py;
    # ISSUE 8 — see README "Tracing & profiling")
    trace_mode: str = "off"           # "off" (capture windows still
                                      # armable) | "steps" (one span per
                                      # step / staged batch / supervisor
                                      # launch) | "full" (+ worker decode
                                      # slices, H2D puts, phase segments)
    trace_capture_steps: int = 50     # capture-window length, in steps:
                                      # SIGUSR1 / trace.trigger / anomaly
                                      # detectors elevate to full detail
                                      # (+ optional device trace) for this
                                      # many steps
    trace_capture_budget: int = 3     # max capture windows per run (auto-
                                      # triggers can never profile-storm a
                                      # multi-day run; 0 = captures off)
    trace_slow_step_k: float = 3.0    # arm a capture when step_s (or the
                                      # data phase) exceeds k × its own
                                      # rolling p95
    trace_device_profile: bool = False  # capture windows also record a
                                      # jax.profiler device trace into
                                      # <telemetry_dir>/traces/
    # learning-health diagnostics (telemetry/health.py; ISSUE 13 — see
    # README "Learning health" for formulas and sentinel semantics)
    health_stride: int = 0            # 0 = off (no diagnostics traced;
                                      # the health-on parameter trajectory
                                      # is bitwise the health-off one);
                                      # N = trace the in-graph
                                      # collapse diagnostics (embedding
                                      # std/participation ratio, queue
                                      # norm/age, q↔k param drift, grad
                                      # group norms) under one lax.cond
                                      # firing every N steps, recorded as
                                      # the step records' `health` block.
                                      # neg_sim/logit_margin are standard
                                      # metrics regardless of this knob.
    collapse_window: int = 50         # CollapseSentinel window W, in
                                      # OBSERVATIONS (per-step for
                                      # margin/acc1, per-health-stride
                                      # sample for embedding std)
    collapse_min_step: int = 0        # sentinel predicates evaluate only
                                      # past this step (init-time acc1 IS
                                      # chance and the margin is still
                                      # forming — an early window must
                                      # not page anyone)
    collapse_acc1: float = 0.0        # predicate: max acc1 over a full
                                      # window < this floor (%; 0 = off)
    collapse_emb_std: float = 0.0     # predicate: every sampled
                                      # embedding std in a full window
                                      # <= this epsilon (0 = off; needs
                                      # health_stride > 0 to see samples)
    collapse_margin: float = 0.0      # predicate: max logit margin over
                                      # a full window <= this (0 = off)
    collapse_rollback: bool = False   # opt-in: a fired predicate raises
                                      # CollapseError into the bounded
                                      # NaN-rollback path (restore last
                                      # good checkpoint + data-window
                                      # advance, max_rollbacks-capped);
                                      # default is a structured `health`
                                      # incident only
    ckpt_dir: str = "checkpoints"
    ckpt_every_epochs: int = 1
    resume: str = ""                  # path | "auto"
    export_path: str = ""             # write encoder_q (.safetensors/.npz) at end
    steps_per_epoch: int | None = None  # derived from dataset unless set
    # fault tolerance (resilience/; preemptible-VM pretraining survives
    # SIGTERM, corrupt checkpoints, NaN losses, and flaky reads unattended)
    loss_sentinel: bool = True        # every-step non-finite-loss check
                                      # (one-step lag — no pipeline bubble)
    max_rollbacks: int = 3            # consecutive NaN rollbacks before the
                                      # run aborts (0 = never roll back:
                                      # a non-finite loss raises immediately)
    watchdog_secs: float = 0.0        # flag when no step completes within
                                      # this window (0 = watchdog off)
    loader_retries: int = 3           # transient data-read retries per batch
                                      # (Prefetcher, exponential backoff)
    loader_backoff_secs: float = 0.5  # base backoff delay between retries
    decode_abort_rate: float = 0.5    # abort (DataQualityError) when the
                                      # cumulative decode-failure rate
                                      # exceeds this after the first host
                                      # batch (0 = never abort; failures are
                                      # still metered either way)
    resilience_sync_steps: int = 16   # multi-host only: cadence (in steps)
                                      # at which per-host fault signals
                                      # (SIGTERM flag, decode counters) are
                                      # allgathered so every host acts on
                                      # them identically — one host breaking
                                      # alone hangs the rest in the next
                                      # collective (0 disables the sync,
                                      # and with it preemption handling and
                                      # the decode abort on multi-host runs)
    chaos: str = ""                   # fault-injection spec for drills/tests,
                                      # e.g. "sigterm_at_step=100" or
                                      # "nan_at_step=3,loader_error_at_batch=7"
                                      # (resilience/chaos.py; also via the
                                      # MOCO_TPU_CHAOS env var)
    knn_monitor: bool = False         # periodic kNN top-1 during pretrain
    knn_every_epochs: int = 1         # monitor cadence (the eval costs ~160 s
                                      # on the 1-core sandbox — long CPU runs
                                      # thin it out; the final epoch always
                                      # reports so gates see a fresh number)
    knn_bank_size: int = 4096         # monitor bank cap (train-subset size)
    num_classes: int = 1000           # dataset classes (kNN/eval only)

    def __post_init__(self):
        # config-BUILD-time validation (runs again on every replace()): a
        # bad depth/worker count must fail where it was written, not as a
        # wedged queue half an epoch into a run
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )
        if self.staging_workers < 1:
            raise ValueError(
                f"staging_workers must be >= 1, got {self.staging_workers}"
            )
        if self.input_cache_mb < 0:
            raise ValueError(
                f"input_cache_mb must be >= 0, got {self.input_cache_mb}"
            )
        # input-service knobs (ISSUE 14): a typo'd endpoint list must fail
        # where it was written, not as an unreachable-server stall mid-run.
        # The parser lives in the stdlib service protocol module — a
        # function-level import, so config stays importable without jax
        if self.input_request_timeout_s <= 0:
            raise ValueError(
                "input_request_timeout_s must be > 0, got "
                f"{self.input_request_timeout_s}"
            )
        if self.input_service:
            from moco_tpu.data.service.protocol import parse_endpoints

            parse_endpoints(self.input_service)  # raises ValueError
            if self.h2d_trim:
                raise ValueError(
                    "input_service and h2d_trim are mutually exclusive: "
                    "extent-trimming slices the staged canvas CLIENT-side "
                    "into a shape grid the remote shard frames do not "
                    "carry — run the service with full canvases (the "
                    "remote decode is what h2d_trim's savings came from) "
                    "or trim in-process"
                )
            if self.input_prestage:
                raise ValueError(
                    "input_service and input_prestage are mutually "
                    "exclusive on the train host: the service loader "
                    "would feed training while the prestage sat unused "
                    "as a len() source — a same-length-different-data "
                    "server pool would pass the meta check and silently "
                    "train off the pinned cache. Point the staging "
                    "servers at it instead "
                    "(tools/staging_server.py --prestage <dir>)"
                )
        # sharding knobs (ISSUE 15): literals kept in sync with
        # parallel/mesh.SHARDING_MODES — config must stay importable
        # without jax
        if self.sharding not in ("dp", "fsdp", "fsdp_tp"):
            raise ValueError(
                f"unknown sharding {self.sharding!r}; choose from "
                "dp/fsdp/fsdp_tp"
            )
        if self.sharding != "dp" and self.variant != "v3":
            raise ValueError(
                f"sharding={self.sharding!r} requires variant='v3': the "
                "queue-based v1/v2 step needs the replicated queue's "
                "identical-enqueue invariant (and its encoders fit "
                "per-chip) — FSDP targets the queue-free large-batch v3 "
                "regime"
            )
        if self.sharding_axis_size < 0:
            raise ValueError(
                f"sharding_axis_size must be >= 0, got "
                f"{self.sharding_axis_size}"
            )
        if self.sharding != "dp" and self.zero_sharding:
            raise ValueError(
                "zero_sharding and sharding=fsdp/fsdp_tp are mutually "
                "exclusive: fsdp already shards the optimizer state over "
                "the fsdp axis — re-placing it with the ZeRO-1 data-axis "
                "layout would silently re-replicate the shards"
            )
        if self.collective_chunks < 1:
            raise ValueError(
                f"collective_chunks must be >= 1, got "
                f"{self.collective_chunks}"
            )
        # grad-sync knobs (ISSUE 6): literals kept in sync with
        # parallel/gradsync.GRAD_SYNC_MODES — config must stay importable
        # without jax (the serve/stdlib processes)
        if self.grad_sync not in ("fused", "bucketed", "quantized", "demo"):
            raise ValueError(
                f"unknown grad_sync {self.grad_sync!r}; choose from "
                "fused/bucketed/quantized/demo"
            )
        if self.grad_sync_bucket_mb <= 0:
            raise ValueError(
                f"grad_sync_bucket_mb must be > 0, got {self.grad_sync_bucket_mb}"
            )
        if self.grad_sync_quant_dtype not in ("int8", "bfloat16"):
            raise ValueError(
                f"unknown grad_sync_quant_dtype {self.grad_sync_quant_dtype!r}"
            )
        if self.grad_sync_cadence < 1:
            raise ValueError(
                f"grad_sync_cadence must be >= 1, got {self.grad_sync_cadence}"
            )
        if not 0.0 < self.grad_sync_topk <= 1.0:
            raise ValueError(
                f"grad_sync_topk must be in (0, 1], got {self.grad_sync_topk}"
            )
        if not 0.0 <= self.grad_sync_demo_beta < 1.0:
            raise ValueError(
                f"grad_sync_demo_beta must be in [0, 1), got "
                f"{self.grad_sync_demo_beta}"
            )
        # tracing knobs (ISSUE 8): literals kept in sync with
        # telemetry/trace.TRACE_MODES — config stays importable without
        # the telemetry stack loaded
        if self.trace_mode not in ("off", "steps", "full"):
            raise ValueError(
                f"unknown trace_mode {self.trace_mode!r}; choose from "
                "off/steps/full"
            )
        if self.trace_capture_steps < 1:
            raise ValueError(
                f"trace_capture_steps must be >= 1, got "
                f"{self.trace_capture_steps}"
            )
        if self.trace_capture_budget < 0:
            raise ValueError(
                f"trace_capture_budget must be >= 0, got "
                f"{self.trace_capture_budget}"
            )
        if self.trace_slow_step_k <= 1.0:
            raise ValueError(
                f"trace_slow_step_k must be > 1, got {self.trace_slow_step_k}"
            )
        # learning-health knobs (ISSUE 13): config stays importable
        # without jax — literals only, like the gradsync/trace blocks
        if self.health_stride < 0:
            raise ValueError(
                f"health_stride must be >= 0, got {self.health_stride}"
            )
        if self.collapse_window < 1:
            raise ValueError(
                f"collapse_window must be >= 1, got {self.collapse_window}"
            )
        if self.collapse_min_step < 0:
            raise ValueError(
                f"collapse_min_step must be >= 0, got {self.collapse_min_step}"
            )
        for knob in ("collapse_acc1", "collapse_emb_std", "collapse_margin"):
            if getattr(self, knob) < 0:
                raise ValueError(
                    f"{knob} must be >= 0 (0 disables the predicate), "
                    f"got {getattr(self, knob)}"
                )
        if self.collapse_emb_std and not self.health_stride:
            raise ValueError(
                "collapse_emb_std needs health_stride > 0: the embedding-"
                "std predicate consumes the stride-sampled in-graph "
                "diagnostics and would otherwise watch an empty stream"
            )

    def replace(self, **kw) -> "PretrainConfig":
        return dataclasses.replace(self, **kw)

    @property
    def effective_lr(self) -> float:
        return _effective_lr(self)


def _effective_lr(config) -> float:
    """`lr` if set, else the batch-scaled `base_lr × batch/256`. An explicit
    `--lr` always wins (reference CLI semantics); presets that follow the
    linear-scaling rule ship `lr=0.0` + `base_lr` so batch overrides stay
    on-recipe (VERDICT r2 weak #4)."""
    if config.lr:
        return config.lr
    if not config.base_lr:
        raise ValueError("config needs lr or base_lr (both are 0)")
    return config.base_lr * config.batch_size / 256


@dataclass
class EvalConfig:
    """Linear probe (`main_lincls.py` defaults) + kNN settings."""

    arch: str = "resnet50"
    pretrained: str = ""              # --pretrained checkpoint path
    dataset: str = "imagefolder"
    data_dir: str = ""
    image_size: int = 224
    cifar_stem: bool = False
    num_classes: int = 1000
    num_workers: int = 0              # host-side loader threads (-j); 0 = default (8)
    stage_size: int = 0               # staging canvas shorter side (0 = default)
    prefetch_depth: int = 2           # batches staged ahead (epoch_loader)
    staging_workers: int = 4          # host staging threads per Prefetcher
    seed: int = 0
    # lincls recipe: lr 30, epochs 100, milestones 60/80, wd 0, batch 256
    lr: float = 30.0                  # absolute lr; 0.0 = derive from base_lr
    base_lr: float = 0.0              # lr-per-256 (moco-v3 lincls scales lr by
                                      # batch/256; see `_effective_lr`)
    batch_size: int = 256
    epochs: int = 100
    schedule: tuple[int, ...] = (60, 80)
    cos: bool = False
    sgd_momentum: float = 0.9
    weight_decay: float = 0.0
    # kNN protocol (SURVEY §2.5): top-200 neighbors, T=0.07
    knn_k: int = 200
    knn_temperature: float = 0.07
    knn_bank_chunk: int = 65536       # bank rows per streamed top-k slice
                                      # (caps sims at [batch, chunk]; 0 = off)
    print_freq: int = 10
    ckpt_dir: str = "lincls_checkpoints"  # probe checkpoints ("" = off)
    resume: str = ""                      # "" | "auto" (latest probe ckpt)
    evaluate: bool = False                # -e/--evaluate: validate the
                                          # (resumed) probe and exit, no
                                          # training (`main_lincls.py:≈L95`)

    def __post_init__(self):
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )
        if self.staging_workers < 1:
            raise ValueError(
                f"staging_workers must be >= 1, got {self.staging_workers}"
            )

    def replace(self, **kw) -> "EvalConfig":
        return dataclasses.replace(self, **kw)

    @property
    def effective_lr(self) -> float:
        return _effective_lr(self)


@dataclass
class ServeConfig:
    """Online embedding service (moco_tpu/serve/; ISSUE 5). One flat
    dataclass like the drivers', exposed by tools/serve.py as `--flags`."""

    pretrained: str = ""              # exported encoder (.safetensors/.npz),
                                      # any dialect in checkpoint.CHECKPOINT_DIALECTS
    arch: str = "resnet50"
    image_size: int = 224
    cifar_stem: bool = False
    host: str = "127.0.0.1"
    port: int = 8080                  # 0 = ephemeral (tests/bench)
    # micro-batcher (serve/batcher.py): flush on bucket-full OR deadline
    buckets: tuple[int, ...] = (1, 8, 32, 128)  # padded compile shapes; the
                                      # jitted apply compiles exactly these
    flush_ms: float = 10.0            # max coalesce wait before a partial
                                      # bucket flushes (the latency a lone
                                      # request pays to help the next one)
    max_queue: int = 256              # admission-queue depth; beyond it
                                      # requests shed with `overloaded`
    request_deadline_ms: float = 2000.0  # per-request budget; expired-in-
                                      # queue requests shed with
                                      # `deadline_exceeded`, never stall
    embed_cache_mb: int = 64          # content-hash embedding LRU budget
                                      # (serve/cache.py; 0 = off)
    # observability (same events.jsonl stream as training)
    telemetry_dir: str = ""           # "" = telemetry off
    snapshot_every: int = 25          # serve-record cadence, in batches
    # distributed tracing (ISSUE 8): request/flush spans + capture windows
    trace_mode: str = "off"           # off | steps | full (README table)
    trace_capture_steps: int = 50     # capture-window length, in FLUSHED
                                      # batches (the serve tick unit)
    trace_capture_budget: int = 3     # max capture windows per process
    trace_shed_spike: int = 8         # arm a capture when this many
                                      # overload sheds land within 5 s
                                      # (0 = shed-spike detector off)
    # optional kNN-classify endpoint over a precomputed feature bank
    knn_bank: str = ""                # npz with `features` [N,D] + `labels` [N]
    knn_k: int = 200
    knn_temperature: float = 0.07
    num_classes: int = 0              # 0 = derive from bank labels
    drain_timeout_s: float = 60.0     # SIGTERM: max wait for in-flight work
    # hot-reload drift guard (ISSUE 13): before swapping a reloaded
    # engine in, embed a fixed probe batch on old+new and refuse (409
    # reload_collapsed — the fleet quarantines the step) a checkpoint
    # whose probe embeddings are degenerate
    reload_probe: int = 8             # probe rows (0 = guard off)
    reload_min_spread: float = 1e-4   # refuse when 1-‖mean unit row‖ of
                                      # the NEW engine's probe embeddings
                                      # falls below this (rank-one
                                      # collapse as seen from serving)
    # dual swap (ISSUE 16): mean probe-row cosine between a paired
    # bank's recorded probe features and the NEW engine's embedding of
    # the same rows must clear this floor or the pair is refused
    # (409 reload_bank_mismatch — the fleet quarantines the pair)
    bank_agreement_min: float = 0.98
    # sharded ANN index (ISSUE 20): ann_cells > 0 requires a verified
    # paired index next to the bank (tools/bank_build.py --ann-cells)
    # and replaces the exact /v1/knn vote with the IVF probe; 0 keeps
    # the exact path bit-identical to before
    ann_cells: int = 0                # coarse-quantizer cells (0 = exact)
    ann_nprobe: int = 8               # cells probed per query
    ann_rerank: int = 0               # candidates kept per probe
                                      # (0 = knn_k)
    ann_shard: int = 0                # this replica's cell partition ...
    ann_shards: int = 1               # ... of how many (cell % shards)
    # tiered admission (ISSUE 20): interactive vs batch lanes
    admission_tiers: bool = True      # False folds "batch" onto the
                                      # interactive lane
    batch_max_queue: int = 1024       # batch-lane admission depth
    batch_deadline_ms: float = 30000.0  # batch-lane default deadline

    def __post_init__(self):
        # the ONE bucket-ladder rule, shared with the runtime's own check
        # (serve/batcher.py is numpy+stdlib — safe at config-import time)
        from moco_tpu.serve.batcher import validate_buckets

        b = validate_buckets(self.buckets)
        if self.max_queue < b[-1]:
            raise ValueError(
                f"max_queue ({self.max_queue}) must hold at least one full "
                f"bucket ({b[-1]})"
            )
        if self.flush_ms < 0 or self.request_deadline_ms <= 0:
            raise ValueError(
                "flush_ms must be >= 0 and request_deadline_ms > 0"
            )
        if self.embed_cache_mb < 0:
            raise ValueError(
                f"embed_cache_mb must be >= 0, got {self.embed_cache_mb}"
            )
        if self.reload_probe < 0 or self.reload_min_spread < 0:
            raise ValueError(
                "reload_probe and reload_min_spread must be >= 0 "
                f"(0 disables the guard), got {self.reload_probe} / "
                f"{self.reload_min_spread}"
            )
        if not -1.0 <= self.bank_agreement_min <= 1.0:
            raise ValueError(
                "bank_agreement_min is a cosine floor in [-1, 1], got "
                f"{self.bank_agreement_min}"
            )
        if self.trace_mode not in ("off", "steps", "full"):
            raise ValueError(
                f"unknown trace_mode {self.trace_mode!r}; choose from "
                "off/steps/full"
            )
        if self.trace_capture_steps < 1 or self.trace_capture_budget < 0 \
                or self.trace_shed_spike < 0:
            raise ValueError(
                "trace_capture_steps must be >= 1, trace_capture_budget "
                "and trace_shed_spike >= 0"
            )
        if self.ann_cells < 0 or self.ann_nprobe < 1 or self.ann_rerank < 0:
            raise ValueError(
                "need ann_cells >= 0 (0 = exact), ann_nprobe >= 1, "
                f"ann_rerank >= 0 (0 = knn_k); got {self.ann_cells} / "
                f"{self.ann_nprobe} / {self.ann_rerank}"
            )
        if self.ann_shards < 1 or not 0 <= self.ann_shard < self.ann_shards:
            raise ValueError(
                f"need 0 <= ann_shard < ann_shards, got "
                f"{self.ann_shard} / {self.ann_shards}"
            )
        if self.ann_cells and not self.knn_bank:
            raise ValueError(
                "ann_cells > 0 needs a --knn-bank (the index pairs with "
                "a versioned bank)"
            )
        if self.batch_max_queue < b[-1]:
            raise ValueError(
                f"batch_max_queue ({self.batch_max_queue}) must hold at "
                f"least one full bucket ({b[-1]})"
            )
        if self.batch_deadline_ms <= 0:
            raise ValueError(
                f"batch_deadline_ms must be > 0, got "
                f"{self.batch_deadline_ms}"
            )

    def replace(self, **kw) -> "ServeConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# The five BASELINE.json target configs as named presets.
# ---------------------------------------------------------------------------

PRESETS: dict[str, PretrainConfig | EvalConfig] = {
    # 1. MoCo-v1 ResNet-18 CIFAR-10, K=4096, single-process (CPU smoke ref)
    "cifar10-moco-v1": PretrainConfig(
        name="cifar10-moco-v1",
        variant="v1",
        arch="resnet18",
        num_negatives=4096,
        temperature=0.07,
        cifar_stem=True,
        dataset="cifar10",
        image_size=32,
        batch_size=256,
        epochs=200,
        cos=False,
        knn_monitor=True,
        num_classes=10,
    ),
    # 0. MoCo-v1 ResNet-50 ImageNet-1k — the reference's DEFAULT run
    #    (no MLP, no aug+, no cosine; T=0.07, milestones 120/160; the 60.6%
    #    linear-probe row in BASELINE.md)
    "imagenet-moco-v1": PretrainConfig(
        name="imagenet-moco-v1",
        variant="v1",
        arch="resnet50",
        dataset="imagefolder",
        compute_dtype="bfloat16",
    ),
    # 2. MoCo-v2 ResNet-50 ImageNet-1k, K=65536, MLP head, cosine LR
    "imagenet-moco-v2": PretrainConfig(
        name="imagenet-moco-v2",
        variant="v2",
        arch="resnet50",
        num_negatives=65536,
        temperature=0.2,
        mlp_head=True,
        aug_plus=True,
        cos=True,
        dataset="imagefolder",
        compute_dtype="bfloat16",
    ),
    # 4. Linear-probe + kNN eval on frozen MoCo-v2 features
    "imagenet-lincls": EvalConfig(),
    # 4b. MoCo-v3 linear probe (sibling repo's `main_lincls.py` recipe: SGD
    #     lr 3·batch/256, 90 epochs, cosine, wd 0 — its README linear-probe
    #     command for ViT). Probes BACKBONE features of a v3 export.
    "imagenet-lincls-v3": EvalConfig(
        arch="vit_small",
        lr=0.0,
        base_lr=3.0,
        batch_size=1024,
        epochs=90,
        schedule=(),
        cos=True,
    ),
    # 5. MoCo-v3 ViT-S/16, queue-free large-batch contrastive
    "imagenet-moco-v3-vits": PretrainConfig(
        name="imagenet-moco-v3-vits",
        variant="v3",
        arch="vit_small",
        embed_dim=256,
        momentum_ema=0.99,
        momentum_ramp=True,
        temperature=0.2,
        optimizer="adamw",
        lr=0.0,
        base_lr=1.5e-4,
        weight_decay=0.1,
        batch_size=4096,
        epochs=300,
        warmup_epochs=40,
        cos=True,
        aug_plus=True,
        dataset="imagefolder",
        compute_dtype="bfloat16",
    ),
    # 5a. MoCo-v3 ViT-B/16 — the sibling repo's larger ViT run (same AdamW
    #     recipe as ViT-S: lr 1.5e-4·b/256, wd 0.1, batch 4096, 40-epoch
    #     warmup; only the backbone width/depth changes). remat on by
    #     default: ViT-B at per-chip batch 512 needs it to fit HBM.
    "imagenet-moco-v3-vitb": PretrainConfig(
        name="imagenet-moco-v3-vitb",
        variant="v3",
        arch="vit_base",
        embed_dim=256,
        momentum_ema=0.99,
        momentum_ramp=True,
        temperature=0.2,
        optimizer="adamw",
        lr=0.0,
        base_lr=1.5e-4,
        weight_decay=0.1,
        batch_size=4096,
        epochs=300,
        warmup_epochs=40,
        cos=True,
        aug_plus=True,
        remat=True,
        dataset="imagefolder",
        compute_dtype="bfloat16",
    ),
    # 5b. MoCo-v3 ResNet-50 leg (sibling repo's `MoCo_ResNet`; SURVEY §2.9
    #     "ResNet recipe uses LARS"): LARS, lr 0.3·batch/256, wd 1.5e-6,
    #     100 ep / 10 warmup, T=1.0 (moco-v3 default), crop-min 0.2,
    #     m=0.99 cosine-ramped — the repo's R50 README command.
    "imagenet-moco-v3-r50": PretrainConfig(
        name="imagenet-moco-v3-r50",
        variant="v3",
        arch="resnet50",
        embed_dim=256,
        momentum_ema=0.99,
        momentum_ramp=True,
        temperature=1.0,
        optimizer="lars",
        lr=0.0,
        base_lr=0.3,
        weight_decay=1.5e-6,
        batch_size=4096,
        epochs=100,
        warmup_epochs=10,
        cos=True,
        crop_min=0.2,
        dataset="imagefolder",
        compute_dtype="bfloat16",
    ),
}


# 6. MoCo v2 over token sequences (Contriever's recipe, arXiv:2112.09118:
#    momentum encoder, queue of negatives, two independent crops of a
#    document, AdamW) with SDAR-30B-A3B-Chat's published stack as the
#    encoder. The preset is the published model whole; one chip holds a
#    share of it (`--num-hidden-layers 4 --num-experts 16 --vocab-size 18992`
#    is one of eight expert-parallel chips' share of four layers: README).
PRESETS["text-moco-v2-sdar"] = PretrainConfig(
    name="text-moco-v2-sdar",
    variant="v2",
    arch="sdar_30b_a3b",
    num_negatives=65536,
    temperature=0.2,
    momentum_ema=0.99,  # AdamW at lr 1e-5 moves a weight by 1e-5 a step: at
                        # 0.999 the key encoder's share of that is under ten
                        # float32 steps of a 0.03 weight and is lost in rounding
    mlp_head=True,
    optimizer="adamw",
    lr=1e-5,
    weight_decay=0.01,
    batch_size=32,
    seq_len=512,
    epochs=200,
    cos=True,
    dataset="synthetic_tokens",
    compute_dtype="bfloat16",
    remat=True,
    health_stride=16,  # the expert-load counters ride the health scalars
)


# 7. The same recipe with Ouro-2.6B's published stack as the encoder
#    (models/ouro.py: 48 dense layers run `total_ut_steps` = 4 times with the
#    same weights, one compiled pass under scan). The preset is the published
#    model whole (2.6 B parameters, 52 GB of train state: more than a chip);
#    `--num-hidden-layers 6` is the first of eight pipeline stages, looped on
#    its own six layers, which one chip holds (README).
PRESETS["text-moco-v2-ouro"] = PRESETS["text-moco-v2-sdar"].replace(
    name="text-moco-v2-ouro",
    arch="ouro_2p6b",
    batch_size=16,
)


# 8. The same recipe over LONG documents with the language stack of
#    Keye-VL-2.0-30B-A3B as the encoder (models/keye.py: SDAR's routed layer
#    under learned sparse attention, a 16-head indexer picks 2 048 keys a
#    query): views of 8 192 tokens, 2 documents a chip. The preset is the
#    published stack whole; `--num-hidden-layers 4 --num-experts 16
#    --vocab-size 18992` is one of eight expert-parallel chips' share of four
#    layers (README).
PRESETS["text-moco-v2-keye"] = PRESETS["text-moco-v2-sdar"].replace(
    name="text-moco-v2-keye",
    arch="keye_vl2_30b_a3b",
    batch_size=2,
    seq_len=8192,
)


# 9. The same recipe with kanana-2-30b-a3b's published stack as the encoder
#    (models/kanana.py: latent attention, a leading dense layer, then shared
#    and routed experts under a sigmoid router): views of 4 096 tokens, 4
#    documents a chip. The preset is the published stack whole;
#    `--num-hidden-layers 5 --num-experts 8 --vocab-size 16032` is one of 16
#    expert-parallel chips' share of the dense layer and four routed ones
#    (README).
PRESETS["text-moco-v2-kanana"] = PRESETS["text-moco-v2-sdar"].replace(
    name="text-moco-v2-kanana",
    arch="kanana_2_30b_a3b",
    batch_size=4,
    seq_len=4096,
)


# 3. Same recipe, ShuffleBN across 8 chips (v3-8) — identical step program by
# construction (derived, so the two can never silently fork); the mesh size
# comes from the hardware.
PRESETS["imagenet-moco-v2-8chip"] = PRESETS["imagenet-moco-v2"].replace(
    name="imagenet-moco-v2-8chip"
)


# fields whose default is None but which must parse as ints
_INT_NONE_FIELDS = {"steps_per_epoch"}


def add_config_flags(parser, config_cls) -> None:
    """Expose every dataclass field as a `--flag` (the reference's flat
    argparse surface). Shared by the train/lincls/knn drivers."""
    for f in dataclasses.fields(config_cls):
        name = "--" + f.name.replace("_", "-")
        if isinstance(f.default, bool):
            parser.add_argument(
                name,
                type=lambda s: s.lower() in ("1", "true", "yes"),
                default=None,
            )
        elif isinstance(f.default, tuple):
            # int-tuple fields (schedule milestones, serve buckets):
            # space-separated on the CLI, retupled in collect_overrides
            parser.add_argument(name, type=int, nargs="*", default=None)
        else:
            caster = (
                int
                if f.name in _INT_NONE_FIELDS
                else type(f.default)
                if f.default is not None
                else str
            )
            parser.add_argument(name, type=caster, default=None)


def collect_overrides(args, config_cls) -> dict:
    """Non-None parsed flags → dataclass replace() kwargs."""
    overrides = {
        f.name: getattr(args, f.name)
        for f in dataclasses.fields(config_cls)
        if getattr(args, f.name, None) is not None
    }
    for f in dataclasses.fields(config_cls):
        if isinstance(f.default, tuple) and f.name in overrides:
            overrides[f.name] = tuple(overrides[f.name])
    return overrides


def get_preset(name: str):
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}; choose from {sorted(PRESETS)}")
    return PRESETS[name]
