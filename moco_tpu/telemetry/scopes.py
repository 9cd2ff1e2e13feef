"""The names the program gives its own work, in ONE place (ISSUE 25).

Two kinds, both read from a `jax.profiler` trace by name:

  - device SCOPES: `jax.named_scope` names inside the fused step program
    (both builders, `train_step.py` and `v3_step.py`, take them from here,
    so a refactor cannot rename one silently). Every operation of
    `jit_fused_step` lies under exactly one of `STEP_SCOPES`; the
    `COLLECTIVE_SCOPES` nest beneath them around the cross-device
    collectives. A scope is HLO metadata (`op_name`): it costs nothing at
    run time and does not change the program's values.
  - host SPANS: `Tracer.span(...)` names of the driver loop, the set-up and
    the input threads. `RunTelemetry` gives the tracer an annotation
    factory, so every span also enters a `jax.profiler.TraceAnnotation`
    and lands in a capture window's (or the benchmark's) device trace, on
    the profiler's clock, at every `trace_mode`.

Pure stdlib: `telemetry/trace.py`'s import diet (mocolint R12) extends to
anything it could ever want to import from here.
"""

from __future__ import annotations

# -- device scopes --------------------------------------------------------------
AUG = "aug"                # two-crop augmentation, incl. the blur kernel and fold_in; token views
K_FWD = "k_fwd"            # key / momentum encoder forward (v2: shuffle, unshuffle)
Q_FWD_BWD = "q_fwd_bwd"    # query forward + backward, minus what is under loss_queue
LOSS_QUEUE = "loss_queue"  # logits, contrastive loss, accuracy / health scalars, enqueue
OPT_EMA = "opt_ema"        # EMA, gradient sync, optimizer update, schedule
STEP_SCOPES = (AUG, K_FWD, Q_FWD_BWD, LOSS_QUEUE, OPT_EMA)

SHUFFLE_BN = "shuffle_bn"  # v2: the all-gather + permutation before the key forward
KEY_GATHER = "key_gather"  # the all-gather of the keys (v2: unshuffle; v3: in-batch negatives)
GRAD_SYNC = "grad_sync"    # GradSync's reduce of the per-device gradients
COLLECTIVE_SCOPES = (SHUFFLE_BN, KEY_GATHER, GRAD_SYNC)

# inside a routed token encoder (`models/sdar.py`), nested under `k_fwd` and
# `q_fwd_bwd`: every operation of the encoder lies under exactly one of them
ATTN = "attn"                    # pre-norm, projections, q/k norm, rotary, scores, output
MOE_ROUTER = "moe_router"        # pre-norm, the float32 router, softmax, top-k
MOE_DISPATCH = "moe_dispatch"    # sort by expert, gather, scatter-add back, residual
MOE_EXPERTS = "moe_experts"      # the grouped products and SwiGLU
EMBED_POOL = "embed_pool"        # token embedding; final norm, mean pool, head
ENCODER_SCOPES = (ATTN, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, EMBED_POOL)

# inside a looped dense token encoder (`models/ouro.py`), nested the same way:
# `attn` (there: projections, rotary, scores, output) and `embed_pool` (token
# embedding; mean pool, head) again, and two siblings of `attn`, never inside it
MLP = "mlp"                      # the three products and SwiGLU
NORM = "norm"                    # the four sandwich norms, the closing norm, the residual adds
# the loop's own hand-over of the residual stream (the scan's counter and stack
# of layer inputs) is under none of them: it reads under `k_fwd` / `q_fwd_bwd` alone
LOOPED_SCOPES = (ATTN, MLP, NORM, EMBED_POOL)

# inside a routed token encoder with learned sparse attention (`models/keye.py`),
# beside `ENCODER_SCOPES`' five: two more siblings of `attn`, never inside it
# (`attn` keeps the four projections, q and k's norm and rotary, and the
# attention kernels)
INDEX = "index"                  # the indexer's projections, LayerNorm, rotary and scores
SELECT = "select"                # the top-k, and the selection as the kernels are handed it
SPARSE_SCOPES = (INDEX, SELECT)

# -- host spans -----------------------------------------------------------------
STEP_SPAN = "step"         # one per driver-loop iteration; enters the profiler as
STEP_ANNOTATION = "train"  # StepTraceAnnotation(STEP_ANNOTATION, step_num=<global step>)
LOOP_SPANS = ("data_wait", "dispatch", "fence", "sentinel", "loss_readback",
              "telemetry", "checkpoint")
# the loop's spans that are also a field of the step record (`timing.py`): the
# driver opens each with `timer.phase(<field>)` as the same `with`'s next item,
# so span and field are one interval; what is under none of them is the
# record's `loop_s`
STEP_PHASES = {"data_wait": "data_s", "dispatch": "host_s", "sentinel": "wait_s",
               "fence": "fence_s", "loss_readback": "readback_s",
               "telemetry": "telemetry_s"}
SETUP_SPANS = ("create_train_state", "model_init", "opt_init", "place_state",
               "build_step", "first_batch", "restore")
INPUT_SPANS = ("stage_batch", "decode_slice", "gather", "h2d_shard")
