#!/usr/bin/env python
"""Run the training driver under the out-of-process supervisor (ISSUE 4).

    python tools/supervise.py --telemetry-dir runs/r1/telemetry \
        --ckpt-dir runs/r1/ckpt -- \
        python -m moco_tpu.train --preset imagenet-moco-v2 \
            --telemetry-dir runs/r1/telemetry --ckpt-dir runs/r1/ckpt

Everything after `--` is the child command, launched verbatim (plus
`--resume auto` on restarts unless the command already carries a
`--resume`). The supervisor detects hangs from heartbeat.json staleness,
classifies every death (exit-code protocol, death signal, events-tail
forensics), restarts within a progress-refunded budget with exponential
backoff, and quarantines integrity-failing checkpoints before each
relaunch. Lifecycle events land as `kind: "supervisor"` records in the
child's events.jsonl — `tools/telemetry_report.py` renders them.

Exit code: 0 when the child finished cleanly; the child's final exit code
when the supervisor gave up (fatal class or exhausted budget), so one
level further up (cron, systemd) still sees the structured code.

See README "Run supervision" for the exit-code table and policy knobs.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moco_tpu.resilience.supervisor import (  # noqa: E402
    RestartPolicy,
    Supervisor,
)
from moco_tpu.utils.logging import info  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[1],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--telemetry-dir", required=True,
                   help="the child's telemetry dir (heartbeat.json + "
                        "events.jsonl live here; must match the child's "
                        "--telemetry-dir)")
    p.add_argument("--ckpt-dir", default="",
                   help="the child's checkpoint dir: enables the resume-"
                        "integrity preflight and the checkpoint-step "
                        "progress fallback")
    p.add_argument("--max-restarts", type=int, default=5,
                   help="consecutive no-progress restarts before giving up "
                        "(any step progress refunds the full budget)")
    p.add_argument("--heartbeat-stale-secs", type=float, default=120.0,
                   help="kill the child when its newest step-phase beat is "
                        "older than this; 0 disables hang detection — "
                        "required on non-main pod hosts, which never write "
                        "a heartbeat")
    p.add_argument("--startup-grace-secs", type=float, default=900.0,
                   help="staleness allowance before each launch's first "
                        "step beat (cold compile / restore)")
    p.add_argument("--term-grace-secs", type=float, default=30.0,
                   help="SIGTERM -> grace -> SIGKILL escalation window")
    p.add_argument("--backoff-base-secs", type=float, default=1.0)
    p.add_argument("--backoff-max-secs", type=float, default=60.0)
    p.add_argument("--backoff-jitter", type=float, default=0.2)
    p.add_argument("--poll-secs", type=float, default=2.0)
    p.add_argument("--oom-rss-bytes", type=float, default=0.0,
                   help="classify an external SIGKILL as OOM when the "
                        "events-tail RSS is >= this (0 = never)")
    p.add_argument("--no-force-resume", action="store_true",
                   help="do NOT append `--resume auto` to the child on "
                        "restarts")
    p.add_argument("--resize-device-flag", default="",
                   help="flag used to pin the device count on a resize "
                        "relaunch (ISSUE 11). Default: whichever of "
                        "--num-devices/--fake-devices the child argv "
                        "already uses, else --num-devices")
    p.add_argument("--resize-slow-cadence", type=int, default=0,
                   help="grad_sync_cadence override appended when a resize "
                        "request flags the new mesh slow-linked (`slow=1` "
                        "in resize.request); 0 = never override")
    p.add_argument("--child-log", default="",
                   help="child stdout/stderr log path (default "
                        "<telemetry-dir>/child.log)")
    p.add_argument("child", nargs=argparse.REMAINDER,
                   help="-- then the child command")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    child = args.child
    if child and child[0] == "--":
        child = child[1:]
    if not child:
        build_parser().error("no child command given (append `-- python -m "
                             "moco_tpu.train ...`)")
    policy = RestartPolicy(
        max_restarts=args.max_restarts,
        heartbeat_stale_secs=args.heartbeat_stale_secs,
        startup_grace_secs=args.startup_grace_secs,
        term_grace_secs=args.term_grace_secs,
        backoff_base_secs=args.backoff_base_secs,
        backoff_max_secs=args.backoff_max_secs,
        backoff_jitter=args.backoff_jitter,
        poll_secs=args.poll_secs,
        oom_rss_bytes=args.oom_rss_bytes,
    )
    sup = Supervisor(
        child,
        telemetry_dir=args.telemetry_dir,
        ckpt_dir=args.ckpt_dir,
        policy=policy,
        force_resume=not args.no_force_resume,
        child_log_path=args.child_log,
        resize_device_flag=args.resize_device_flag,
        resize_slow_cadence=args.resize_slow_cadence,
    )
    # SIGUSR2 to the SUPERVISOR requests an elastic resize (ISSUE 11): the
    # next monitor cycle claims any pending resize.request payload (or an
    # empty "resize to what's visible" request) and signals the child
    import signal

    signal.signal(signal.SIGUSR2, lambda *_: sup.resize.signal_resize())
    result = sup.run()
    info(
        f"supervisor: {result.final_class} after {result.launches} launch(es)"
        f" ({result.restarts} restart(s)"
        f"{', budget exhausted' if result.gave_up else ''})"
    )
    if result.final_class == "clean":
        return 0
    return result.exit_code if result.exit_code and result.exit_code > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
