#!/usr/bin/env python
"""Offline summarizer for telemetry events.jsonl (ISSUE 2 tentpole part 6).

    python tools/telemetry_report.py <run_dir>/telemetry/events.jsonl
    python tools/telemetry_report.py events.jsonl --json
    python tools/telemetry_report.py events.jsonl --follow
    python tools/telemetry_report.py <fleet_telemetry_dir>   # ISSUE 10

Renders, from the schema-versioned record stream the driver writes
(moco_tpu/telemetry/registry.py):

  - step-time p50/p95/p99 (ms) + the data/host/device phase split
  - gradient sync (ISSUE 6): mode + analytic sync-bytes/step/device from
    the `grad_sync` records, comm-phase share from the fenced `comm_s`
    samples (grads-ready → reduced)
  - MFU (mean/max) and the peak-FLOPs assumption it was judged against
  - throughput (rolling at end-of-run, cumulative mean)
  - HBM high-water mark + host-RSS high-water
  - input pipeline (ISSUE 3): prefetch queue depth, staging-worker busy
    fraction, decode-once cache hit rate, staged-batch latency p50/p95
  - incident counts by event kind (preempt/rollback/chaos/watchdog/...)
  - supervisor lifecycle (ISSUE 4): launches/restarts/kills, death
    classifications, final budget state and outcome — the `kind:
    "supervisor"` records tools/supervise.py appends to the same stream
  - elastic resize (ISSUE 11): requests, relaunches (old→new device
    count, cadence overrides), and preflight mesh_change incidents from
    the same supervisor stream, folded as a `resize:` section (and
    rendered live by --follow, like fleet lines)
  - serving (ISSUE 5): request/shed counts, latency p50/p95/p99, batch
    count and mean bucket occupancy, embedding-cache hit rate — from the
    cumulative `kind: "serve"` snapshots the embedding service emits
    (the LAST snapshot summarizes the run)
  - serve fleet (ISSUE 10): pass the FLEET telemetry DIRECTORY (the
    `--telemetry-dir` of tools/serve_fleet.py) and the report merges the
    fleet's own events.jsonl with every `replica*/events.jsonl` under
    it: per-replica launch/restart/kill/ejection counts and death
    classifications from the `kind: "fleet"` records, router totals +
    shed rate from the last `router_stats` record, reload history
    (detected / rolled / quarantined), and a per-replica fold of each
    replica's own last serve snapshot (the single-file `serve:` section
    assumes exactly one server)
  - bank lifecycle (ISSUE 16): the `kind: "bank"` records the bank
    builder (build_start/shard_done/build_done), the embedding service
    (the atomic dual `swap`), and the fleet (bank_waiting / quarantine /
    bank_quarantine / rollback) emit, folded as a `bank:` section
    (builds, swaps, quarantines, rollbacks, last build/swap, bank age) —
    and rendered live by --follow, like fleet lines
  - SLO transitions (ISSUE 12): the `kind: "slo"` alert/recovery records
    tools/obsd.py appends into the same stream, folded per rule
    (alert/recovery counts, still-active rules) as a `slo:` section —
    and rendered live by --follow, like fleet/resize lines
  - learning health (ISSUE 13): the `health` blocks the driver stamps on
    health-stride step records (embedding std / participation ratio,
    logit margin, queue norm/age, q↔k drift — telemetry/health.py) plus
    CollapseSentinel incident/recovery events, folded as a `health:`
    section (last sample + window-worst floors) — and rendered live by
    --follow as their own `health:` tail lines
  - pod-record count and worst cross-host step-time spread

`--follow` (ISSUE 8 satellite) is the live-tail mode: poll the file and
render step/incident/supervisor/serve lines AS THEY LAND — the operator's
view of a run in progress, reading the same stream every offline consumer
reads. Reads are partial-line-safe (the writer flushes whole buffers, but
a poll can still catch a line mid-write: bytes after the last newline
stay buffered until the newline arrives), survive the file not existing
yet (supervisor started before the child), and reset on truncation.

Robustness: unparseable lines (a torn tail from a SIGKILL mid-flush) are
counted and skipped, never fatal; unknown record kinds and unknown future
schema versions are tallied but not interpreted. `--json` emits one
machine-readable summary object instead of the human text. Pure stdlib —
runs anywhere the events file can be copied to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# stdlib-safe: aggregate is pinned pure-stdlib (R11 obsd-stdlib-only)
from moco_tpu.telemetry.aggregate import TELEMETRY_SUBDIR_PREFIXES  # noqa: E402


def load_events(path: str) -> tuple[list[dict], int]:
    """Parse a JSONL events file; returns (records, skipped_line_count)."""
    records, skipped = [], 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if isinstance(rec, dict):
                records.append(rec)
            else:
                skipped += 1
    return records, skipped


def _percentile(values: list[float], q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1, round(q / 100.0 * (len(ordered) - 1))))
    return ordered[int(rank)]


def expand_events_arg(path: str) -> list[tuple[str, str]]:
    """`(label, events_path)` pairs for one CLI argument. A FILE is
    itself (label ""); a DIRECTORY is a fleet telemetry dir (ISSUE 10:
    its own events.jsonl plus every `replica*/events.jsonl`) or an
    input-service telemetry root (ISSUE 14: the run's events.jsonl plus
    every `staging_server*/events.jsonl` beside it)."""
    if not os.path.isdir(path):
        return [("", path)]
    pairs = []
    own = os.path.join(path, "events.jsonl")
    if os.path.exists(own):
        pairs.append(("fleet", own))
    for name in sorted(os.listdir(path)):
        sub = os.path.join(path, name, "events.jsonl")
        if (name.startswith(TELEMETRY_SUBDIR_PREFIXES)
                and os.path.exists(sub)):
            pairs.append((name, sub))
    if not pairs:
        raise OSError(f"no events.jsonl under directory {path}")
    return pairs


def load_events_multi(pairs: list[tuple[str, str]]) -> tuple[list[dict], int]:
    """Merge several events files; each record is tagged with its source
    label under `_src` (empty for the single-file case) so per-replica
    folds can group without re-reading."""
    records, skipped = [], 0
    for label, path in pairs:
        recs, skip = load_events(path)
        if label:
            for r in recs:
                r["_src"] = label
        records.extend(recs)
        skipped += skip
    return records, skipped


def summarize(records: list[dict], skipped: int = 0) -> dict:
    """Fold parsed records into one summary dict (the --json payload)."""
    steps = [r for r in records if r.get("kind") == "step"]
    events = [r for r in records if r.get("kind") == "event"]
    pods = [r for r in records if r.get("kind") == "pod"]
    run_starts = [r for r in records if r.get("kind") == "run_start"]
    run_ends = [r for r in records if r.get("kind") == "run_end"]
    supervisor = [r for r in records if r.get("kind") == "supervisor"]
    serves = [r for r in records if r.get("kind") == "serve"]
    fleet = [r for r in records if r.get("kind") == "fleet"]
    slos = [r for r in records if r.get("kind") == "slo"]
    input_servers = [r for r in records if r.get("kind") == "input_server"]
    banks = [r for r in records if r.get("kind") == "bank"]

    step_s = [r["step_s"] for r in steps if "step_s" in r]
    data_s = [r["data_s"] for r in steps if "data_s" in r]
    host_s = [r["host_s"] for r in steps if "host_s" in r]
    device_s = [r["device_s"] for r in steps if "device_s" in r]
    mfu = [r["mfu"] for r in steps if "mfu" in r]
    hbm = [r["hbm_peak_bytes"] for r in steps if "hbm_peak_bytes" in r]
    rss = [r["host_rss_bytes"] for r in steps if "host_rss_bytes" in r]

    events_by_kind: dict[str, int] = {}
    for e in events:
        key = str(e.get("event", "unknown"))
        events_by_kind[key] = events_by_kind.get(key, 0) + 1
    # incidents = events that signal trouble; routine markers the driver
    # emits on purpose (epoch/eval bookkeeping) are reported separately,
    # matching the driver's own `incidents` counter (log_event-routed only)
    routine = {"epoch_summary", "knn_eval", "grad_sync", "sharding", "setup"}
    incidents = {k: v for k, v in events_by_kind.items() if k not in routine}

    summary: dict = {
        "records": len(records),
        "skipped_lines": skipped,
        "runs": len(run_starts),
        "steps": len(steps),
        "events_by_kind": events_by_kind,
        "incidents": incidents,
        "incidents_total": sum(incidents.values()),
        "pod_records": len(pods),
    }
    if run_starts:
        first = run_starts[0]
        summary["run"] = {
            k: first[k]
            for k in ("name", "variant", "arch", "batch_size", "n_chips",
                      "n_procs", "device_kind", "peak_flops_per_chip",
                      "flops_per_step", "run_id", "trace_id")
            if k in first
        }
    if step_s:
        summary["step_time_ms"] = {
            f"p{q}": round(_percentile(step_s, q) * 1e3, 3) for q in (50, 95, 99)
        }
        total = sum(step_s)
        summary["phase_share"] = {
            "data": round(sum(data_s) / total, 4) if total else 0.0,
            "host": round(sum(host_s) / total, 4) if total else 0.0,
        }
        # the main thread's other phases (ISSUE 35), where the records
        # carry them: the wait for the step before, the two drains, the
        # telemetry's own time and the loop's
        for name in ("wait", "fence", "readback", "telemetry", "loop"):
            seconds = sum(r.get(name + "_s", 0.0) for r in steps)
            if seconds and total:
                summary["phase_share"][name] = round(seconds / total, 4)
        summary["steps_span"] = [steps[0].get("step"), steps[-1].get("step")]
        # drains (ISSUE 35): steps dispatched to an idle device (`starved`)
        # and the share of the time their dispatch (`host_s`) took: what the
        # main thread needed to hand over the next step, no bound on the
        # device's idle share
        starved = [r for r in steps if r.get("starved")]
        if starved and total:
            summary["drains"] = {
                "steps": len(starved),
                "share": round(len(starved) / len(steps), 4),
                "dispatch_share": round(
                    sum(r.get("host_s", 0.0) for r in starved) / total, 5),
            }
        # the interpreter's collections that ended inside the steps
        gc_n = sum(r.get("gc_n", 0) for r in steps)
        if gc_n:
            gc_s = sum(r.get("gc_s", 0.0) for r in steps)
            summary["gc"] = {
                "collections": gc_n,
                "full": sum(r.get("gc2_n", 0) for r in steps),
                "ms_each": round(1e3 * gc_s / gc_n, 3),
                "ms_per_step": round(1e3 * gc_s / len(steps), 3),
                "worst_step_ms": round(
                    1e3 * max(r.get("gc_s", 0.0) for r in steps), 3),
            }
    # stalls (ISSUE 35): the `stall` events, each with the phase that held
    # most of its excess over the rolling median
    stalls = [e for e in events if e.get("event") == "stall"]
    if stalls:
        summary["stalls"] = {
            "count": len(stalls),
            "total_ms": round(1e3 * sum(e.get("excess_s", 0.0) for e in stalls), 1),
            "each": [{k: e.get(k) for k in
                      ("step", "phase", "excess_s", "gc2_n", "starved",
                       "queue_depth", "dump")} for e in stalls[:16]],
        }
    if device_s:
        summary["device_time_ms"] = {
            "samples": len(device_s),
            "p50": round(_percentile(device_s, 50) * 1e3, 3),
            "max": round(max(device_s) * 1e3, 3),
        }
    # gradient sync (ISSUE 6): comm-phase share over the fenced samples
    # (grads-ready → reduced, from the same strided fence as device_s) plus
    # the static plan (mode + analytic sync-bytes/step/device) — from the
    # one routine `grad_sync` event or the stamped step records
    comm = [(r["comm_s"], r["step_s"]) for r in steps
            if "comm_s" in r and r.get("step_s")]
    if comm:
        shares = [c / s for c, s in comm]
        summary["comm"] = {
            "samples": len(comm),
            "p50_ms": round(_percentile([c for c, _ in comm], 50) * 1e3, 3),
            "max_ms": round(max(c for c, _ in comm) * 1e3, 3),
            "share_mean": round(sum(shares) / len(shares), 4),
        }
    gs_events = [e for e in events if e.get("event") == "grad_sync"]
    gs_steps = [r["grad_sync"] for r in steps
                if isinstance(r.get("grad_sync"), dict)]
    if gs_events or gs_steps:
        last = gs_steps[-1] if gs_steps else {
            k: v for k, v in gs_events[-1].items()
            if k not in ("kind", "event", "t", "schema")
        }
        summary["grad_sync"] = last
    # sharding plan (ISSUE 15): mode + mesh shape + measured per-device
    # param/opt bytes, from the one routine `sharding` event
    sh_events = [e for e in events if e.get("event") == "sharding"]
    if sh_events:
        summary["sharding"] = {
            k: v for k, v in sh_events[-1].items()
            if k not in ("kind", "event", "t", "schema")
        }
    if mfu:
        summary["mfu"] = {
            "mean": round(sum(mfu) / len(mfu), 5),
            "max": round(max(mfu), 5),
        }
    throughputs = [r["imgs_per_sec"] for r in steps if "imgs_per_sec" in r]
    if throughputs:
        summary["imgs_per_sec"] = {
            "last": round(throughputs[-1], 2),
            "mean": round(sum(throughputs) / len(throughputs), 2),
        }
    if hbm:
        summary["hbm_high_water_bytes"] = int(max(hbm))
    if rss:
        summary["host_rss_high_water_bytes"] = int(max(rss))
    # input-pipeline snapshots are cumulative — the LAST one (run_end wins
    # over the last sampled step) summarizes the whole run
    input_snaps = [r["input"] for r in steps if isinstance(r.get("input"), dict)]
    if run_ends and isinstance(run_ends[-1].get("input"), dict):
        input_snaps.append(run_ends[-1]["input"])
    if input_snaps:
        summary["input"] = input_snaps[-1]
    # the snapshots' exact cumulative counters (ISSUE 25) between the first
    # and the last: the feed in steady state, the compile stall left out
    exact = [s for s in input_snaps
             if "staged_images" in s and "worker_busy_s" in s and "wall_s" in s]
    if len(exact) >= 2 and exact[-1]["wall_s"] > exact[0]["wall_s"]:
        first, last = exact[0], exact[-1]
        window_s = last["wall_s"] - first["wall_s"]
        summary["input_steady"] = {
            "seconds": round(window_s, 3),
            "staged_imgs_per_s": round(
                (last["staged_images"] - first["staged_images"]) / window_s, 2),
            "worker_busy_frac": round(
                (last["worker_busy_s"] - first["worker_busy_s"])
                / (max(last.get("workers", 1), 1) * window_s), 4),
        }
    # compile counters are cumulative too; set-up spans come once per run
    compiles = [r["compile"] for r in steps + run_ends
                if isinstance(r.get("compile"), dict)]
    if compiles:
        summary["compile"] = compiles[-1]
    setups = [e for e in events
              if e.get("event") == "setup" and isinstance(e.get("spans"), dict)]
    if setups:
        summary["setup_s"] = setups[-1]["spans"]
        for block in ("attn", "moe"):
            if isinstance(setups[-1].get(block), dict):
                summary[block] = setups[-1][block]
    if pods:
        spreads = [
            p["step_s_max"] - p["step_s_min"]
            for p in pods
            if "step_s_max" in p and "step_s_min" in p
        ]
        if spreads:
            summary["pod_step_spread_ms_max"] = round(max(spreads) * 1e3, 3)
    if supervisor:
        by_event: dict[str, int] = {}
        for r in supervisor:
            key = str(r.get("event", "unknown"))
            by_event[key] = by_event.get(key, 0) + 1
        exits = [r for r in supervisor if r.get("event") == "exit"]
        sup: dict = {
            "events": by_event,
            "launches": by_event.get("launch", 0),
            "restarts": by_event.get("restart", 0),
            # one kill may emit two records (sigterm escalation, then
            # sigkill); count children killed, not signals sent
            "kills": sum(1 for r in supervisor if r.get("event") == "kill"
                         and r.get("phase") != "sigkill"),
            "classifications": [str(r.get("classification", "?"))
                                for r in exits],
        }
        finals = [r for r in supervisor if r.get("event") in ("done", "give_up")]
        if finals:
            last = finals[-1]
            sup["outcome"] = str(last["event"])
            if "reason" in last:
                sup["reason"] = last["reason"]
        budgets = [r["budget_left"] for r in supervisor if "budget_left" in r]
        if budgets:
            sup["budget_left"] = budgets[-1]
        summary["supervisor"] = sup
        # elastic resize (ISSUE 11): the resize_* / mesh_change records ride
        # the same supervisor stream; fold them into their own section so a
        # resize reads as ONE incident (request → exit 49 → relaunch)
        resize_sec = _summarize_resize(supervisor)
        if resize_sec:
            summary["resize"] = resize_sec
    if serves and not fleet:
        # snapshots are cumulative; the last one summarizes the run.
        # With FLEET records present this section is suppressed: N
        # replicas each write their own cumulative stream, and "the last
        # merged snapshot" would present one arbitrary replica's
        # counters as the run's — the fleet section carries the honest
        # per-replica fold + served_total instead.
        last = serves[-1]
        summary["serve"] = {
            k: last[k]
            for k in ("requests", "served", "shed_overload", "shed_deadline",
                      "batch_errors", "batches", "occupancy_mean", "buckets",
                      "latency_ms", "queue_wait_ms", "cache", "draining",
                      "uptime_s")
            if k in last
        }
        summary["serve"]["snapshots"] = len(serves)
    if fleet:
        summary["fleet"] = _summarize_fleet(fleet, serves)
    if input_servers:
        summary["input_servers"] = _summarize_input_servers(input_servers)
    if banks:
        summary["bank"] = _summarize_bank(banks)
    health_sec = _summarize_health(steps, events)
    if health_sec:
        summary["health"] = health_sec
    if slos:
        summary["slo"] = _summarize_slo(slos)
    if run_ends:
        summary["run_end"] = run_ends[-1]
    return summary


_ADDITIVE_SERVER_STATS = ("shards", "streamed_mb", "decode_s",
                          "credit_stall_s", "wall_s", "errors",
                          "decode_failures", "decode_total")


def _summarize_input_servers(records: list[dict]) -> dict:
    """Fold the `kind:"input_server"` records of the staging-server
    telemetry dirs (ISSUE 14): per server, the cumulative `stats`
    counters SUMMED across decode-worker lives (a relaunch restarts
    them from zero — detected as a counter decrease, the obsd
    counter-reset discipline — so the kill-drill report still counts
    every shard the pre-kill life served), latency p50/p95 and
    cache-hit rate from the last life, plus the supervisor half's
    lifecycle counts (launches/ejections/kills/death classes) — one
    story per server, totals across the pool."""
    by_server: dict[int, dict] = {}
    for r in records:
        sid = int(r.get("server_id", -1))
        entry = by_server.setdefault(sid, {"events": {}})
        event = str(r.get("event", "?"))
        if event == "stats":
            snap = {
                k: r[k]
                for k in ("shards", "streamed_mb", "shard_s_p50",
                          "shard_s_p95", "decode_s", "credit_stall_s",
                          "wall_s", "errors", "connections",
                          "connections_peak", "cache_hit_rate",
                          "decode_failures", "decode_total")
                if k in r
            }
            prev = entry.get("stats")
            pid, prev_pid = r.get("pid"), entry.get("_stats_pid")
            if prev is not None:
                if pid is not None and prev_pid is not None:
                    # exact: a relaunch changes the worker pid — catches
                    # a new life whose first snapshot already exceeds
                    # the old life's last (counters never decreased)
                    relaunched = pid != prev_pid
                else:  # legacy records without pid: counter decrease
                    relaunched = (
                        snap.get("wall_s", 0) < prev.get("wall_s", 0)
                        or snap.get("shards", 0) < prev.get("shards", 0))
                if relaunched:
                    base = entry.setdefault("_lives_base", {})
                    for k in _ADDITIVE_SERVER_STATS:
                        base[k] = base.get(k, 0) + prev.get(k, 0)
            entry["_stats_pid"] = pid
            entry["stats"] = snap
        else:
            entry["events"][event] = entry["events"].get(event, 0) + 1
            if event == "worker_exit" and "classification" in r:
                entry.setdefault("death_classes", []).append(
                    str(r["classification"]))
    servers = {}
    totals = {"shards": 0, "streamed_mb": 0.0, "errors": 0}
    for sid in sorted(by_server):
        entry = by_server[sid]
        stats = entry.get("stats", {})
        entry.pop("_stats_pid", None)
        base = entry.pop("_lives_base", None)
        if base:
            stats = dict(stats)
            for k, v in base.items():
                stats[k] = round(v + stats.get(k, 0), 3)
            entry["stats"] = stats
        totals["shards"] += stats.get("shards", 0)
        totals["streamed_mb"] += stats.get("streamed_mb", 0.0)
        totals["errors"] += stats.get("errors", 0)
        servers[str(sid)] = entry
    return {"servers": servers, "totals": totals,
            "n_servers": len(servers)}


def _summarize_bank(banks: list[dict]) -> dict:
    """Fold the `kind:"bank"` lifecycle stream (ISSUE 16): builder
    progress (build_start/shard_done/build_done), each replica's atomic
    dual `swap`, and the fleet's `bank_waiting`/`quarantine`/
    `bank_quarantine`/`rollback`. Event names normalize to the same
    `bank_` prefix obsd uses at ingest, so the section's counters match
    `event:bank_*` SLO objectives line for line."""
    by_event: dict[str, int] = {}
    last_swap = None
    last_build = None
    for r in banks:
        name = str(r.get("event", "unknown"))
        if not name.startswith("bank"):
            name = "bank_" + name
        by_event[name] = by_event.get(name, 0) + 1
        if name == "bank_swap":
            last_swap = r
        elif name == "bank_build_done":
            last_build = r
    sec: dict = {
        "events": dict(sorted(by_event.items())),
        "builds": by_event.get("bank_build_done", 0),
        "swaps": by_event.get("bank_swap", 0),
        "quarantines": by_event.get("bank_quarantine", 0),
        "rollbacks": by_event.get("bank_rollback", 0),
    }
    if last_build is not None:
        sec["last_build"] = {
            k: last_build[k]
            for k in ("step", "rows", "feat_dim", "shards",
                      "manifest_sha256")
            if k in last_build
        }
    if last_swap is not None:
        sec["last_swap"] = {
            k: last_swap[k]
            for k in ("step", "bank_step", "rows", "generation",
                      "agreement")
            if k in last_swap
        }
        step, bank_step = last_swap.get("step"), last_swap.get("bank_step")
        if (isinstance(step, (int, float))
                and isinstance(bank_step, (int, float))):
            sec["age_steps"] = int(step - bank_step)
    return sec


def _summarize_health(steps: list[dict], events: list[dict]) -> dict | None:
    """Fold the learning-health story (ISSUE 13): the `health` blocks the
    driver stamps onto health-stride step records (in-graph collapse
    diagnostics — telemetry/health.py documents each key) plus the
    CollapseSentinel's `health` incident/recovery events. None when the
    run carried neither (health_stride=0 and no sentinel armed)."""
    blocks = [(r.get("step"), r["health"]) for r in steps
              if isinstance(r.get("health"), dict)]
    incidents = [e for e in events if e.get("event") == "health"]
    recoveries = [e for e in events if e.get("event") == "health_recovered"]
    if not blocks and not incidents and not recoveries:
        return None
    sec: dict = {"samples": len(blocks)}
    if blocks:
        sec["last"] = dict(blocks[-1][1])
        # collapse is a FLOOR violation: the window's worst (lowest)
        # margin/std tells the story the last sample can hide
        for key in ("logit_margin", "emb_std_q", "emb_std_k",
                    "qnorm_min", "acc1"):
            vals = [b[key] for _, b in blocks
                    if isinstance(b.get(key), (int, float))]
            if vals:
                sec.setdefault("min", {})[key] = min(vals)
    if incidents or recoveries:
        sec["incidents"] = {
            "fired": len(incidents),
            "recovered": len(recoveries),
            "predicates": [
                {k: e[k] for k in ("predicate", "step", "value",
                                   "threshold", "window") if k in e}
                for e in incidents[-8:]
            ],
        }
    return sec


def _summarize_slo(slos: list[dict]) -> dict:
    """Fold the `kind:"slo"` records obsd (ISSUE 12) appended into the
    stream: per-rule alert/recovery counts + whether the LAST transition
    left the rule alerting (the stream is ordered, so last wins)."""
    by_rule: dict[str, dict] = {}
    for r in slos:
        rule = str(r.get("rule", "?"))
        entry = by_rule.setdefault(rule, {
            "alerts": 0, "recoveries": 0, "active": False,
        })
        action = r.get("action")
        if action == "alert":
            entry["alerts"] += 1
            entry["active"] = True
        elif action == "recover":
            entry["recoveries"] += 1
            entry["active"] = False
        for k in ("objective", "threshold", "severity"):
            if k in r:
                entry[k] = r[k]
        if "value_fast" in r:
            entry["last_value"] = r["value_fast"]
    return {
        "alerts": sum(e["alerts"] for e in by_rule.values()),
        "recoveries": sum(e["recoveries"] for e in by_rule.values()),
        "active": sorted(r for r, e in by_rule.items() if e["active"]),
        "by_rule": by_rule,
    }


def _summarize_resize(supervisor: list[dict]) -> dict | None:
    """Fold resize_request / resize_relaunch / mesh_change supervisor
    records into one `resize` section. None when the run saw none."""
    requests = [r for r in supervisor if r.get("event") == "resize_request"]
    relaunches = [r for r in supervisor
                  if r.get("event") == "resize_relaunch"]
    mesh_changes = [r for r in supervisor if r.get("event") == "mesh_change"]
    reverts = [r for r in supervisor if r.get("event") == "resize_revert"]
    if not (requests or relaunches or mesh_changes or reverts):
        return None
    sec: dict = {
        "requests": len(requests),
        "relaunches": len(relaunches),
        "mesh_changes": len(mesh_changes),
    }
    if reverts:
        sec["reverts"] = len(reverts)
    transitions = []
    for r in relaunches:
        t = {k: r[k] for k in ("devices_from", "devices_to", "step",
                               "grad_sync_cadence", "source") if k in r}
        transitions.append(t)
    if transitions:
        sec["transitions"] = transitions
    return sec


def _summarize_fleet(fleet: list[dict], serves: list[dict]) -> dict:
    """Fold the `kind: "fleet"` lifecycle stream (ISSUE 10) + each
    replica's own serve snapshots (grouped by the `_src` tag the
    multi-dir loader stamps) into one section."""
    by_event: dict[str, int] = {}
    per_replica: dict[int, dict] = {}
    for r in fleet:
        event = str(r.get("event", "unknown"))
        by_event[event] = by_event.get(event, 0) + 1
        idx = r.get("replica")
        if idx is None:
            continue
        rep = per_replica.setdefault(int(idx), {
            "launches": 0, "restarts": 0, "kills": 0, "ejections": 0,
            "readmissions": 0, "reloads": 0, "classifications": [],
        })
        if event == "launch":
            rep["launches"] += 1
            rep["restarts"] = max(rep["launches"] - 1, 0)
        elif event == "kill" and r.get("phase") != "sigkill":
            rep["kills"] += 1  # one kill decision, not one per signal
        elif event == "eject":
            rep["ejections"] += 1
        elif event == "readmit":
            rep["readmissions"] += 1
        elif event == "reload_replica" and r.get("status") == "ok":
            rep["reloads"] += 1
        elif event == "replica_exit":
            rep["classifications"].append(str(r.get("classification", "?")))
    sec: dict = {"events": by_event, "replicas": per_replica}
    starts = [r for r in fleet if r.get("event") == "fleet_start"]
    if starts:
        sec["size"] = starts[-1].get("replicas")
    stats = [r for r in fleet if r.get("event") == "router_stats"]
    if stats:
        last = stats[-1]
        router = {
            k: last[k]
            for k in ("requests", "ok", "retries", "retry_ok",
                      "shed_no_backend", "upstream_timeout",
                      "upstream_error", "shed_deadline_router",
                      "passthrough_non_200", "healthy",
                      # ISSUE 12 autoscaler-schema fields
                      "outstanding", "latency_ms", "window", "interval_s",
                      # ISSUE 20 tier/sharded-kNN fields
                      "requests_interactive", "requests_batch",
                      "knn_fanout", "knn_partial", "ann_shards",
                      "knn_merge_ms")
            if k in last
        }
        reqs = router.get("requests", 0)
        shed = (router.get("shed_no_backend", 0)
                + router.get("upstream_timeout", 0)
                + router.get("upstream_error", 0)
                + router.get("shed_deadline_router", 0))
        router["shed_rate"] = round(shed / reqs, 4) if reqs else 0.0
        fanout = router.get("knn_fanout", 0)
        if fanout:
            router["knn_partial_rate"] = round(
                router.get("knn_partial", 0) / fanout, 4)
        sec["router"] = router
    # autoscale lifecycle (ISSUE 20): the actions and the last reason
    scaled = [r for r in fleet
              if str(r.get("event", "")).startswith("autoscale_")]
    if scaled:
        counts: dict[str, int] = {}
        for r in scaled:
            name = str(r.get("event"))
            counts[name] = counts.get(name, 0) + 1
        sec["autoscale"] = {
            "events": counts,
            "last": {k: scaled[-1][k]
                     for k in ("event", "replica", "shard", "reason",
                               "replicas", "t")
                     if k in scaled[-1]},
        }
    reload_events = ("reload_detected", "reload_replica", "reload_done",
                     "reload_failed", "reload_quarantine",
                     "reload_bad_layout")
    history = [
        {k: r[k] for k in ("event", "step", "replica", "reason", "status",
                           "path", "t") if k in r}
        for r in fleet if r.get("event") in reload_events
    ]
    if history:
        sec["reload_history"] = history[-32:]
    # each replica's OWN last serve snapshot (cumulative): the single-file
    # `serve:` section can't tell N servers apart
    by_src: dict[str, dict] = {}
    for s in serves:
        src = s.get("_src")
        if src:
            by_src[src] = s
    if by_src:
        sec["serve_by_replica"] = {
            src: {
                k: snap[k]
                for k in ("requests", "served", "shed_overload",
                          "shed_deadline", "batches", "occupancy_mean",
                          "reloads")
                if k in snap
            }
            for src, snap in sorted(by_src.items())
        }
        sec["served_total"] = sum(
            s.get("served", 0) for s in by_src.values()
        )
    return sec


def fold_programs(summary: dict, inventory: dict) -> dict:
    """Fold a progcheck program inventory (`python -m tools.progcheck
    --inventory`, ISSUE 9) into the summary: program counts, per-mode
    gradsync payload, and the MFU cross-check — XLA `cost_analysis` FLOPs
    vs the MFUEstimator's analytic count for the same proxy program, so a
    drift in the analytic model (the numerator every reported MFU rests
    on) is visible next to the compiler's own arithmetic."""
    progs = inventory.get("programs", [])
    sec: dict = {
        "count": inventory.get("program_count", len(progs)),
        "mesh_size": inventory.get("mesh_size"),
        "by_family": inventory.get("by_family", {}),
    }
    sync = {
        p["mode"]: p["sync_bytes_per_step"]
        for p in progs
        if p.get("family") == "gradsync" and "sync_bytes_per_step" in p
    }
    if sync:
        sec["gradsync_bytes_per_step"] = sync
    cross = [
        {
            "name": p["name"],
            "cost_analysis_flops": p["flops"],
            "analytic_flops": p["analytic_flops"],
            "ratio": p.get("flops_vs_analytic"),
        }
        for p in progs
        if p.get("flops") is not None and p.get("analytic_flops")
    ]
    if cross:
        sec["mfu_cross_check"] = cross
    summary["programs"] = sec
    return summary


def render(summary: dict) -> str:
    """Human-readable report from a summarize() dict."""
    lines = []
    run = summary.get("run", {})
    if run:
        lines.append(
            "run: {name} ({variant}/{arch}) batch={batch_size} "
            "chips={n_chips} procs={n_procs}".format(
                **{k: run.get(k, "?") for k in
                   ("name", "variant", "arch", "batch_size", "n_chips",
                    "n_procs")}
            )
        )
        if run.get("peak_flops_per_chip"):
            lines.append(
                f"  MFU basis: {run['peak_flops_per_chip'] / 1e12:.0f} "
                f"TFLOP/s/chip peak, {run.get('flops_per_step', 0) / 1e9:.2f} "
                f"GFLOP/step analytic"
            )
    lines.append(
        f"records: {summary['records']} ({summary['steps']} steps, "
        f"{summary['runs']} run(s), {summary['pod_records']} pod, "
        f"{summary['skipped_lines']} unparseable line(s) skipped)"
    )
    pct = summary.get("step_time_ms")
    if pct:
        lines.append(
            f"step time: p50 {pct['p50']:.1f} ms · p95 {pct['p95']:.1f} ms "
            f"· p99 {pct['p99']:.1f} ms"
        )
        share = summary.get("phase_share", {})
        more = "".join(
            f" · {name} {100 * share[name]:.1f}%"
            for name in ("wait", "fence", "readback", "telemetry", "loop")
            if name in share)
        lines.append(
            f"  phase share: data {100 * share.get('data', 0):.1f}% · "
            f"host {100 * share.get('host', 0):.1f}%"
            + (more if more else " (rest: async device/meters)")
        )
        drains = summary.get("drains")
        if drains:
            lines.append(
                f"  drains: {drains['steps']} steps dispatched to an idle "
                f"device ({100 * drains['share']:.1f}% of steps) · their "
                f"dispatch took {100 * drains['dispatch_share']:.2f}% of the "
                f"time"
            )
        collected = summary.get("gc")
        if collected:
            lines.append(
                f"  gc: {collected['collections']} collections "
                f"({collected['full']} full) · {collected['ms_each']:.3f} ms "
                f"each · {collected['ms_per_step']:.3f} ms a step · worst "
                f"step {collected['worst_step_ms']:.1f} ms"
            )
    stalls = summary.get("stalls")
    if stalls:
        each = ", ".join(
            f"step {e['step']} +{1e3 * (e['excess_s'] or 0.0):.0f} ms in "
            f"{e['phase']} (gc2 {e['gc2_n']}, "
            f"{'starved' if e['starved'] else 'queued'}, queue depth "
            f"{e['queue_depth']})" for e in stalls["each"])
        lines.append(
            f"stalls: {stalls['count']} · {stalls['total_ms']:.0f} ms over "
            f"the rolling median · {each}"
        )
    dev = summary.get("device_time_ms")
    if dev:
        lines.append(
            f"device drain (fenced, {dev['samples']} samples): "
            f"p50 {dev['p50']:.1f} ms · max {dev['max']:.1f} ms"
        )
    gs = summary.get("grad_sync")
    if gs:
        extras = []
        if "bucket_mb" in gs:
            extras.append(f"{gs['bucket_mb']} MiB × {gs.get('buckets', '?')} "
                          "buckets")
        if "quant_dtype" in gs:
            extras.append(str(gs["quant_dtype"]))
        if "cadence" in gs:
            extras.append(f"top-{100 * gs.get('topk', 0):.1f}% every "
                          f"{gs['cadence']} step(s)")
        lines.append(
            f"grad sync: {gs.get('mode', '?')} · "
            f"{gs.get('sync_bytes_per_step', 0) / 2**20:.2f} MiB/step/device"
            + (f" ({', '.join(extras)})" if extras else "")
        )
    comm = summary.get("comm")
    if comm:
        lines.append(
            f"  comm phase (fenced, {comm['samples']} samples): "
            f"p50 {comm['p50_ms']:.1f} ms · max {comm['max_ms']:.1f} ms · "
            f"share {100 * comm['share_mean']:.1f}%"
        )
    sh = summary.get("sharding")
    if sh:
        mesh = sh.get("mesh_shape")
        mesh_txt = ("×".join(f"{k}={v}" for k, v in mesh.items())
                    if isinstance(mesh, dict) else "?")
        lines.append(
            f"sharding: {sh.get('mode', '?')} (mesh {mesh_txt}) · "
            f"params {sh.get('param_bytes_per_device', 0) / 2**20:.2f} "
            f"MiB/device · opt "
            f"{sh.get('opt_bytes_per_device', 0) / 2**20:.2f} MiB/device"
        )
    mfu = summary.get("mfu")
    if mfu:
        label = ""
        if sh and sh.get("mode") and sh.get("mode") != "dp":
            # ISSUE 15 satellite: MFU is reported per sharding mode — the
            # FLOPs basis is layout-invariant, the label says what layout
            # achieved it
            label = f" [{sh['mode']}]"
        lines.append(f"MFU{label}: mean {100 * mfu['mean']:.2f}% · "
                     f"max {100 * mfu['max']:.2f}%")
    elif summary["steps"]:
        # only a TRAINING stream can owe an MFU; a serve-only events file
        # (zero step records) has nothing to apologize for
        lines.append(
            "MFU: n/a (no peak-FLOPs basis for this device_kind — re-run "
            "training with peak_flops_per_chip set in the config)"
        )
    thr = summary.get("imgs_per_sec")
    if thr:
        lines.append(
            f"throughput: {thr['last']:.1f} imgs/s (rolling, end of run) · "
            f"{thr['mean']:.1f} mean"
        )
    if "hbm_high_water_bytes" in summary:
        lines.append(
            f"HBM high-water: {summary['hbm_high_water_bytes'] / 2**30:.2f} GiB"
        )
    if "host_rss_high_water_bytes" in summary:
        lines.append(
            f"host RSS high-water: "
            f"{summary['host_rss_high_water_bytes'] / 2**30:.2f} GiB"
        )
    inp = summary.get("input")
    if inp:
        lines.append(
            f"input: {inp.get('staged_batches', 0)} staged batches "
            f"({inp.get('staged_mb', 0):.0f} MiB) · queue depth mean "
            f"{inp.get('queue_depth_mean', 0):.2f} · "
            f"{inp.get('workers', 1)} worker(s) busy "
            f"{100 * inp.get('worker_busy_frac', 0):.1f}%"
        )
        lines.append(
            f"  staged-batch latency: p50 "
            f"{1e3 * inp.get('staged_batch_s_p50', 0):.1f} ms · p95 "
            f"{1e3 * inp.get('staged_batch_s_p95', 0):.1f} ms"
        )
        if "cache_hit_rate" in inp:
            lines.append(
                f"  decode-once cache: {100 * inp['cache_hit_rate']:.1f}% hit "
                f"({inp.get('cache_hits', 0)} hit / "
                f"{inp.get('cache_misses', 0)} miss)"
            )
        if inp.get("wall_s"):
            lines.append(
                f"  credit stalls: {inp.get('credit_stall_s', 0):.1f} s "
                f"blocked on an empty ready queue "
                f"({100 * inp.get('credit_stall_s', 0) / inp['wall_s']:.1f}% "
                f"of {inp['wall_s']:.0f} s)"
            )
        steady = summary.get("input_steady")
        if steady:
            lines.append(
                f"  steady state ({steady['seconds']:.0f} s between the first "
                f"and last snapshot): {steady['staged_imgs_per_s']:.0f} imgs/s "
                f"staged · workers busy "
                f"{100 * steady['worker_busy_frac']:.1f}%"
            )
    comp = summary.get("compile")
    if comp:
        lines.append(
            f"compile: {comp.get('n', 0)} programs · backend "
            f"{comp.get('backend_s', 0):.1f} s · trace+lower "
            f"{comp.get('trace_lower_s', 0):.1f} s · persistent cache "
            f"{comp.get('cache_hits', 0)} hit / "
            f"{comp.get('cache_misses', 0)} miss · step program "
            f"{comp.get('fused_step_n', 0)}× {comp.get('fused_step_s', 0):.1f} s"
        )
    setup = summary.get("setup_s")
    if setup:
        attn, moe = summary.get("attn"), summary.get("moe")
        lines.append(
            "set-up: " + " · ".join(
                f"{name} {secs:.2f} s" for name, secs in
                sorted(setup.items(), key=lambda kv: -kv[1]))
            + (f" · attention {attn.get('path')}, {attn.get('tiles_skipped', 0)} of "
               f"{attn.get('tiles', 0)} score tiles skipped, q/k prep "
               f"{attn.get('qk_prep', 'xla')}" if attn else "")
            + (f", top-{attn['select'].get('topk')} selection by {attn['select'].get('path')}"
               if attn and attn.get("select") else "")
            + (f", remat keeps {len(attn['kept'].get('names', ()))} named values, "
               f"{attn['kept'].get('bytes_per_layer', 0) / 1e6:.1f} MB a layer"
               if attn and attn.get("kept") else "")
            + (f" · expert rows by {moe.get('dispatch')}, {moe.get('rows', 0)} a pass, "
               f"{moe.get('spill_rows', 0)} in the small spill pass, "
               f"{moe.get('passes', 0)} whole passes after it" if moe else "")
        )
    isv = summary.get("input_servers")
    if isv:
        tot = isv.get("totals", {})
        lines.append(
            f"input service: {isv.get('n_servers', 0)} staging server(s) · "
            f"{tot.get('shards', 0)} shards "
            f"({tot.get('streamed_mb', 0):.0f} MiB streamed, "
            f"{tot.get('errors', 0)} error(s))"
        )
        for sid, entry in sorted(isv.get("servers", {}).items(),
                                 key=lambda kv: int(kv[0])):
            stats = entry.get("stats", {})
            parts = [f"  server {sid}:"]
            if stats:
                parts.append(
                    f"{stats.get('shards', 0)} shards · shard p50 "
                    f"{1e3 * stats.get('shard_s_p50', 0):.1f} ms / p95 "
                    f"{1e3 * stats.get('shard_s_p95', 0):.1f} ms · "
                    f"{stats.get('streamed_mb', 0):.0f} MiB"
                )
                if "cache_hit_rate" in stats:
                    parts.append(
                        f"· cache {100 * stats['cache_hit_rate']:.1f}% hit")
                if stats.get("decode_failures"):
                    parts.append(
                        f"· DECODE FAILURES "
                        f"{stats['decode_failures']}/"
                        f"{stats.get('decode_total', 0)} (zero canvases "
                        "served — the train host cannot see these)"
                    )
                if stats.get("wall_s"):
                    # credit_stall_s accumulates CONCURRENTLY across the
                    # client connections: normalize per connection or a
                    # healthy 4-stream run renders a nonsense 360%. Peak,
                    # not the live gauge — the final snapshot lands after
                    # clients disconnected (gauge back at 0)
                    conns = max(int(stats.get("connections_peak")
                                    or stats.get("connections", 1)
                                    or 1), 1)
                    parts.append(
                        f"· idle-for-credit "
                        f"{100 * stats.get('credit_stall_s', 0) / (stats['wall_s'] * conns):.0f}%/conn"
                    )
            ev = entry.get("events", {})
            life = []
            for key in ("launch", "eject", "kill", "worker_exit",
                        "give_up"):
                if ev.get(key):
                    life.append(f"{key}×{ev[key]}")
            if life:
                parts.append("· " + " ".join(life))
            if entry.get("death_classes"):
                parts.append(
                    "(" + ", ".join(entry["death_classes"]) + ")")
            lines.append(" ".join(parts))
    if "pod_step_spread_ms_max" in summary:
        lines.append(
            f"pod: {summary['pod_records']} records, worst cross-host step "
            f"spread {summary['pod_step_spread_ms_max']:.1f} ms"
        )
    sup = summary.get("supervisor")
    if sup:
        outcome = sup.get("outcome", "running")
        lines.append(
            f"supervisor: {sup['launches']} launch(es), {sup['restarts']} "
            f"restart(s), {sup['kills']} kill(s) — {outcome}"
            + (f" ({sup['reason']})" if sup.get("reason") else "")
        )
        if sup["classifications"]:
            counts: dict[str, int] = {}
            for c in sup["classifications"]:
                counts[c] = counts.get(c, 0) + 1
            detail = ", ".join(f"{k}×{v}" for k, v in sorted(counts.items()))
            lines.append(f"  death classifications: {detail}")
        if "budget_left" in sup:
            lines.append(f"  restart budget left: {sup['budget_left']}")
    rsz = summary.get("resize")
    if rsz:
        hops = []
        for t in rsz.get("transitions", ()):
            frm = t.get("devices_from")
            arrow = (f"{'?' if frm is None else frm}→"
                     f"{t.get('devices_to') or 'visible'}")
            if "step" in t:
                arrow += f"@{t['step']}"
            if "grad_sync_cadence" in t:
                arrow += f" (cadence {t['grad_sync_cadence']})"
            hops.append(arrow)
        lines.append(
            f"resize: {rsz['relaunches']} relaunch(es) from "
            f"{rsz['requests']} request(s)"
            + (f" — {' · '.join(hops)}" if hops else "")
            + (f" · {rsz['reverts']} reverted (unbootable argv)"
               if rsz.get("reverts") else "")
        )
        if rsz.get("mesh_changes"):
            lines.append(
                f"  mesh changes observed at relaunch preflight: "
                f"{rsz['mesh_changes']}"
            )
    srv = summary.get("serve")
    if srv:
        shed = srv.get("shed_overload", 0) + srv.get("shed_deadline", 0)
        lines.append(
            f"serve: {srv.get('requests', 0)} requests "
            f"({srv.get('served', 0)} served, {shed} shed: "
            f"{srv.get('shed_overload', 0)} overload / "
            f"{srv.get('shed_deadline', 0)} deadline, "
            f"{srv.get('batch_errors', 0)} batch error(s))"
        )
        lat = srv.get("latency_ms", {})
        if lat:
            lines.append(
                f"  latency: p50 {lat.get('p50', 0):.1f} ms · "
                f"p95 {lat.get('p95', 0):.1f} ms · p99 {lat.get('p99', 0):.1f} ms"
            )
        lines.append(
            f"  batches: {srv.get('batches', 0)} over buckets "
            f"{srv.get('buckets', [])} · occupancy mean "
            f"{100 * srv.get('occupancy_mean', 0):.1f}%"
        )
        cache = srv.get("cache")
        if cache:
            lines.append(
                f"  embed cache: {100 * cache.get('hit_rate', 0):.1f}% hit "
                f"({cache.get('hits', 0)} hit / {cache.get('misses', 0)} "
                f"miss, {cache.get('entries', 0)} entries)"
            )
        tiers = srv.get("tiers")
        if tiers:
            per = " · ".join(
                f"{t} {c.get('submitted', 0)} submitted "
                f"({c.get('shed_overload', 0)}+{c.get('shed_deadline', 0)} "
                f"shed)"
                for t, c in sorted(tiers.items())
            )
            lines.append(f"  tiers: {per}")
        ann = srv.get("ann")
        if ann:
            recall = ann.get("recall_probe")
            lines.append(
                f"ann: shard {ann.get('shard', 0)}/{ann.get('shards', 1)} "
                f"— {ann.get('owned_rows', '?')} rows in "
                f"{ann.get('cells', '?')} cells (nprobe "
                f"{ann.get('nprobe', '?')}, rerank {ann.get('rerank', '?')})"
                + (f" · recall@1 probe {recall:.4f}"
                   if isinstance(recall, (int, float)) else "")
                + f" · {ann.get('candidate_calls', 0)} candidate call(s)"
            )
    flt = summary.get("fleet")
    if flt:
        router = flt.get("router", {})
        lines.append(
            f"fleet: {flt.get('size', len(flt.get('replicas', {})))} "
            f"replica(s) · router {router.get('requests', 0)} requests "
            f"({router.get('retries', 0)} retried, shed rate "
            f"{100 * router.get('shed_rate', 0):.2f}%)"
        )
        lat = router.get("latency_ms")
        if lat:
            lines.append(
                f"  router latency (window {router.get('window', '?')}): "
                f"p50 {lat.get('p50', 0):.1f} ms · "
                f"p95 {lat.get('p95', 0):.1f} ms · "
                f"p99 {lat.get('p99', 0):.1f} ms · outstanding "
                f"{router.get('outstanding', 0)}"
            )
        if "requests_interactive" in router or "requests_batch" in router:
            lines.append(
                f"  tiers: {router.get('requests_interactive', 0)} "
                f"interactive / {router.get('requests_batch', 0)} batch"
            )
        if router.get("knn_fanout"):
            merge = router.get("knn_merge_ms") or {}
            lines.append(
                f"  knn fan-out ({router.get('ann_shards', '?')} shards): "
                f"{router['knn_fanout']} scatter(s), "
                f"{router.get('knn_partial', 0)} partial "
                f"({100 * router.get('knn_partial_rate', 0):.2f}%)"
                + (f" · merge p95 {merge.get('p95', 0):.1f} ms"
                   if merge else "")
            )
        scale = flt.get("autoscale")
        if scale:
            counts = scale.get("events", {})
            last = scale.get("last", {})
            lines.append(
                "autoscale: "
                + " · ".join(f"{k.replace('autoscale_', '')} ×{v}"
                             for k, v in sorted(counts.items()))
                + (f" — last: {last.get('event', '?')} replica "
                   f"{last.get('replica', '?')} ({last.get('reason', '')})"
                   if last else "")
            )
        for idx, rep in sorted(flt.get("replicas", {}).items()):
            counts: dict[str, int] = {}
            for c in rep["classifications"]:
                counts[c] = counts.get(c, 0) + 1
            deaths = ", ".join(f"{k}×{v}" for k, v in sorted(counts.items()))
            lines.append(
                f"  replica {idx}: {rep['launches']} launch(es), "
                f"{rep['restarts']} restart(s), {rep['kills']} kill(s), "
                f"{rep['ejections']} ejection(s)"
                + (f" — deaths: {deaths}" if deaths else "")
            )
        srv_by = flt.get("serve_by_replica")
        if srv_by:
            per = " · ".join(
                f"{src} {snap.get('served', 0)}/{snap.get('requests', 0)}"
                for src, snap in srv_by.items()
            )
            lines.append(
                f"  served (per replica, served/requests): {per} — "
                f"total {flt.get('served_total', 0)}"
            )
        history = flt.get("reload_history", [])
        done = [h for h in history if h["event"] == "reload_done"]
        quarantined = [h for h in history
                       if h["event"] == "reload_quarantine"]
        if history:
            lines.append(
                f"  reloads: {len(done)} deployed "
                f"({', '.join(str(h.get('step')) for h in done[-6:])})"
                + (f" · {len(quarantined)} quarantined "
                   f"({', '.join(str(h.get('step')) for h in quarantined[-6:])})"
                   if quarantined else "")
            )
    bank = summary.get("bank")
    if bank:
        lines.append(
            f"bank: {bank.get('builds', 0)} build(s) · "
            f"{bank.get('swaps', 0)} dual swap(s) · "
            f"{bank.get('quarantines', 0)} quarantine(s) · "
            f"{bank.get('rollbacks', 0)} rollback(s)"
        )
        lb = bank.get("last_build")
        if lb:
            lines.append(
                f"  last build: step {lb.get('step', '?')} — "
                f"{lb.get('rows', '?')} rows × {lb.get('feat_dim', '?')} "
                f"dims in {lb.get('shards', '?')} shard(s)"
            )
        ls = bank.get("last_swap")
        if ls:
            agree = ls.get("agreement")
            lines.append(
                f"  last swap: checkpoint step {ls.get('step', '?')} + "
                f"bank step {ls.get('bank_step', '?')} "
                f"(generation {ls.get('generation', '?')}"
                + (f", probe agreement {agree:.4f}"
                   if isinstance(agree, (int, float)) else "")
                + f") — bank age {bank.get('age_steps', '?')} step(s)"
            )
    health = summary.get("health")
    if health:
        last = health.get("last", {})
        parts = [f"health: {health['samples']} sample(s)"]
        if "logit_margin" in last:
            worst = health.get("min", {}).get("logit_margin")
            parts.append(
                f"margin {last['logit_margin']:.4f}"
                + (f" (min {worst:.4f})" if worst is not None else "")
            )
        if "emb_std_k" in last:
            parts.append(
                f"emb std q/k {last.get('emb_std_q', 0):.4f}/"
                f"{last['emb_std_k']:.4f}"
            )
        if "pdrift" in last:
            parts.append(f"q-k drift {last['pdrift']:.4f}")
        lines.append(" · ".join(parts))
        if "qnorm_mean" in last:
            lines.append(
                f"  queue: norm mean {last['qnorm_mean']:.4f} min "
                f"{last.get('qnorm_min', 0):.4f} · age "
                f"{last.get('qage_steps', 0):.0f} step(s)"
                + (f" · participation ratio {last['emb_pr_q']:.1f}"
                   if "emb_pr_q" in last else "")
            )
        if "moe_assign_per_token" in last:
            # a routed token encoder (models/sdar.py): what its router sent
            # to the experts this chip holds
            lines.append(
                f"  experts: {last['moe_assign_per_token']:.3f} assignment(s) "
                f"a token to held experts · fullest over mean load "
                f"{last.get('moe_load_max_over_mean', 0):.3f}"
            )
        if "ut_pass_delta" in last:
            # a looped token encoder (models/ouro.py): how often the shared
            # stack ran and how far its last pass still moved the state
            lines.append(
                f"  loop: {last.get('ut_passes', 0):.0f} pass(es) over the shared "
                f"stack · the last moved the state by {last['ut_pass_delta']:.4f} "
                f"of its norm"
            )
        if "sel_keys_per_query" in last:
            # a token encoder whose attention selects its keys (models/keye.py):
            # how many a query kept, and what a tile-skipping kernel could not skip
            lines.append(
                f"  sparse: {last['sel_keys_per_query']:.1f} key(s) a query selected · "
                f"{100 * last.get('sel_live_tile_share', 0):.1f} % of the causal "
                f"score tiles hold a selected pair (worst layer)"
            )
        inc = health.get("incidents")
        if inc:
            preds = ", ".join(
                f"{p.get('predicate', '?')}@{p.get('step', '?')}"
                for p in inc.get("predicates", ())
            )
            lines.append(
                f"  collapse incidents: {inc['fired']} fired"
                + (f" ({preds})" if preds else "")
                + f" · {inc['recovered']} recovered"
            )
    slo = summary.get("slo")
    if slo:
        active = slo.get("active", [])
        lines.append(
            f"slo: {slo.get('alerts', 0)} alert(s), "
            f"{slo.get('recoveries', 0)} recovery(ies)"
            + (f" — ACTIVE: {', '.join(active)}" if active
               else " — all clear")
        )
        for rule, e in sorted(slo.get("by_rule", {}).items()):
            detail = (f"{e.get('objective', '?')} vs "
                      f"{e.get('threshold', '?')}")
            if "last_value" in e:
                detail += f", last {e['last_value']}"
            lines.append(
                f"  {rule}: {e['alerts']} alert(s) / "
                f"{e['recoveries']} recovery(ies) ({detail})"
                + (" [ACTIVE]" if e.get("active") else "")
            )
    progs = summary.get("programs")
    if progs:
        fams = ", ".join(f"{k}×{v}" for k, v in
                         sorted(progs.get("by_family", {}).items()))
        lines.append(f"programs: {progs.get('count', 0)} audited ({fams})")
        sync = progs.get("gradsync_bytes_per_step")
        if sync:
            detail = " · ".join(f"{m} {b} B" for m, b in sorted(sync.items()))
            lines.append(f"  gradsync payload/step/device: {detail}")
        for c in progs.get("mfu_cross_check", ())[:4]:
            lines.append(
                f"  {c['name']}: cost_analysis "
                f"{c['cost_analysis_flops'] / 1e6:.1f} MFLOP vs analytic "
                f"{c['analytic_flops'] / 1e6:.1f} MFLOP"
                + (f" (×{c['ratio']:.2f})" if c.get("ratio") else "")
            )
    inc = summary.get("incidents", {})
    if inc:
        detail = ", ".join(f"{k}×{v}" for k, v in sorted(inc.items()))
        lines.append(f"incidents: {summary['incidents_total']} ({detail})")
    else:
        lines.append("incidents: none")
    routine = {
        k: v for k, v in summary.get("events_by_kind", {}).items()
        if k not in inc
    }
    if routine:
        detail = ", ".join(f"{k}×{v}" for k, v in sorted(routine.items()))
        lines.append(f"routine events: {detail}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# live tail (--follow)
# ---------------------------------------------------------------------------


def render_record(rec: dict) -> str | None:
    """One human line per record for the live tail; None for record kinds
    with no line-by-line story (pod vectors ride the summary)."""
    kind = rec.get("kind")
    if kind == "step":
        parts = [f"step {rec.get('step', '?'):>6}"]
        if "step_s" in rec:
            parts.append(f"{1e3 * rec['step_s']:8.1f} ms")
        share = []
        for phase in ("data_s", "host_s", "telemetry_s", "wait_s",
                      "fence_s", "readback_s", "loop_s"):
            if phase in rec and rec.get("step_s"):
                share.append(
                    f"{phase[:-2]} {100 * rec[phase] / rec['step_s']:.0f}%"
                )
        if share:
            parts.append("(" + " · ".join(share) + ")")
        if "imgs_per_sec" in rec:
            parts.append(f"{rec['imgs_per_sec']:.1f} img/s")
        if "loss" in rec:
            parts.append(f"loss {rec['loss']:.4f}"
                         if isinstance(rec["loss"], float)
                         else f"loss {rec['loss']}")
        line = "  ".join(parts)
        health = rec.get("health")
        if isinstance(health, dict):
            # learning-health stride sample (ISSUE 13): its own tail line
            # so a margin sliding toward 0 jumps out of the step stream
            hp = [f"health: step {rec.get('step', '?'):>6}"]
            for key, label in (("logit_margin", "margin"),
                               ("emb_std_q", "std_q"),
                               ("emb_std_k", "std_k"),
                               ("qnorm_min", "qnorm_min"),
                               ("pdrift", "drift")):
                if isinstance(health.get(key), (int, float)):
                    hp.append(f"{label} {health[key]:.4f}")
            line += "\n" + "  ".join(hp)
        return line
    if kind == "event":
        name = rec.get("event", "?")
        detail = " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("v", "t", "kind", "event", "msg", "run_id",
                         "trace_id")
        )
        msg = rec.get("msg", "")
        return f"[{name}] {msg}{' ' if msg and detail else ''}{detail}".rstrip()
    if kind == "supervisor":
        detail = " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("v", "t", "kind", "event", "run_id", "trace_id")
        )
        event = str(rec.get("event", "?"))
        if event.startswith("resize") or event == "mesh_change":
            # elastic transitions get their own live-tail prefix (ISSUE 11
            # satellite), same as fleet lines — a resize in progress should
            # jump out of the step stream
            return f"resize: {event} {detail}".rstrip()
        return f"supervisor: {event} {detail}".rstrip()
    if kind == "fleet":
        detail = " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("v", "t", "kind", "event", "run_id", "trace_id")
        )
        return f"fleet: {rec.get('event', '?')} {detail}".rstrip()
    if kind == "bank":
        # bank lifecycle (ISSUE 16): a build/swap/quarantine/rollback in
        # progress gets the fleet-style detail line
        detail = " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("v", "t", "kind", "event", "run_id", "trace_id")
        )
        return f"bank: {rec.get('event', '?')} {detail}".rstrip()
    if kind == "input_server":
        # staging-server stream (ISSUE 14): stats snapshots get a compact
        # throughput line, lifecycle transitions the fleet-style detail
        sid = rec.get("server_id", "?")
        if rec.get("event") == "stats":
            return (
                f"input: server {sid} {rec.get('shards', 0)} shards · "
                f"p50 {1e3 * rec.get('shard_s_p50', 0):.1f} ms · "
                f"{rec.get('streamed_mb', 0):.0f} MiB · "
                f"{rec.get('errors', 0)} error(s)"
            )
        detail = " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("v", "t", "kind", "event", "run_id", "trace_id",
                         "server_id")
        )
        return f"input: server {sid} {rec.get('event', '?')} {detail}".rstrip()
    if kind == "slo":
        # obsd transitions (ISSUE 12): an alert in progress must jump out
        # of the step stream the way resize/fleet lines do
        action = str(rec.get("action", "?")).upper()
        parts = [f"slo: {action} {rec.get('rule', '?')}"]
        if "value_fast" in rec:
            parts.append(
                f"{rec.get('objective', '?')}={rec['value_fast']} "
                f"(slow {rec.get('value_slow', '?')}) "
                f"{rec.get('op', '>')} {rec.get('threshold', '?')}"
            )
        if "run_id" in rec:
            parts.append(f"run={rec['run_id']}")
        return " ".join(parts)
    if kind == "serve":
        lat = rec.get("latency_ms") or {}
        line = (
            f"serve: {rec.get('served', 0)}/{rec.get('requests', 0)} served"
            f" · p95 {lat.get('p95', 0):.1f} ms · queue "
            f"{rec.get('queue_depth', 0)}"
        )
        ann = rec.get("ann")
        if isinstance(ann, dict):
            # sharded ANN (ISSUE 20): the recall probe rides the tail so
            # a quantizer degrading after a swap jumps out of the stream
            recall = ann.get("recall_probe")
            line += (
                f" · ann {ann.get('shard', 0)}/{ann.get('shards', 1)}"
                + (f" recall {recall:.3f}"
                   if isinstance(recall, (int, float)) else "")
            )
        return line
    if kind == "run_start":
        return (f"run_start: {rec.get('name', '?')} arch="
                f"{rec.get('arch', '?')} batch={rec.get('batch_size', '?')}"
                f" run_id={rec.get('run_id', '-')}")
    if kind == "run_end":
        return (f"run_end: {rec.get('steps', 0)} steps, "
                f"{rec.get('incidents', 0)} incident(s)")
    return None


def follow(path: str, out=None, poll_secs: float = 0.5, stop=None,
           from_start: bool = True) -> int:
    """Tail `path`, rendering records as complete lines land. Returns the
    number of records rendered (useful for tests; the CLI runs until
    interrupted). `stop` is an optional threading.Event-like object."""
    out = out or sys.stdout
    rendered = 0
    offset = 0
    buffer = b""
    if not from_start:
        try:
            offset = os.path.getsize(path)
        except OSError:
            offset = 0
    while stop is None or not stop.is_set():
        try:
            size = os.path.getsize(path)
        except OSError:
            time.sleep(poll_secs)  # not created yet (child still booting)
            continue
        if size < offset:  # truncated/rotated: start over
            offset, buffer = 0, b""
        if size > offset:
            with open(path, "rb") as f:
                f.seek(offset)
                chunk = f.read()
            offset += len(chunk)
            buffer += chunk
            # partial-line safety: only lines TERMINATED by a newline are
            # parsed; the unterminated tail waits for its next chunk
            *complete, buffer = buffer.split(b"\n")
            for raw in complete:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    rec = json.loads(raw.decode("utf-8", errors="replace"))
                except json.JSONDecodeError:
                    continue
                if not isinstance(rec, dict):
                    continue
                line = render_record(rec)
                if line is not None:
                    print(line, file=out, flush=True)
                    rendered += 1
        else:
            time.sleep(poll_secs)
    return rendered


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    parser.add_argument("events",
                        help="path to telemetry events.jsonl, or a fleet "
                             "telemetry DIRECTORY (merges its "
                             "events.jsonl + replica*/events.jsonl)")
    parser.add_argument("--json", action="store_true",
                        help="emit one machine-readable summary object")
    parser.add_argument("--follow", action="store_true",
                        help="live-tail: render step/incident/supervisor "
                             "lines as they land (ctrl-C to stop)")
    parser.add_argument("--poll-secs", type=float, default=0.5,
                        help="--follow poll cadence")
    parser.add_argument("--programs", default=None, metavar="INVENTORY",
                        help="progcheck --inventory JSON to fold in "
                             "(program counts, gradsync payload, MFU "
                             "cross-check)")
    args = parser.parse_args(argv)
    if args.follow:
        path = args.events
        if os.path.isdir(path):  # fleet dir: follow the fleet's own stream
            path = os.path.join(path, "events.jsonl")
        try:
            follow(path, poll_secs=args.poll_secs)
        except KeyboardInterrupt:
            pass
        return 0
    try:
        records, skipped = load_events_multi(expand_events_arg(args.events))
    except OSError as e:
        print(f"cannot read {args.events}: {e}", file=sys.stderr)
        return 2
    summary = summarize(records, skipped)
    if args.programs:
        try:
            with open(args.programs, encoding="utf-8") as f:
                fold_programs(summary, json.load(f))
        except (OSError, json.JSONDecodeError, ValueError) as e:
            print(f"cannot read program inventory {args.programs}: {e}",
                  file=sys.stderr)
            return 2
    if args.json:
        print(json.dumps(summary, default=float))
    else:
        print(render(summary))
    return 0 if summary["steps"] or summary["records"] else 1


if __name__ == "__main__":
    sys.exit(main())
