"""From the names the program gives its own work to per-layer numbers.

The program (ISSUE 25) names its device work with `jax.named_scope` (five
top-level scopes in the fused step, `SCOPES` below) and its host work with
tracer spans that enter the profiler as `TraceAnnotation`s (one `train` step
annotation per driver-loop iteration, with `data_wait`, `dispatch`, ... inside
it). This module reads both from the run's `.xplane.pb`:

  - device: time by scope. A TPU device event is named by its HLO line without
    metadata and carries no `op_name` among the statistics
    `jax.profiler.ProfileData` shows; the scope is in the statistics of the
    event's METADATA (`tf_op`, the instruction's `op_name`), which that reader
    hides. So the file is opened a second time, here, with a reader of the
    protobuf wire format that decodes only what is needed (names, statistics,
    event times) and skips the rest.
  - host: the main thread's spans inside each `train` step, what of the step is
    under none of them, and each idle gap of the device by the innermost span
    the main thread was in.

One reduction per trace file and process (`_CACHE`): every reader that needs it
shares it. Where the program has no scopes or spans (a parent commit), the
reductions find nothing, say so on standard error, and the readers return
`None`, never 0. Nothing here imports the program.
"""

from __future__ import annotations

import glob
import json
import os
import re
import resource
import struct
import sys
import time

# the program's names, copied (nothing here imports the program):
# `tests/perfbench/test_perfbench_program_spans.py` holds the copy to
# `moco_tpu/telemetry/scopes.py`
SCOPES = ("aug", "k_fwd", "q_fwd_bwd", "loss_queue", "opt_ema")
STEP_EVENT = "train"          # the program's StepTraceAnnotation
# the program's spans on the main thread: a device idle gap is laid to the innermost
# of these (the runtime's own events nest deeper and say how, not where in the loop)
SPANS = (STEP_EVENT, "data_wait", "dispatch", "fence", "sentinel", "loss_readback", "telemetry",
         "checkpoint", "first_batch", "perfbench_dispatch")
# the compiler's own asynchronous copies (the wait for a prefetch into faster memory):
# no instruction of the program's, so no `op_name` and no scope; a bucket of their own
ASYNC_COPY = re.compile(r"^(?:copy|slice)-(?:start|done)\b")
STEP_PROGRAM = "fused_step"   # the step program's module, as `trace_reduce` looks for it
DEVICE_LINES = {"tpu": ("XLA Ops",), "cpu": ("tf_XLAPjRtCpuClient", "tf_XLAEigen")}
DEVICE_PLANES = {"tpu": "/device:TPU:", "cpu": "/host:CPU"}
# host events that are plumbing of the profiler or the thread pool, not work
NOISE = ("ThreadpoolListener::", "PythonRefManager::")


def note(what: str) -> None:
    print(f"perfbench: program_spans: {what}", file=sys.stderr, flush=True)


# -- protobuf wire format, as far as an XSpace needs it -------------------------


def fields(buf):
    """`(field number, wire type, value)` of one message: a varint as int, a
    length-delimited field as a memoryview, a fixed64 as its 8 bytes."""
    i, n = 0, len(buf)
    while i < n:
        key = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, value
        elif wire == 2:
            size = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            yield number, wire, buf[i:i + 8]
            i += 8
        elif wire == 5:
            yield number, wire, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def read_stat(buf, stat_names: dict):
    """An XStat -> `(name, value)`; a `ref_value` is the name of another entry."""
    name, value = "", None
    for number, wire, v in fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number in (3, 4):
            value = v
        elif number in (5, 6):
            value = text(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def read_plane(buf, want_line) -> dict:
    """An XPlane -> its name and, for the lines `want_line(name)` admits, their
    events as `(name, start_ps, duration_ps, stats)` with the statistics of the
    event and of its metadata merged."""
    name, lines, metadata, stat_meta = "", [], [], []
    for number, wire, v in fields(buf):
        if number == 2:
            name = text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            metadata.append(v)
        elif number == 5:
            stat_meta.append(v)
    stat_names = {}
    for entry in stat_meta:                       # map<int64, XStatMetadata>
        for number, wire, v in fields(entry):
            if number == 2:
                sid, sname = 0, ""
                for n2, w2, v2 in fields(v):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2:
                        sname = text(v2)
                stat_names[sid] = sname
    plane = {"name": name, "lines": {}, "line_names": []}
    events_meta: dict | None = None
    for line in lines:
        lname, line_id, t0_ns, raw_events = "", 0, 0, []
        for number, wire, v in fields(line):
            if number == 1:
                line_id = v
            elif number == 2:
                lname = text(v)
            elif number == 3:
                t0_ns = v
            elif number == 4:
                raw_events.append(v)
        if lname in plane["line_names"] or not lname:   # threads share a name: told apart by id
            lname = f"{lname}#{line_id}"
        plane["line_names"].append(lname)
        if not want_line(name, lname):
            continue
        if events_meta is None:                   # map<int64, XEventMetadata>, once, when wanted
            events_meta = {}
            for entry in metadata:
                for number, wire, v in fields(entry):
                    if number == 2:
                        mid, mname, mstats = 0, "", {}
                        for n2, w2, v2 in fields(v):
                            if n2 == 1:
                                mid = v2
                            elif n2 == 2:
                                mname = text(v2)
                            elif n2 == 5:
                                k, val = read_stat(v2, stat_names)
                                mstats[k] = val
                        events_meta[mid] = (mname, mstats)
        out = plane["lines"].setdefault(lname, [])
        for ev in raw_events:
            mid, offset_ps, dur_ps, stats = 0, 0, 0, None
            for number, wire, v in fields(ev):
                if number == 1:
                    mid = v
                elif number == 2:
                    offset_ps = v
                elif number == 3:
                    dur_ps = v
                elif number == 4:
                    k, val = read_stat(v, stat_names)
                    stats = stats or {}
                    stats[k] = val
            mname, mstats = events_meta.get(mid, ("", {}))
            if stats:
                stats = {**mstats, **stats}
            else:
                stats = mstats
            out.append((mname, t0_ns * 1000 + offset_ps, dur_ps, stats))
    return plane


def read_space(path: str, want_line) -> list:
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [read_plane(v, want_line) for number, wire, v in fields(data) if number == 1]


# -- scopes from an instruction's op_name ---------------------------------------

_WRAP = re.compile(r"^(?:transpose|jvp|vmap|pmap|jit|pjit|remat|checkpoint|custom_jvp|custom_vjp"
                   r"|rematted_computation|shard_map|named)\((.*)\)$")


_PLAIN_WRAPS = ("checkpoint", "rematted_computation", "shard_map")   # a rematerialised block's path


def path_of(op_name: str) -> tuple[list, bool]:
    """`jit(f)/q_fwd_bwd/transpose(jvp(ResNet))/layer1_0/mul` -> the path's
    components with the transformations' wrappers taken off, and whether the
    path went through a transpose (a backward operation)."""
    parts, backward = [], False
    for part in op_name.split("/"):
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            backward = backward or part.startswith("transpose(")
            part = m.group(1)
        if part and part not in _PLAIN_WRAPS:
            parts.append(part)
    return parts, backward


def scope_of(op_name: str):
    """`(scope, depth-two label)`: the innermost recognised scope of the path
    (`loss_queue` sits inside `q_fwd_bwd`'s path and wins), and that scope with
    the module block beneath it, forward and backward apart:
    `q_fwd_bwd/layer1 bwd`. `(None, None)` where no scope is in the path."""
    parts, backward = path_of(op_name)
    where = [i for i, p in enumerate(parts) if p in SCOPES]
    if not where:
        return None, None
    i = where[-1]
    rest = [p for p in parts[i + 1:-1]           # the last component is the primitive
            if not p[:1].isupper() and p not in SCOPES]   # `ResNet`, `V3Model`: the model's class
    block = re.sub(r"_\d+$", "", rest[0]) if rest else "-"
    return parts[i], f"{parts[i]}/{block}{' bwd' if backward else ''}"


def short(name: str) -> str:
    return name.split(" = ")[0].lstrip("%")


# -- the reduction --------------------------------------------------------------


def self_times(events: list) -> list:
    """`(name, self_ps, stats)` per event of one line: its duration less what its
    nested events cover (a `while` or a `conditional` spans its body's events)."""
    out, stack = [], []
    for name, start, dur, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(dur, stack[-1][1] - start)
        out.append([name, dur, stats])
        stack.append((len(out) - 1, start + dur))
    return out


def innermost_at(spans: list, t: int):
    """The innermost of `(name, start, end)` that holds `t`."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else None


def wanted(platform: str):
    """The lines a reduction reads: every host thread, the device's operations."""
    dev_prefix, dev_lines = DEVICE_PLANES[platform], DEVICE_LINES[platform]

    def want(plane: str, line: str) -> bool:
        if plane.startswith("/host:"):
            return True
        return plane.startswith(dev_prefix) and line.startswith(dev_lines)
    return want


def reduce_file(path: str, platform: str) -> dict:
    t_start = time.perf_counter()
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    red = reduce_planes(read_space(path, wanted(platform)), platform)
    red.update(path=path, seconds=time.perf_counter() - t_start,
               rss_added_gib=(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss - rss0) / 2 ** 20)
    return red


def reduce_planes(planes: list, platform: str) -> dict:
    dev_prefix, dev_lines = DEVICE_PLANES[platform], DEVICE_LINES[platform]
    # -- device: self time by scope, inside the step program -------------------
    by_scope, by_block, unscoped_ops = {}, {}, {}
    total_ps = async_copy_ps = scoped_events = 0
    busy: list = []
    n_device_planes = 0
    for p in planes:
        if not p["name"].startswith(dev_prefix):
            continue
        seen = False
        for lname, events in p["lines"].items():
            if not lname.startswith(dev_lines):
                continue
            device_events = [e for e in events if e[2] > 0 and not e[0].startswith(NOISE)
                             and (platform != "cpu" or "hlo_op" in e[3])]
            if platform == "cpu":                  # only the step program's thunks
                device_events = [e for e in device_events
                                 if STEP_PROGRAM in str(e[3].get("hlo_module", ""))]
            seen = seen or bool(device_events)
            busy += [(e[1], e[1] + e[2]) for e in device_events]
            for name, self_ps, stats in self_times(device_events):
                # the scope is in the instruction's `op_name`: the `tf_op` statistic of a
                # TPU event's metadata (its name is the HLO line without metadata)
                op_name = str(stats.get("tf_op") or "")
                scope, block = scope_of(op_name)
                total_ps += self_ps
                if scope is not None:
                    scoped_events += 1
                    by_scope[scope] = by_scope.get(scope, 0) + self_ps
                    by_block[block] = by_block.get(block, 0) + self_ps
                elif not op_name and ASYNC_COPY.match(short(name)):
                    async_copy_ps += self_ps
                else:
                    unscoped_ops[short(name)] = unscoped_ops.get(short(name), 0) + self_ps
        n_device_planes += seen
    # -- host: the main thread's steps and their spans -------------------------
    steps, threads = [], {}
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for lname, events in p["lines"].items():
            spans = [(n.split("#")[0], s, s + d) for n, s, d, st in events
                     if d > 0 and not n.startswith(NOISE)]
            threads[lname] = spans
            for n, s, d, st in events:
                if n.split("#")[0] == STEP_EVENT and "step_num" in st and d > 0:
                    steps.append({"thread": lname, "step": int(st["step_num"]),
                                  "start": s, "end": s + d})
    steps.sort(key=lambda r: r["start"])
    loop = None
    if steps:
        main = steps[0]["thread"]
        spans = threads[main]
        children, unspanned = {}, []
        for st in steps:
            inside = [(n, s, e) for n, s, e in spans
                      if s >= st["start"] and e <= st["end"] and n != STEP_EVENT]
            # direct children: the program's spans that lie in no other of them (the
            # runtime's own events say how a span's time went, and cover nothing)
            own = [c for c in inside if c[0] in SPANS]
            direct = [c for c in own
                      if not any(o is not c and o[1] <= c[1] and c[2] <= o[2]
                                 and (o[2] - o[1]) > (c[2] - c[1]) for o in own)]
            covered = 0
            for n, s, e in direct:
                children.setdefault(n, []).append((e - s) * 1e-12)
                covered += e - s
            unspanned.append(max(st["end"] - st["start"] - covered, 0) * 1e-12)
            st["spans"] = inside
        loop = {"thread": main, "steps": len(steps), "children": children,
                "unspanned_s": unspanned,
                "step_s": [(st["end"] - st["start"]) * 1e-12 for st in steps]}
    # -- idle gaps of the device by what the main thread was in ----------------
    gaps: dict = {}
    if busy and steps:
        busy.sort()
        merged = [list(busy[0])]
        for s, e in busy[1:]:
            if s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        main_spans = [sp for sp in threads[steps[0]["thread"]] if sp[0] in SPANS]
        for (_, e), (s, _) in zip(merged, merged[1:]):
            if s - e < 1_000_000:                   # under a microsecond: between two operations
                continue
            where = innermost_at(main_spans, (e + s) // 2) or "(outside every span)"
            g = gaps.setdefault(where, [0.0, 0, 0.0])
            g[0] += (s - e) * 1e-12
            g[1] += 1
            g[2] = max(g[2], (s - e) * 1e-12)
    return {
        "scope_ps": by_scope, "block_ps": by_block, "unscoped_ops_ps": unscoped_ops,
        "async_copy_ps": async_copy_ps, "total_ps": total_ps, "scoped_events": scoped_events,
        "device_planes": n_device_planes, "loop": loop, "steps": steps, "threads": threads,
        "gaps": gaps,
    }


_CACHE: dict = {}


def trace_file(run) -> str | None:
    if "manifest" not in run or "cell" not in run:
        return None
    trace_dir = run["manifest"].work_dir("run-" + run["cell"]["name"], "trace")
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduction(run) -> dict | None:
    """The run's one reduction, made at the first reader's call; its two tables
    go to standard error then."""
    path = trace_file(run)
    if path is None:
        note("no .xplane.pb under the run's trace directory")
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        platform = "tpu" if "tpu" in run["device_kind"].lower() else "cpu"
        try:
            _CACHE[key] = red = reduce_file(path, platform)
        except Exception:   # a trace this reader cannot follow costs its metrics, not the run
            import traceback

            note("the trace could not be reduced:\n" + traceback.format_exc())
            _CACHE[key] = None
            return None
        note(f"second read of the trace: {red['seconds']:.2f} s, host peak +{red['rss_added_gib']:.2f} GiB")
        for line in tables(red, run.get("traced_steps") or 1):
            note(line)
    return _CACHE[key]


def per_step_ms(red: dict, ps: float, traced_steps: int) -> float:
    return ps * 1e-9 / max(traced_steps, 1) / max(red["device_planes"], 1)


def scope_ms(run, scope: str):
    """Device milliseconds a traced step spends under `scope`; `None` where no
    device event carries a scope (a program without them, or a step program
    loaded from a compile cache that was filled before the scopes existed)."""
    red = reduction(run)
    if red is None or not red["scoped_events"]:
        if red is not None:
            note(f"no device event carries a scope: {scope}_device_ms not reported")
        return None
    return per_step_ms(red, red["scope_ps"].get(scope, 0), run["traced_steps"])


def unscoped_ps(red: dict) -> int:
    """The step program's device time under no scope, the compiler's asynchronous
    copies (a bucket of their own) left out."""
    return red["total_ps"] - sum(red["scope_ps"].values()) - red["async_copy_ps"]


def tables(red: dict, traced_steps: int) -> list:
    """The two tables PERF.md's section 5 holds: device ms a step by scope to
    depth two, and the device's idle gaps by the main thread's innermost span."""
    out = []
    total = per_step_ms(red, red["total_ps"], traced_steps)
    out.append(f"device ms a traced step by scope (step program's operations {total:.3f} ms):")
    for scope in SCOPES + ("(async copies)", "(unscoped)"):
        ps = {"(async copies)": red["async_copy_ps"],
              "(unscoped)": unscoped_ps(red)}.get(scope, red["scope_ps"].get(scope, 0))
        out.append(f"  {scope:<14} {per_step_ms(red, ps, traced_steps):9.3f}")
        blocks = sorted(((k, v) for k, v in red["block_ps"].items() if k.split("/")[0] == scope),
                        key=lambda kv: -kv[1])
        for k, v in blocks[:14]:
            out.append(f"    {k:<34} {per_step_ms(red, v, traced_steps):9.3f}")
    worst = sorted(red["unscoped_ops_ps"].items(), key=lambda kv: -kv[1])[:8]
    for k, v in worst:
        if per_step_ms(red, v, traced_steps) >= 0.0005:      # a row that would print as 0.000 says nothing
            out.append(f"    unscoped {k[:60]:<60} {per_step_ms(red, v, traced_steps):9.3f}")
    loop = red["loop"]
    if loop:
        n = max(loop["steps"], 1)
        out.append(f"main thread ({loop['thread']}) ms a traced step, {loop['steps']} steps of "
                   f"{1e3 * sum(loop['step_s']) / n:.3f} ms:")
        for name, ds in sorted(loop["children"].items(), key=lambda kv: -sum(kv[1])):
            out.append(f"  {name:<20} {1e3 * sum(ds) / n:9.3f}")
        out.append(f"  {'(under no span)':<20} {1e3 * sum(loop['unspanned_s']) / n:9.3f}")
        inner = {}
        for st in red["steps"]:
            for name, s, e in st.get("spans", ()):
                inner.setdefault(name, []).append((e - s) * 1e-12)
        nested = {k: v for k, v in inner.items() if k not in loop["children"]}
        for name, ds in sorted(nested.items(), key=lambda kv: -sum(kv[1]))[:12]:
            out.append(f"    nested {name[:40]:<40} {1e3 * sum(ds) / n:9.3f} ({len(ds)} events)")
    out.append("device idle gaps by the main thread's innermost span (s in all; gaps; longest s):")
    for where, (secs, count, longest) in sorted(red["gaps"].items(), key=lambda kv: -kv[1][0]):
        out.append(f"  {where:<24} {secs:.6f} {count:6d} {longest:.6f}")
    if not red["gaps"]:
        out.append("  (none over a microsecond, or no step annotation to place them by)")
    return out


def events_of(run, kind: str) -> list:
    """The run's `events.jsonl` records that are events of `kind`."""
    if "manifest" not in run or "cell" not in run:
        return []
    path = os.path.join(run["manifest"].work_dir("run-" + run["cell"]["name"], "telemetry"),
                        "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r for r in records if r.get("kind") == "event" and r.get("event") == kind]
