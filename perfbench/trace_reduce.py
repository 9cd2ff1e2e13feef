"""From a profiler trace (`.xplane.pb`, read with `jax.profiler.ProfileData`) to
the few numbers the metrics need: device busy time and the traced window, time
by operation, time by program, the longest idle gaps by what the host was
doing. The only names it looks for are the ones XLA, Mosaic and the harness's
own `perfbench_dispatch` annotation give.

Where events sit differs by platform: on a TPU each chip is a plane
`/device:TPU:<n>` with the lines `XLA Ops` and `XLA Modules`; the CPU backend
(the tests' small recorded trace) runs its operations on threads of the host
plane and has no line of programs.
"""

from __future__ import annotations

import glob
import os
import statistics

LAYOUT = {
    "tpu": {"plane": "/device:TPU:", "ops": ("XLA Ops",), "modules": ("XLA Modules",)},
    "cpu": {"plane": "/host:CPU", "ops": ("tf_XLAPjRtCpuClient", "tf_XLAEigen"), "modules": ()},
}
DISPATCH = "perfbench_dispatch"


def merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(plane, prefixes):
    for line in plane.lines:
        if line.name.startswith(tuple(prefixes)):
            for ev in line.events:
                if ev.duration_ns > 0:
                    yield ev


def reduce_file(path: str, platform: str) -> dict:
    import jax

    layout = LAYOUT[platform]
    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    summary = {p.name: [ln.name for ln in p.lines] for p in planes}
    dispatch = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                dispatch += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name == DISPATCH]
    dispatch = merge(dispatch)
    busy, windows, ops, programs, gaps = [], [], {}, {}, {}
    for p in planes:
        if not p.name.startswith(layout["plane"]) or not layout["ops"]:
            continue
        spans = []
        for ev in _events(p, layout["ops"]):
            spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            ops.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
        for ev in _events(p, layout["modules"]) if layout["modules"] else ():
            programs.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
        if not spans:
            continue
        merged = merge(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        windows.append((merged[-1][1] - merged[0][0]) * 1e-9)
        for (_, e), (s, _) in zip(merged, merged[1:]):
            mid = (e + s) / 2
            inside = any(a <= mid <= b for a, b in dispatch)
            what = ("host inside the step call (dispatch)" if inside else
                    "host between step calls (data wait, loss read-back, meters)")
            gaps.setdefault(what, []).append((s - e) * 1e-9)
    if not busy:
        raise ValueError(f"no device operation in the trace {path}: planes {summary}")
    return {
        "planes": summary, "file_bytes": os.path.getsize(path),
        "busy_s": statistics.fmean(busy), "window_s": max(windows),
        "ops": ops, "programs": programs,
        "device_ops": sorted(([k, v / len(busy)] for k, v in by_short(ops).items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([f"{k}; longest {max(v):.6f} s, {len(v)} gaps", sum(v) / len(busy)]
                             for k, v in gaps.items()), key=lambda kv: -kv[1]),
    }


def short(name: str) -> str:
    """An XLA event is named by its whole HLO line; the instruction's name
    before ` = ` says which one it is."""
    return name.split(" = ")[0].lstrip("%")[:80]


def by_short(ops: dict) -> dict:
    out: dict = {}
    for name, ds in ops.items():
        out[short(name)] = out.get(short(name), 0.0) + sum(ds)
    return out


def reduce_dir(trace_dir: str, platform: str) -> dict:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(found[-1], platform)


def durations(table: dict, needle: str) -> list:
    """All durations of the entries whose name contains `needle`."""
    return [d for name, ds in table.items() if needle in name for d in ds]


def custom_calls(trace: dict) -> list:
    """Every device event that is a custom call, `[name, events, seconds]`: a
    Mosaic kernel's event carries the HLO line and not the kernel's own name, so
    the writer of a new kernel's reader looks its shapes up here."""
    out = [[name[:400], len(ds), sum(ds)] for name, ds in trace["ops"].items()
           if "custom-call(" in name]
    return sorted(out, key=lambda r: -r[2])
