"""Device time by the two scopes that a token encoder with latent attention and
shared experts adds to a routed encoder's five
(`moco_tpu/telemetry/scopes.py::LATENT_SCOPES`, copied here: nothing of the
benchmark imports the program; `tests/perfbench/test_perfbench_latent.py` holds
the copy to the original), and by its attention kernels. An event belongs to
the INNERMOST of `LATENT` in its instruction's `op_name`, key and query encoder
alike, the rematerialised forward too: `mla_latent` lies inside `attn`, whose
reader over `nested_spans.py` still counts it there; `moe_shared` lies inside
none of the five. A kernel's events are those whose path holds the kernel's
name as a component (`pallas_call(name=...)`). The trace is read with
`program_spans`' reader of the wire format; one reduction a trace file and
process. Where no event carries one of the names (a program without them, as
the parent of the PR that added them), the readers return `None`.
"""

from __future__ import annotations

import os

from perfbench import program_spans
from perfbench.program_spans import path_of, read_space

LATENT = ("mla_latent", "moe_shared")
KERNELS = ("mla_attention_fwd", "mla_attention_bwd")
_CACHE: dict = {}


def reduce_planes(planes: list, platform: str) -> dict:
    prefix, lines = program_spans.DEVICE_PLANES[platform], program_spans.DEVICE_LINES[platform]
    by_scope: dict = {}
    by_kernel: dict = {}
    n_planes = 0
    for p in planes:
        if not p["name"].startswith(prefix):
            continue
        seen = False
        for lname, events in p["lines"].items():
            if not lname.startswith(lines):
                continue
            events = [e for e in events if e[2] > 0 and not e[0].startswith(program_spans.NOISE)
                      and (platform != "cpu" or "hlo_op" in e[3])]
            if platform == "cpu":
                events = [e for e in events
                          if program_spans.STEP_PROGRAM in str(e[3].get("hlo_module", ""))]
            seen = seen or bool(events)
            for _, self_ps, stats in program_spans.self_times(events):
                parts, _ = path_of(str(stats.get("tf_op") or ""))
                inner = [part for part in parts if part in LATENT]
                if inner:
                    by_scope[inner[-1]] = by_scope.get(inner[-1], 0) + self_ps
                for part in parts:
                    if part in KERNELS:
                        by_kernel[part] = by_kernel.get(part, 0) + self_ps
                        break
        n_planes += seen
    return {"scope_ps": by_scope, "kernel_ps": by_kernel, "device_planes": n_planes}


def reduction(run) -> dict | None:
    path = program_spans.trace_file(run)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        platform = "tpu" if "tpu" in run["device_kind"].lower() else "cpu"
        try:
            _CACHE[key] = reduce_planes(read_space(path, program_spans.wanted(platform)), platform)
        except Exception:   # a trace this reader cannot follow costs its metrics, not the run
            import traceback

            program_spans.note("latent scopes: the trace could not be reduced:\n"
                               + traceback.format_exc())
            _CACHE[key] = None
        else:
            red = _CACHE[key]
            for table, names in (("scope_ps", LATENT), ("kernel_ps", KERNELS)):
                for name in names:
                    ms = program_spans.per_step_ms(red, red[table].get(name, 0), run["traced_steps"])
                    program_spans.note(f"latent {table[:-3]:<6} {name:<19} {ms:9.3f} ms a traced step")
    return _CACHE[key]


def scope_ms(run, scope: str):
    """Device milliseconds a traced step spends under `scope`, or `None` where
    the trace holds neither name."""
    red = reduction(run)
    if red is None or not red["scope_ps"]:
        return None
    return program_spans.per_step_ms(red, red["scope_ps"].get(scope, 0), run["traced_steps"])


def roofline_pct(run, names, work: dict):
    """The share of their roofline that the kernels `names` reach: the least
    time the chip could take for a step's `work` (`flops` over the bf16 peak,
    `bytes` over the memory's, whichever is larger) over their device time."""
    from perfbench import peaks

    red = reduction(run)
    ps = sum(red["kernel_ps"].get(name, 0) for name in names) if red else 0
    if not ps:
        return None
    ms = program_spans.per_step_ms(red, ps, run["traced_steps"])
    peak = peaks.for_kind(run["device_kind"])
    least = max(work["flops"] / peak["flops_bf16"], work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
