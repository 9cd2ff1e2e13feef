"""Device time by the scopes a routed token encoder nests under the step's
five (`moco_tpu/telemetry/scopes.py::ENCODER_SCOPES`, copied here: nothing of
the benchmark imports the program; `tests/perfbench/test_perfbench_seq.py`
holds the copy to the original). An event belongs to the INNERMOST of these
names in its instruction's `op_name`, forward and transpose alike, key and
query encoder alike. The trace is read with `program_spans`' reader of the
wire format; one reduction a trace file and process. Where no event carries
one of the names (a program without them), the readers return `None`.
"""

from __future__ import annotations

import os

from perfbench import program_spans
from perfbench.program_spans import path_of, read_space

NESTED = ("attn", "moe_router", "moe_dispatch", "moe_experts", "embed_pool")
# `lax.ragged_dot` reaches the TPU as custom calls named `ragged-dot-...` whose
# `op_name` is that name again and no path of the program's (my chip run, PR 27:
# 39.6 ms a step under no scope): the grouped product is known by that name, as
# the blur kernel is by its shapes
GROUPED_PRODUCT, GROUPED_SCOPE = "ragged-dot", "moe_experts"
_CACHE: dict = {}


def reduce_planes(planes: list, platform: str) -> dict:
    prefix, lines = program_spans.DEVICE_PLANES[platform], program_spans.DEVICE_LINES[platform]
    by_scope: dict = {}
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
            for name, self_ps, stats in program_spans.self_times(events):
                parts, _ = path_of(str(stats.get("tf_op") or ""))
                inner = [part for part in parts if part in NESTED]
                if not inner and program_spans.short(name).startswith(GROUPED_PRODUCT):
                    inner = [GROUPED_SCOPE]
                if inner:
                    by_scope[inner[-1]] = by_scope.get(inner[-1], 0) + self_ps
        n_planes += seen
    return {"scope_ps": by_scope, "device_planes": n_planes}


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

            program_spans.note("nested scopes: the trace could not be reduced:\n"
                               + traceback.format_exc())
            _CACHE[key] = None
        else:
            red = _CACHE[key]
            for name in NESTED:
                program_spans.note(f"nested scope {name:<13} {scope_ms_of(red, name, run):9.3f} ms a traced step")
    return _CACHE[key]


def scope_ms_of(red: dict, scope: str, run) -> float:
    return program_spans.per_step_ms(red, red["scope_ps"].get(scope, 0), run["traced_steps"])


def scope_ms(run, scope: str):
    """Device milliseconds a traced step spends under `scope`, or `None`."""
    red = reduction(run)
    if red is None or not red["scope_ps"]:
        return None
    return scope_ms_of(red, scope, run)


def counter(run, key: str):
    """Mean of a stride-gated counter of the program's step records' `health`
    block over the window (the whole run where the window holds no sample)."""
    for records in (run["window_records"], run["records"]):
        values = [r["health"][key] for r in records if key in (r.get("health") or {})]
        if values:
            return sum(values) / len(values)
    return None
