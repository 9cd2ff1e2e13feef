"""Device time by the scopes a looped dense token encoder nests under the
step's five (`moco_tpu/telemetry/scopes.py::LOOPED_SCOPES`, copied here: nothing
of the benchmark imports the program; `tests/perfbench/test_perfbench_looped.py`
holds the copy to the original). As `nested_spans.py`, which knows the routed
encoder's five names: an event belongs to the INNERMOST of these names in its
instruction's `op_name`, forward and transpose alike, key and query encoder
alike, every pass of the loop alike. `attn` and `embed_pool` keep their readers
over `nested_spans.py` (which reads the same events by the same rule, since
neither new name lies inside either); this file reads `mlp` and `norm`. The
trace is read with `program_spans`' reader of the wire format; one reduction a
trace file and process. Where no event carries one of the names (a program
without them), the readers return `None`.
"""

from __future__ import annotations

import os

from perfbench import program_spans
from perfbench.program_spans import path_of, read_space

LOOPED = ("attn", "mlp", "norm", "embed_pool")
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
            for _, self_ps, stats in program_spans.self_times(events):
                parts, _ = path_of(str(stats.get("tf_op") or ""))
                inner = [part for part in parts if part in LOOPED]
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

            program_spans.note("looped scopes: the trace could not be reduced:\n"
                               + traceback.format_exc())
            _CACHE[key] = None
        else:
            red = _CACHE[key]
            for name in LOOPED:
                ms = program_spans.per_step_ms(red, red["scope_ps"].get(name, 0), run["traced_steps"])
                program_spans.note(f"looped scope {name:<11} {ms:9.3f} ms a traced step")
    return _CACHE[key]


def scope_ms(run, scope: str):
    """Device milliseconds a traced step spends under `scope`, or `None` where
    the trace holds none of the new names (a program without them)."""
    red = reduction(run)
    if red is None or not {"mlp", "norm"} & set(red["scope_ps"]):
        return None
    return program_spans.per_step_ms(red, red["scope_ps"].get(scope, 0), run["traced_steps"])
