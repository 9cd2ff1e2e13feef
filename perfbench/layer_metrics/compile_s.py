"""Seconds of set-up spent tracing, lowering and compiling (or reading from the
compile cache) the fused step program, every load of it: `fused_step_s` of the
program's cumulative compile counters on the last step record before the
window. The eager initialisation's small programs are not in it: they run
inside the `model_init` span and are `model_init_s`."""


def read(run):
    first = run["window_records"][0]["step"] if run["window_records"] else None
    before = [r["compile"] for r in run["records"]
              if "fused_step_s" in r.get("compile", {}) and (first is None or r["step"] < first)]
    return before[-1]["fused_step_s"] if before else None
