"""Seconds of steps 1 and 2: each compiles, or loads from the compile cache, one
`jit_fused_step` program."""


def read(run):
    firsts = [r["step_s"] for r in run["records"] if r["step"] in (1, 2)]
    return sum(firsts) if len(firsts) == 2 else None
