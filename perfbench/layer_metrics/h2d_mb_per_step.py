"""Megabytes (10^6 bytes) the input threads put on the device per step in the
window: the delta of the program's exact `staged_bytes` counter between the
first and the last `InputPipelineStats` snapshot inside the window, over the
steps between them."""


def read(run):
    snaps = [(r["step"], r["input"]["staged_bytes"]) for r in run["window_records"]
             if "staged_bytes" in r.get("input", {})]
    if len(snaps) < 2 or snaps[-1][0] == snaps[0][0]:
        return None
    return (snaps[-1][1] - snaps[0][1]) / (snaps[-1][0] - snaps[0][0]) / 1e6
