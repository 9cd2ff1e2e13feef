"""Median latency of one staged batch (read or decode -> device queue), from the
last `InputPipelineStats` snapshot up to the window's close. The program's reservoir spans
the whole run, warm-up batches included."""


def read(run):
    last = run["window_records"][-1]["step"] if run["window_records"] else 0
    snaps = [r["input"] for r in run["records"] if "input" in r and r["step"] <= last]
    return 1e3 * snaps[-1]["staged_batch_s_p50"] if snaps else None
