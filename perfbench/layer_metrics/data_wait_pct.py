"""Share of the window's step time that the loop spent waiting for a batch."""


def read(run):
    recs = run["window_records"]
    total = sum(r["step_s"] for r in recs)
    return 100.0 * sum(r["data_s"] for r in recs) / total if total > 0 else None
