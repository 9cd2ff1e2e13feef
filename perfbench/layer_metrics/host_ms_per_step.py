"""Mean host milliseconds of the driver loop per step in the window: the step
call's dispatch (`host_s`) and the telemetry's own time."""


def read(run):
    recs = run["window_records"]
    if not recs:
        return None
    return 1e3 * sum(r["host_s"] + r.get("telemetry_s", 0.0) for r in recs) / len(recs)
