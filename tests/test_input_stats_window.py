"""ISSUE 25 (e): `InputPipelineStats.snapshot()` carries the exact cumulative
counters beside the rounded fields, so a reader takes a window's delta."""

import pytest

from moco_tpu.data.stats import InputPipelineStats


def test_snapshot_keeps_the_rounded_fields_and_adds_the_exact_ones():
    s = InputPipelineStats()
    s.note_workers(4)
    s.note_staged(0.125, 2, 19_267_584 + 1536, images=128)
    s.note_worker_busy(0.031)
    snap = s.snapshot()
    for key in ("staged_batches", "staged_mb", "staged_batch_s_p50", "staged_batch_s_p95",
                "queue_depth", "queue_depth_mean", "workers", "worker_busy_frac",
                "credit_stall_s", "wall_s"):
        assert key in snap
    assert snap["staged_bytes"] == 19_269_120 and isinstance(snap["staged_bytes"], int)
    assert snap["staged_images"] == 128
    assert snap["worker_busy_s"] == pytest.approx(0.031)
    assert snap["staged_mb"] == 18.4           # rounded MiB: no window delta could be taken from it


def test_a_window_delta_between_two_snapshots():
    s = InputPipelineStats()
    per_batch = 128 * 224 * 224 * 3 + 128 * 3 * 4
    for _ in range(8):                          # warm-up
        s.note_staged(0.4, 1, per_batch, images=128)
        s.note_worker_busy(0.2)
    first = s.snapshot()
    for _ in range(16):                         # the window
        s.note_staged(0.13, 2, per_batch, images=128)
        s.note_worker_busy(0.05)
    last = s.snapshot()
    steps = last["staged_batches"] - first["staged_batches"]
    assert steps == 16
    assert (last["staged_bytes"] - first["staged_bytes"]) / steps / 1e6 == pytest.approx(19.269120)
    assert last["staged_images"] - first["staged_images"] == 16 * 128
    assert last["worker_busy_s"] - first["worker_busy_s"] == pytest.approx(0.8)


def test_images_default_keeps_old_callers_working():
    s = InputPipelineStats()
    s.note_staged(0.1, 0, 1000)
    assert s.snapshot()["staged_images"] == 0 and s.snapshot()["staged_bytes"] == 1000


def report_records():
    """Step records as a run writes them: a compile stall before the first
    snapshot, then a steady feed of 128 images every 0.125 s by four workers
    busy a quarter of the time."""
    def snap(batches, wall_s, busy_s):
        return {"staged_batches": batches, "staged_mb": 18.4 * batches,
                "staged_bytes": 19_269_120 * batches, "staged_images": 128 * batches,
                "worker_busy_s": busy_s, "workers": 4, "wall_s": wall_s,
                "worker_busy_frac": round(busy_s / (4 * wall_s), 4)}

    compile_block = {"n": 231, "backend_s": 60.25, "trace_lower_s": 8.5, "cache_hits": 229,
                     "cache_misses": 2, "fused_step_n": 2, "fused_step_s": 34.5}
    return [
        {"kind": "event", "event": "setup", "spans": {"model_init": 35.9, "opt_init": 1.3}},
        {"kind": "step", "step": 16, "step_s": 0.125, "compile": compile_block,
         "input": snap(18, 100.0, 3.0)},
        {"kind": "step", "step": 32, "step_s": 0.125, "compile": compile_block,
         "input": snap(34, 102.0, 5.0)},
    ]


@pytest.mark.parametrize("line, holds", [
    ("steady state", ("2 s between", "1024 imgs/s staged", "workers busy 25.0%")),
    ("compile:", ("231 programs", "backend 60.2 s", "229 hit / 2 miss", "step program 2× 34.5 s")),
    ("set-up:", ("model_init 35.90 s", "opt_init 1.30 s")),
])
def test_the_report_reads_the_exact_counters_the_compile_block_and_the_setup_event(line, holds):
    """The operator's reader of what no benchmark metric reads yet: the feed in
    steady state from the exact counters' delta (the whole-run share, 1.2 % here,
    is mostly the compile stall), the compile counters, the set-up spans."""
    from tools.telemetry_report import render, summarize

    summary = summarize(report_records())
    assert "setup" not in summary["incidents"]
    found = [text for text in render(summary).splitlines() if line in text]
    assert len(found) == 1
    for part in holds:
        assert part in found[0]


def test_the_report_leaves_the_lines_out_for_a_run_without_the_counters():
    from tools.telemetry_report import render, summarize

    old = [{"kind": "step", "step": 16, "step_s": 0.125,
            "input": {"staged_batches": 18, "staged_mb": 331.2, "wall_s": 100.0}}]
    text = render(summarize(old))
    assert "input:" in text
    assert "steady state" not in text and "compile:" not in text and "set-up:" not in text
