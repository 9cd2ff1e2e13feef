"""The command end to end at a tiny size on one CPU device (ResNet-18, 32x32,
JPEGs through the program's own ImageFolder), in a copy of the benchmark to
which a cell, a traffic mix and a per-layer metric were added as new files and
manifest entries only. Then the same run with the timed path broken
underneath: `correct` has to come out false."""

import json
import os

import pytest

import pb_helpers
from pb_helpers import CONTRACT_KEYS, copy_benchmark, run_cell


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A later PR's tree: new files and entries, no file of the benchmark edited."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("later_pr")))
    extra = os.path.join(root, "tests", "perfbench", "extra")
    with open(os.path.join(extra, "traffic", "small_jpegs.json"), "w") as f:
        json.dump({"generator": "jpeg_tree", "distinct_files": 24, "entries": 2048, "classes": 2,
                   "width": 64, "height": 48, "quality": 80, "data_seed": 9}, f)
    with open(os.path.join(extra, "layer_metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run['window_records']) or None\n")
    with open(os.path.join(extra, "limits", "r18-tiny.small_jpegs.json"), "w") as f:
        json.dump({"limits": {"loss1": 0.02, "loss2": 0.02, "loss3": 0.02, "grad1": 0.5,
                              "dq3": 0.5, "dk3": 0.5, "canvas_max": 0}}, f)
    path = os.path.join(extra, "tiny_manifest.json")
    with open(path) as f:
        m = json.load(f)
    m["workloads"].append({"name": "r18-tiny.small_jpegs", "config": "r18-tiny",
                           "traffic": "small_jpegs", "chips": 1, "why": "added by the test"})
    m["per_layer"].append({"name": "steps_in_window", "unit": "steps", "better": "higher",
                           "source": "program_counter", "layer": "driver loop",
                           "moves": "train_imgs_per_s_per_chip",
                           "workloads": ["r18-tiny.small_jpegs"]})
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def test_added_cell_runs_traced_and_reports_the_added_metric(extended):
    rc, result, lines = run_cell("r18-tiny.small_jpegs", trace=1, root=extended, seconds=5)
    assert rc == 0
    assert list(result)[:5] == CONTRACT_KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    per_layer = {"pre_step_s", "first_two_steps_s", "host_ms_per_step", "step_ms_p90",
                 "data_wait_pct", "device_idle_pct", "steps_in_window"}
    if result["attempted"] + 8 >= 16:      # the program snapshots its input counters every 16th step
        per_layer.add("staged_batch_ms")
    assert per_layer <= set(result["metrics"])
    # nothing to read on the CPU: no program line, no Mosaic kernel, no memory statistics
    assert not {"fused_step_device_ms", "step_mfu_pct", "blur_roofline",
                "hbm_peak_pct", "setup_s"} & set(result["metrics"])
    # the traced steps follow the window and are none of its records
    assert result["metrics"]["steps_in_window"]["value"] == result["attempted"]
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    for c in result["compared"].values():
        assert c["value"] <= c["limit"]
    # the staged canvases equal PIL's decode of the tree's files, level for level
    assert result["compared"]["canvas_max"] == {"value": 0.0, "limit": 0,
                                                "leaf": result["compared"]["canvas_max"]["leaf"]}
    assert os.path.isdir(os.path.join(extended, "perfbench", "_work", "data"))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(extended, fault):
    rc, result, _ = run_cell("r18-tiny.small_jpegs", root=extended, seed=12,
                             wrap_step=getattr(pb_helpers, fault))
    assert rc == 0 and result["correct"] is False
    over = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    assert over & ({"dq3", "dk3"} if fault == "state_unchanged" else {"loss2", "loss3", "grad1"})
