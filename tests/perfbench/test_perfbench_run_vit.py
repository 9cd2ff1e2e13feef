"""The command end to end at a tiny size on one CPU device (the 2-block ViT,
32x32, images in memory), untraced; and the command itself where it must refuse:
without a chip, and alone in a directory that holds only the benchmark."""

import os
import subprocess
import sys

from pb_helpers import CONTRACT_KEYS, ROOT, copy_benchmark, run_cell

ARGS = ["--workload", "r50-v2-f32.synthetic", "--seed", "1", "--seconds", "1", "--trace", "0"]


def test_tiny_vit_cell_reports_the_end_to_end_metrics():
    rc, result, lines = run_cell("vit-tiny.tiny_mem", seed=2 ** 31 + 17)
    assert rc == 0
    assert list(result)[:5] == CONTRACT_KEYS and list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"train_imgs_per_s_per_chip", "setup_s"}
    assert result["run"]["longest_step"]["ms"] >= result["run"]["longest_step"]["median_ms"] > 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert result["correct"] is True and result["attempted"] >= 2
    assert set(result["compared"]) == {"loss1", "loss2", "loss3", "grad1", "dq3", "dk3"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "breakdown" not in result
    # one epoch of the traffic outlasts the run
    assert result["run"]["steps_per_epoch"] > result["attempted"] + 8


def test_without_a_chip_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py", *ARGS], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 x tpu" in p.stderr


def test_alone_with_the_benchmarks_files_the_command_exits_nonzero(tmp_path):
    root = copy_benchmark(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py", *ARGS], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
