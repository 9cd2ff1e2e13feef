"""chip_smoke.py: the body runs tiny on fake CPU devices (so the script the
driver sends to the chip is exercised in tier-1), and the script itself has
no CPU mode."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_smoke_body_tiny_on_two_cpu_devices(tmp_path):
    """Same phases as on the chip — JPEG tree, train.main through the real
    feed, checkpoint restore, staged batch vs host source — at resnet_tiny /
    32² / K=256 on two devices. The blur is the portable one here:
    interpret-mode Pallas cannot run inside shard_map on this jax, so the
    kernel itself is covered by tests/test_pallas_blur.py and the
    TPU-lowering test in tests/test_fused_conv.py."""
    import chip_smoke

    record = chip_smoke.run_smoke(
        str(tmp_path), platform="cpu", per_device_batch=8,
        min_mosaic_calls=0, num_devices=2, jpeg_size=(80, 60),
        size_overrides=dict(arch="resnet_tiny", cifar_stem=True, image_size=32,
                            num_negatives=256, stage_size=64),
    )
    assert record["ok"], record["checks"]
    assert record["device"] == {"platform": "cpu", "kind": "cpu", "count": 2}
    # the last stdout line a driver reads: exactly these keys, nothing else
    assert json.loads(json.dumps(chip_smoke.verdict_line(record))) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    assert record["steps"] == 8 and len(record["losses"]) == 8
    assert record["queue_ptr"] == (8 * 16) % 256
    assert record["per_device_batch_rows"] == {0: 8, 1: 8}
    assert record["checks"]["staged_batch_equals_host_source"]
    assert record["checks"]["staging_backend_native"]


def test_script_exits_nonzero_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script fails before compiling anything
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no CPU mode" in proc.stderr
