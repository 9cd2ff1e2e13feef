"""Shared by the benchmark's tests: run one tiny cell in this process on the CPU
(the look for a chip switched to `cpu` here and nowhere else) and parse the
result line."""

import io
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = "tests/perfbench/extra/tiny_manifest.json"
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def state_unchanged(real):
    """The step program broken underneath: it returns its state as it got it."""
    import jax

    def step(state, imgs, extents, n):
        _, metrics = real(jax.tree.map(lambda x: x.copy(), state), imgs, extents, n)
        return state, metrics
    return step


def half_batch(real):
    """Half of the batch left out, the mean taken over the rest."""
    def step(state, imgs, extents, n):
        half = imgs.shape[0] // 2
        return real(state, imgs[:half], extents[:half], n)
    return step


def run_cell(workload, *, seed=11, seconds=1.5, trace=0, root=ROOT, manifest=TINY, wrap_step=None):
    from perfbench import run

    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace), "--root", root, "--manifest", manifest],
                  platform="cpu", wrap_step=wrap_step, out=out)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), lines


def copy_benchmark(dst):
    """The benchmark's files alone, as a later PR's checkout would hold them."""
    ignore = shutil.ignore_patterns("_work", "__pycache__")
    shutil.copytree(os.path.join(ROOT, "perfbench"), os.path.join(dst, "perfbench"), ignore=ignore)
    shutil.copytree(os.path.join(ROOT, "tests", "perfbench", "extra"),
                    os.path.join(dst, "tests", "perfbench", "extra"), ignore=ignore)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst
