"""moco_tpu.utils.cache: one compile cache, placed from outside by
JAX_COMPILATION_CACHE_DIR or at `<checkout>/.jax_cache` — never anywhere
else, because the directory is part of the cache key."""

import os
import subprocess
import sys

import jax

from moco_tpu.utils import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_set_means_no_dir_update_in_code(tmp_path, monkeypatch):
    """With the env var set, JAX itself owns the placement: the helper
    reports the directory and touches neither the config nor the disk."""
    d = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.delenv("MOCO_TPU_NO_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_persistent_cache() == d
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(d)
    assert not os.path.exists(tmp_path / ".jax_cache")


def test_env_var_unset_means_checkout_jax_cache(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("MOCO_TPU_NO_CACHE", raising=False)
    assert cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    monkeypatch.setattr(cache, "DEFAULT_CACHE_DIR", str(tmp_path / ".jax_cache"))
    before = jax.config.jax_compilation_cache_dir
    try:
        out = cache.enable_persistent_cache()
        assert out == str(tmp_path / ".jax_cache") and os.path.isdir(out)
        assert jax.config.jax_compilation_cache_dir == out
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_cache_opt_out_disables_caching(tmp_path, monkeypatch):
    """The opt-out is real even when the env var points JAX at a cache."""
    monkeypatch.setenv("MOCO_TPU_NO_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    try:
        assert cache.enable_persistent_cache() is None
        assert jax.config.jax_enable_compilation_cache is False
        assert not os.path.exists(tmp_path / "x")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_env_placed_cache_fills_and_checkout_cache_is_not_created(tmp_path):
    """End to end in a fresh process running from a COPY of the helper's
    checkout layout: a compile lands in the env-placed directory and no
    `.jax_cache` (and no per-run directory of any kind) appears."""
    fake_checkout = tmp_path / "checkout"
    (fake_checkout / "moco_tpu" / "utils").mkdir(parents=True)
    for rel in ("moco_tpu/__init__.py", "moco_tpu/utils/__init__.py",
                "moco_tpu/utils/cache.py"):
        with open(os.path.join(REPO, rel)) as src, \
                open(fake_checkout / rel, "w") as dst:
            dst.write(src.read())
    placed = tmp_path / "placed"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from moco_tpu.utils.cache import enable_persistent_cache\n"
        "print(enable_persistent_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(placed))
    env.pop("MOCO_TPU_NO_CACHE", None)
    proc = subprocess.run([sys.executable, "-c", code], cwd=fake_checkout,
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(placed)
    assert os.listdir(placed)  # the compile landed where it was placed
    assert sorted(os.listdir(fake_checkout)) == ["moco_tpu"]
    assert not any("per_run" in d for d, _, _ in os.walk(tmp_path))
