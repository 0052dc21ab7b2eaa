"""Launch-side guards: where the compile cache lives, and ``chip_smoke.py``
refusing to run anywhere but on a TPU."""

import contextlib
import os
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@contextlib.contextmanager
def _cache_config_restored():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield saved
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with _cache_config_restored() as saved:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no other directory
        assert (jax.config.jax_compilation_cache_dir
                == saved["jax_compilation_cache_dir"])


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with _cache_config_restored():
        first = compile_cache.enable_compile_cache()
        assert first == os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first


def _run_smoke(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    proc = _run_smoke(SMOKE, ROOT)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout == ""


def test_chip_smoke_alone_refuses(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(SMOKE, tmp_path)
    proc = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""
