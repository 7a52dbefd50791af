"""The compile-cache placement rule and chip_smoke.py's refusals (CPU tier).

What chip_smoke.py proves on a TPU is proven there (``python chip_smoke.py``
through the chip tool); here only what must hold WITHOUT a chip: the cache
lands where the next process will look, and the smoke never passes on a
CPU or apart from the program it checks.
"""
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import pytest

from neuroimagedisttraining_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Hand the test the live config; put back whatever it held."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_env_var_set_leaves_config_untouched(monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/x")
    before = jax.config.jax_compilation_cache_dir

    def refuse(name, value):
        raise AssertionError(f"config.update({name!r}, {value!r})")

    monkeypatch.setattr(jax.config, "update", refuse)
    assert compile_cache.configure_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_unset_is_the_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # fixed: the same answer every time, no pid, no temp dir
    assert compile_cache.configure_compile_cache() == path
    assert str(os.getpid()) not in path
    assert not path.startswith(tempfile.gettempdir())


def test_cache_configured_by_the_embedding_process_stands(
        monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", "/already/set")
    assert compile_cache.configure_compile_cache() == "/already/set"
    assert jax.config.jax_compilation_cache_dir == "/already/set"


def _run_smoke(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu_without_compiling(tmp_path):
    cache = tmp_path / "cache"
    out = _run_smoke(REPO, {
        "JAX_PLATFORMS": "cpu",
        compile_cache.CACHE_ENV: str(cache),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
    })
    assert out.returncode != 0
    reason = [ln for ln in out.stderr.splitlines() if "chip_smoke:" in ln]
    assert len(reason) == 1 and "no TPU" in reason[0], out.stderr[-2000:]
    assert '"ok"' not in out.stdout
    # nothing was compiled: a compile would have left a cache entry
    assert not cache.exists() or not any(cache.iterdir())


def test_chip_smoke_fails_apart_from_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_smoke(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "cannot import the program" in out.stderr
    assert '"ok"' not in out.stdout
