"""The compile-cache placement rule, chip_smoke.py's refusals and the
multi-device dry run (CPU tier).

What chip_smoke.py proves on a TPU is proven there (``python chip_smoke.py``
through the chip tool); here only what must hold WITHOUT a chip: the cache
lands where the next process will look, the smoke never passes on a
CPU or apart from the program it checks, and ``__graft_entry__.py`` runs a
sharded round on the devices it is given.
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


def test_dryrun_multichip_runs_a_sharded_round_on_four_devices():
    """``__graft_entry__.py 4`` as the driver starts it, on four virtual CPU
    devices: one compiled round on a ``clients`` mesh with its all-reduce,
    finite loss and eval, the hybrid mesh, and nothing timed."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    # the suite's own cache directory, not the checkout's .jax_cache
    env[compile_cache.CACHE_ENV] = jax.config.jax_compilation_cache_dir
    out = subprocess.run(
        [sys.executable, "__graft_entry__.py", "4"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    ok = [ln for ln in out.stdout.splitlines()
          if ln.startswith("dryrun_multichip OK:")]
    assert len(ok) == 1, out.stdout[-2000:]
    line = ok[0]
    assert "4 cpu devices" in line and "'clients': 4" in line
    assert "hybrid mesh={'clients': 2, 'space': 2} OK" in line
    n = int(line.split("all-reduces in the round=")[1].split(",")[0])
    assert n >= 1
    # a check that the round runs, never a timing
    assert " ms" not in line and "share" not in line
