"""Entry-point plumbing: the persistent compile cache location and the
chip smoke script's refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import configure_compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_checkout(tmp_path, monkeypatch,
                                            cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = configure_compile_cache(str(tmp_path))
    assert path == os.path.join(str(tmp_path), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # fixed per checkout: a second call names the same directory
    assert configure_compile_cache(str(tmp_path)) == path


def test_compile_cache_env_wins(tmp_path, monkeypatch, cache_dir_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and no other
    directory is set in code."""
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache(str(tmp_path)) == env_dir
    assert jax.config.jax_compilation_cache_dir == before


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_cpu():
    out = _run_smoke(_ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied away from the repository it has nothing to run."""
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    out = _run_smoke(str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
