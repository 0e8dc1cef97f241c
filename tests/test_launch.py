"""Launch surfaces: the compile-cache placement, the serving CLI on one
device, and ``chip_smoke.py``'s refusal to run anywhere but a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**kw):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", **kw)
    env.pop("XLA_FLAGS", None)
    return env


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_follows_env(monkeypatch, tmp_path, restore_cache_dir):
    """A set JAX_COMPILATION_CACHE_DIR is used as-is; nothing in code
    overrides it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_serve_cli_one_device_serves_one_instance():
    """One device gives one TP1 instance (no fake-device default), and
    ``--smoke`` switches to the reduced config."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--requests", "3", "--long-every", "0", "--gen-tokens", "2"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "gemma-2b-smoke: 1 instances x 1 devices" in out.stdout
    assert "finished=3" in out.stdout


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke test fails and prints no result line."""
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, env=_env(),
                         cwd=REPO, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_kernel_check_separates_fault():
    """``chip_smoke``'s kernel check at gemma-2b widths, interpreted:
    the sound kernel reads under its tolerance and the dropped-page
    control over it."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.kernel_check(interpret=True)"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "scattered pool identical True" in out.stdout
