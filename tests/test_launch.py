"""Launch plumbing: Auto-axis meshes, the persistent compile cache, and
``chip_smoke.py``'s refusal to run without a TPU."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import compile_cache
from repro.launch.mesh import make_mesh, make_test_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("build", [
    lambda: make_mesh((1, 1), ("data", "model")),
    lambda: make_test_mesh(n_data=1, n_model=1),
])
def test_meshes_have_auto_axes(build):
    mesh = build()
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (AxisType.Auto,) * 2


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # sets nothing


def test_compile_cache_defaults_to_fixed_ignored_checkout_dir(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_test_session_keeps_the_persistent_cache_off(
        monkeypatch, restore_cache_dir):
    """conftest switches the cache off, and an entry point's
    enable_compile_cache() only names a directory: tests write no compiled
    program into the checkout."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_enable_compilation_cache is False
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
