"""Entry points on the CPU: model and mesh selection, the compile cache,
and ``chip_smoke.py`` refusing to run without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import get_config, get_smoke_config
from repro.launch import serve, train
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("smoke", [False, True])
def test_select_model_follows_smoke_flag_not_backend(smoke):
    cfg, mesh = serve.select_model("minitron-4b", smoke)
    want = get_smoke_config if smoke else get_config
    assert cfg == want("minitron-4b")
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == jax.devices()[:1]
    assert all(t == AxisType.Auto for t in mesh.axis_types)


@pytest.mark.parametrize("module", [serve, train])
def test_launchers_never_build_the_production_mesh(module):
    assert "make_production_mesh" not in vars(module)


@pytest.fixture
def cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """Under ``JAX_PLATFORMS=cpu`` (and in a directory holding only the
    script) the smoke test exits non-zero and prints no ``ok`` line."""
    script = REPO / "chip_smoke.py"
    cwd = REPO
    if where == "alone":
        cwd = tmp_path
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
