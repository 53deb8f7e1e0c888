"""``chip_smoke.py`` off the chip: it refuses a CPU backend, and its phases
run end to end at a tiny size with interpret-mode Pallas.  Also where the
entry points put JAX's persistent compilation cache."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture
def chip_smoke(monkeypatch):
    # the script defaults JAX_PLATFORMS to tpu when it is unset; keep this
    # process on the backend it already has
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS") or "cpu")
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_require_tpu_raises_on_cpu(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu'"):
        chip_smoke.require_tpu()


def test_chip_smoke_serve_phase_tiny(chip_smoke, capsys):
    chip_smoke.phase_serve(n_requests=20)
    out = capsys.readouterr().out
    assert "20 requests" in out
    assert "mismatches vs ir.execute_graph: 0" in out


def test_chip_smoke_kernel_phase_tiny(chip_smoke, capsys):
    chip_smoke.phase_kernels(bf16_shape=(128, 512, 256), int8_shape=(64, 1024, 256))
    out = capsys.readouterr().out
    assert "0 elements outside" in out
    assert "0 elements differ" in out


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, monkeypatch, tmp_path, restore_cache_dir):
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
    assert compile_cache.use_persistent_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compiled_programs_land_in_the_env_cache_dir(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_persistent_compile_cache\n"
        "use_persistent_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()\n"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "PYTHONPATH": str(REPO / "src"),
    }
    subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=tmp_path, check=True, timeout=300
    )
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())
