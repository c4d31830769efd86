"""chip_smoke.py on the CPU: its body at a tiny size, and its refusal to
run without a TPU.  Also the persistent compile cache it turns on."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_passes_its_checks_at_small_n(chip_smoke):
    lines = []
    out = chip_smoke.run_one_chip(n=2000, n_queries=32, l_sweep=(32, 64),
                                  log=lines.append)
    assert out["recall"][64] >= chip_smoke.RECALL_FLOOR
    assert set(out["build_s"]) == {"data", "knn", "graph", "partition", "pq",
                                   "head"}
    assert any("exec tier" in line and "same ids and dists=True" in line
               for line in lines)


def test_four_chip_body_at_small_n():
    """``run_four_chips`` on four host CPU devices (a subprocess: the device
    count is fixed when a process first touches JAX)."""
    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(ROOT / 'chip_smoke.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec); spec.loader.exec_module(mod)\n"
        "out = mod.run_four_chips(n=2000, n_queries=32)\n"
        "assert all(out['same'].values()), out\n"
        "print('FOUR-CHIP-BODY-OK')\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "FOUR-CHIP-BODY-OK" in r.stdout
    assert "partition -> chip: {0: 0, 1: 1, 2: 2, 3: 3}" in r.stdout


def test_smoke_main_refuses_cpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_prefers_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
