"""CPU checks of what chip_smoke.py runs: its serve phase at
reduce_for_smoke size, its refusal to run without a TPU, and the
persistent compile-cache helper the entry points share."""

import importlib.util
import os
from pathlib import Path
import subprocess
import sys

import jax
import pytest

from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_reduced(chip_smoke):
    """The smoke's serve phase end to end on the CPU-sized Llama: every
    request gets its 16 tokens and decode logits match forward."""
    stats = chip_smoke.serve_phase(reduce_for_smoke(get_arch("llama3.2-3b")))
    assert stats.tokens == 8 * 16
    # 8 requests over 4 slots: two waves of 15 decodes (prefill gives
    # each request its first token)
    assert stats.steps >= 2 * 15
    assert stats.warmup_s > 0 and stats.run_s > 0


def test_smoke_refuses_cpu():
    """Without a TPU the script exits non-zero, names the platform it
    found and prints no verdict."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path      # fixed, not per call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
