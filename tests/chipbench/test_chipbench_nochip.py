"""The benchmark never falls back to the CPU, and needs the program."""

from __future__ import annotations

import os
from pathlib import Path
import shutil
import subprocess
import sys

from chipbench import harness
from chipbench import peaks
import pytest

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "nemo-chat", "--seed", str(2 ** 31 + 3), "--seconds",
        "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_without_a_tpu():
    p = _run(REPO)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_chip_check_counts_chips():
    with pytest.raises(harness.NoChip):
        harness.chips(1)          # the CPU backend is no TPU


def test_unknown_device_kind_is_an_error():
    assert peaks.peak_for("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
