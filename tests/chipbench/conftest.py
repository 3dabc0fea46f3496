"""Fixtures for the chip benchmark's CPU tests: a checkout-like root that
holds the benchmark with two tiny cells (a dense and an SSM configuration)
next to the real ones."""

from __future__ import annotations

import json
from pathlib import Path
import shutil
import sys

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DENSE = {
    "name": "tiny-dense", "family": "dense", "base_arch": "mistral-nemo-12b",
    "config": {"head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
               "intermediate_size": 128, "num_attention_heads": 4,
               "num_hidden_layers": 2, "num_key_value_heads": 2,
               "rms_norm_eps": 1e-5, "rope_theta": 1e6,
               "tie_word_embeddings": False, "vocab_size": 256},
    "engine": {"max_batch": 4, "max_seq": 64},
    "check": {"sample": {"min_tokens": 1000, "max_ref_tokens": 4096},
              "limits": {"logit_gap": 0.08}},
}
TINY_SSM = {
    "name": "tiny-ssm", "family": "ssm", "base_arch": "mamba2-2.7b",
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 250,
               "pad_vocab_size_multiple": 16,
               "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2,
                           "headdim": 16, "ngroups": 1, "chunk_size": 32},
               "tie_embeddings": True, "norm_epsilon": 1e-5,
               "residual_in_fp32": False},
    "engine": {"max_batch": 4, "max_seq": 128},
    "check": {"sample": {"min_tokens": 1000, "max_ref_tokens": 4096},
              "limits": {"logit_gap": 0.08}},
}
TINY_TRAFFIC = {
    "arrivals": {"process": "bursts", "rate": 12.0, "burst_mean": 4},
    "prompt_len": {"values": [8, 16], "weights": [1, 1]},
    "output_len": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                   "min": 2, "max": 8},
    "schedule_seed": 5,
}
TINY_BURST = dict(TINY_TRAFFIC,
                  prompt_len={"values": [16, 32], "weights": [1, 1]})


@pytest.fixture
def bench_root(tmp_path, monkeypatch) -> Path:
    """A copy of the benchmark whose BENCHMARK.json adds cells ``td``
    (tiny dense) and ``ts`` (tiny SSM), with the compile cache off."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d in (TINY_DENSE, TINY_SSM):
        (root / "chipbench" / "configs" / f"{d['name']}.json").write_text(
            json.dumps(d))
    (root / "chipbench" / "traffic" / "tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "chipbench" / "traffic" / "tiny-burst.json").write_text(
        json.dumps(TINY_BURST))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"] += [
        {"name": "td", "config": "tiny-dense", "traffic": "tiny",
         "chips": 1, "why": "tiny dense cell for CPU tests"},
        {"name": "ts", "config": "tiny-ssm", "traffic": "tiny-burst",
         "chips": 1, "why": "tiny SSM cell for CPU tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["td", "ts"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    import jax

    from chipbench import harness
    from chipbench import peaks
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    # the chip check skipped: the CPU stands in, read with the v5e's peaks
    monkeypatch.setattr(harness, "chips", lambda n: jax.devices())
    monkeypatch.setattr(peaks, "peak_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    return root


@pytest.fixture
def run_tiny(bench_root):
    """Runs a cell of ``bench_root`` on the CPU for 2 s; returns the
    result line."""
    import time

    from chipbench.harness import run_cell

    def run(cell: str, seed: int = 7, trace: bool = False, **kw):
        return run_cell(bench_root, cell, seed, 2.0, trace,
                        time.perf_counter(), **kw)
    return run
