"""Compile the main-path Pallas kernels, the full-width Llama-3.2-3B
decode step and a Mistral-Nemo-12B-width decode step for a described (not
attached) TPU v5e chip.

Nothing runs: these tests catch what only the chip's compiler refuses —
tiling rules, unlowerable primitives, VMEM and HBM limits — that the
interpret-mode kernel tests cannot see.  The topology is described inside
a fixture (never at import): only one process may load the TPU library,
and under pytest-xdist only the worker given this file does.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding
import pytest

from repro.configs import get_arch
from repro.kernels import decode_attention
from repro.kernels import flash_attention
from repro.kernels import ssd_scan
from repro.models import decode_step
from repro.models import init_cache
from repro.models import init_params

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compile(fn, *structs):
    return jax.jit(fn).lower(*structs).compile()


def _struct(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(sharding, tree):
    return jax.tree.map(lambda a: _struct(sharding, a.shape, a.dtype), tree)


@pytest.mark.parametrize("pinned_rows", [0, 1024])
def test_flash_attention_compiles(one_chip, pinned_rows):
    """Llama-3.2-3B prefill widths: B=1, S=4096, H=24, G=8, D=128."""
    q = _struct(one_chip, (1, 4096, 24, 128))
    kv = _struct(one_chip, (1, 4096, 8, 128))
    compiled = _compile(functools.partial(
        flash_attention, causal=True, pinned_rows=pinned_rows), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles(one_chip):
    """B=8 sequences over an 8192-row cache, H=24, G=8, D=128."""
    q = _struct(one_chip, (8, 24, 128))
    kv = _struct(one_chip, (8, 8192, 8, 128))
    lens = _struct(one_chip, (8,), jnp.int32)
    compiled = _compile(decode_attention, q, kv, kv, lens)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    """Mamba2-2.7B head widths: B=1, S=2048, H=80, P=64, N=128, chunk 256
    (A scalar-prefetched into SMEM, chunk cumsum as matmuls)."""
    x = _struct(one_chip, (1, 2048, 80, 64))
    dt = _struct(one_chip, (1, 2048, 80), jnp.float32)
    A = _struct(one_chip, (80,), jnp.float32)
    bc = _struct(one_chip, (1, 2048, 1, 128))
    compiled = _compile(functools.partial(ssd_scan, chunk=256),
                        x, dt, A, bc, bc)
    assert "tpu_custom_call" in compiled.as_text()


def test_llama3p2_3b_decode_step_compiles(one_chip):
    """The full-width decode step ServeEngine runs (max_batch 4,
    max_seq 1024), from eval_shape structs; weights, cache and outputs
    fit the chip's 16 GiB HBM."""
    cfg = get_arch("llama3.2-3b")
    params = _place(one_chip, jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)))
    cache = _place(one_chip, jax.eval_shape(lambda: init_cache(cfg, 4, 1024)))
    tokens = _struct(one_chip, (4, 1), jnp.int32)
    compiled = _compile(functools.partial(decode_step, cfg=cfg),
                        params, tokens, cache)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 7e9 < mem.argument_size_in_bytes < used < V5E_HBM_BYTES


def test_dense_decode_writes_kv_in_place(one_chip):
    """Mistral-Nemo-12B widths with 2 layers, batch 8 over max_seq 4096,
    one position per row: each layer writes its new K/V row in place in
    the stacked pool, so the step's temporaries hold no copy of a layer's
    K or V (67 MB each)."""
    cfg = dataclasses.replace(get_arch("mistral-nemo-12b"), n_layers=2)
    params = _place(one_chip, jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)))
    cache = _place(one_chip, jax.eval_shape(lambda: init_cache(cfg, 8, 4096)))
    cache = cache._replace(pos=_struct(one_chip, (8,), jnp.int32))
    tokens = _struct(one_chip, (8, 1), jnp.int32)
    compiled = _compile(functools.partial(decode_step, cfg=cfg),
                        params, tokens, cache)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**20
