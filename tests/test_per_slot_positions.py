"""``decode_step`` with one position per batch row: each row decodes as a
scalar-position decode of that row would, its new keys and values land at
its own position, and a scalar ``pos`` keeps its shape."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs import reduce_for_smoke
from repro.models import decode_step
from repro.models import init_cache
from repro.models import init_params
from repro.models import prefill
from repro.serve.engine import _splice

LENS = (5, 9, 12, 6)                 # prompt length of each row
S_MAX = 24


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


@pytest.mark.parametrize("name", ["llama3.2-3b", "deepseek-moe-16b",
                                  "mamba2-2.7b", "zamba2-7b", "gemma2-27b",
                                  "qwen2-vl-7b"])
def test_vector_pos_decodes_each_row_at_its_own_position(name):
    cfg = reduce_for_smoke(get_arch(name))
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    pool = init_cache(cfg, len(LENS), S_MAX)
    fill = jax.jit(lambda p, t: prefill(p, t, cfg))
    for i, n in enumerate(LENS):
        prompt = rng.integers(0, cfg.vocab, (1, n)).astype(np.int32)
        pool = _splice(pool, fill(params, prompt)[1], i)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (len(LENS), 1)), jnp.int32)
    decode = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))

    pos = jnp.asarray(LENS, jnp.int32)
    logits, new = decode(params, toks, pool._replace(pos=pos))
    np.testing.assert_array_equal(np.asarray(new.pos), np.asarray(LENS) + 1)
    for i, n in enumerate(LENS):
        want, one = decode(params, toks, pool._replace(pos=jnp.int32(n)))
        assert one.pos.shape == () and int(one.pos) == n + 1
        np.testing.assert_allclose(_f32(logits[i]), _f32(want[i]),
                                   rtol=3e-2, atol=3e-2)
        others = np.arange(S_MAX) != n
        for got, ref, old in ((new.k, one.k, pool.k),
                              (new.v, one.v, pool.v)):
            if old is None:
                continue
            assert np.any(_f32(got[:, i, n]) != 0)
            np.testing.assert_allclose(_f32(got[:, i, n]), _f32(ref[:, i, n]),
                                       rtol=3e-2, atol=3e-2)
            np.testing.assert_array_equal(_f32(got[:, i, others]),
                                          _f32(old[:, i, others]))
        for got, ref in ((new.conv_x, one.conv_x), (new.conv_bc, one.conv_bc),
                         (new.ssm, one.ssm)):
            if got is not None:
                np.testing.assert_allclose(_f32(got[:, i]), _f32(ref[:, i]),
                                           rtol=3e-2, atol=3e-2)
