"""``decode_step`` with one position per batch row: each row decodes as a
scalar-position decode of that row would, its new keys and values land at
its own position, and a scalar ``pos`` keeps its shape.  Over two steps in
a row every layer keeps both of its writes and no other entry of the pool
changes, whether ``pos`` is a vector or a scalar."""

from __future__ import annotations

import functools

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


FAMILIES = ["llama3.2-3b", "deepseek-moe-16b", "mamba2-2.7b", "zamba2-7b",
            "gemma2-27b", "qwen2-vl-7b"]


@functools.lru_cache(maxsize=None)
def _served(name):
    """Reduced ``name``'s params, a pool with row ``i`` prefilled to
    ``LENS[i]``, two rounds of next tokens and its jitted decode step."""
    cfg = reduce_for_smoke(get_arch(name))
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    pool = init_cache(cfg, len(LENS), S_MAX)
    fill = jax.jit(lambda p, t: prefill(p, t, cfg))
    for i, n in enumerate(LENS):
        prompt = rng.integers(0, cfg.vocab, (1, n)).astype(np.int32)
        pool = _splice(pool, fill(params, prompt)[1], i)
    toks = [jnp.asarray(rng.integers(0, cfg.vocab, (len(LENS), 1)), jnp.int32)
            for _ in range(2)]
    decode = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))
    return params, pool, toks, decode


@pytest.mark.parametrize("name", FAMILIES)
def test_vector_pos_decodes_each_row_at_its_own_position(name):
    params, pool, (toks, _), decode = _served(name)
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


@pytest.mark.parametrize("per_row", [True, False], ids=["vector", "scalar"])
@pytest.mark.parametrize("name", FAMILIES)
def test_two_steps_keep_every_entry_they_do_not_write(name, per_row):
    """Two decode steps in a row: each layer holds both new rows of every
    batch row, every other (layer, row, position) is bit-identical to the
    pool before them, and the second step's logits are those of a
    scalar-position decode (a vector ``pos`` of one value, for a scalar)."""
    params, pool, (t1, t2), decode = _served(name)
    top = max(LENS)
    starts = np.asarray(LENS) if per_row else np.full(len(LENS), top)
    pos = jnp.asarray(LENS, jnp.int32) if per_row else jnp.int32(top)
    logits, two = decode(params, t2, decode(params, t1,
                                            pool._replace(pos=pos))[1])
    assert two.pos.shape == pos.shape
    np.testing.assert_array_equal(np.broadcast_to(np.asarray(two.pos),
                                                  starts.shape), starts + 2)

    def twice(p):
        return decode(params, t2, decode(params, t1, pool._replace(pos=p))[1])

    refs = ({n: twice(jnp.int32(n))[0] for n in set(LENS)} if per_row
            else {top: twice(jnp.full((len(LENS),), top, jnp.int32))[0]})
    for i, n in enumerate(starts):
        np.testing.assert_allclose(_f32(logits[i]), _f32(refs[n][i]),
                                   rtol=3e-2, atol=3e-2)
        written = np.isin(np.arange(S_MAX), (n, n + 1))
        for got, old in ((two.k, pool.k), (two.v, pool.v)):
            if old is None:
                continue
            assert np.all(np.any(_f32(got[:, i][:, written]) != 0,
                                 axis=(2, 3)))
            np.testing.assert_array_equal(_f32(got[:, i][:, ~written]),
                                          _f32(old[:, i][:, ~written]))
