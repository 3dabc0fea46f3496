"""Mamba2 SSD chunked-scan Pallas-TPU kernel.

Grid: (batch·heads, n_chunks) — the chunk axis is the innermost,
*sequential* TPU grid dimension, so the inter-chunk SSM state lives in
VMEM scratch across chunks and is never written back to HBM until the
final state output.  This is the orchestrator's dead-block insight applied
to SSM state: a chunk's running state has a known one-chunk lifetime and
therefore never claims HBM bandwidth (contrast a naive implementation
that materializes (n_chunks, P, N) states).

Per chunk (intra-chunk quadratic + state update):
    L[i,j]   = exp(cum_i - cum_j) (causal)        — (Q, Q)
    y_diag   = (C·Bᵀ ∘ L) (x·dt)                  — (Q, P)
    y_off    = C · state_in · exp(cum)            — (Q, P)
    state    = state_in·exp(total) + Bᵀ·(x·dt·decay_to_end)
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp


def _dot(a, b, ca: int, cb: int):
    """f32 contraction of ``a`` dim ``ca`` with ``b`` dim ``cb`` at fp32
    precision.  The MXU's default pass rounds f32 operands to bf16; the
    decay sums, the masked scores and the carried state are genuinely f32,
    and one bf16 pass puts y off its f32 oracle by ~0.2 at |y| ~ 30."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
               state_ref, *, chunk: int, n_chunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[pl.program_id(0)]                        # scalar A for head
    dt = dt_ref[0].astype(jnp.float32)                 # (Q, 1)
    x = x_ref[0].astype(jnp.float32)                   # (Q, P)
    B = b_ref[0].astype(jnp.float32)                   # (Q, N)
    C = c_ref[0].astype(jnp.float32)                   # (Q, N)

    # Inclusive cumulative sum of dt·A as triangular-ones matmuls: Mosaic
    # has no cumsum, and the transposed-lhs form yields the row copy of
    # the sums without a (Q, 1) → (1, Q) relayout.
    da = dt * a                                        # (Q, 1)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    cum = _dot(causal.astype(jnp.float32), da, 1, 0)   # (Q, 1)
    cum_row = _dot(da, (iota_i <= iota_j).astype(jnp.float32),  # (1, Q)
                   0, 0)
    total = jnp.sum(da)                                # scalar
    xd = x * dt                                        # (Q, P)

    # intra-chunk: causal decay matrix L
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)  # (Q, Q)
    y = _dot(_dot(C, B, 1, 1) * L, xd, 1, 0)           # (Q, P)

    # inter-chunk: contribution of the carried state, then state update
    state = state_ref[...]                             # (N, P)
    y += jnp.exp(cum) * _dot(C, state, 1, 0)
    decay_to_end = jnp.exp(total - cum)                # (Q, 1)
    new_state = state * jnp.exp(total) + _dot(B, xd * decay_to_end, 0, 0)
    state_ref[...] = new_state
    y_ref[0, ...] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, ...] = state_ref[...]


def build_ssd_call(*, bh: int, seq: int, p: int, n: int, chunk: int,
                   dtype, interpret: bool):
    n_chunks = seq // chunk
    grid = (bh, n_chunks)
    kernel = functools.partial(ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                # A (SMEM)
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, chunk, p), lambda b, c, a: (b, c, 0)),  # x
                pl.BlockSpec((1, chunk, 1), lambda b, c, a: (b, c, 0)),  # dt
                pl.BlockSpec((1, chunk, n), lambda b, c, a: (b, c, 0)),  # B
                pl.BlockSpec((1, chunk, n), lambda b, c, a: (b, c, 0)),  # C
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, p), lambda b, c, a: (b, c, 0)),  # y
                pl.BlockSpec((1, n, p), lambda b, c, a: (b, 0, 0)),  # state
            ],
            scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, p), dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        interpret=interpret,
    )
