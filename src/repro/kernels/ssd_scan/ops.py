"""jit-callable wrapper for the SSD scan kernel."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import build_ssd_call


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 256,
             interpret: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernelized SSD.  x (B,S,H,P); dt (B,S,H); A (H,); B/C (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        raise ValueError("sequence must be chunk-aligned")
    rep = h // g

    # flatten (B,S,H,P) → (B·H, S, P); broadcast groups to heads
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    # one 32-bit scalar per (batch, head) row, prefetched into SMEM
    af = jnp.broadcast_to(A[None], (b, h)).reshape(b * h).astype(jnp.float32)
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    bf = Bh.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    cf = Ch.transpose(0, 2, 1, 3).reshape(b * h, s, n)

    call = build_ssd_call(bh=b * h, seq=s, p=p, n=n, chunk=chunk,
                          dtype=x.dtype, interpret=interpret)
    yf, state = call(af, xf, dtf, bf, cf)
    y = yf.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    # kernel state layout (N, P) → model layout (P, N)
    final = state.reshape(b, h, n, p).transpose(0, 1, 3, 2)
    return y, final
