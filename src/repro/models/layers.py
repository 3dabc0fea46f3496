"""Transformer building blocks shared by all assigned architectures.

Pure-functional JAX: params are pytrees of jnp arrays; every function takes
explicit config arguments.  Sharding is expressed through
``repro.sharding.constrain`` logical-axis hints so the same code runs on a
single CPU device (smoke tests) and on the production mesh (dry-run).
"""

from __future__ import annotations

import math
from typing import Optional
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import act_axes
from repro.sharding import constrain
from repro.sharding import current_mesh
from repro.sharding.api import ACT_SEQ


def row_parallel_out(y: jnp.ndarray, w: jnp.ndarray) -> Optional[jnp.ndarray]:
    """Megatron-SP row-parallel output projection (§Perf lever).

    y (B, S, F) with F sharded over ``model``; w (F, D) sharded on dim 0.
    Computes the partial matmul per shard and **reduce-scatters over the
    sequence** (psum_scatter) so the residual stream leaves the block
    sequence-sharded — replacing the all-reduce the plain lowering emits
    (wire bytes: (g-1)/g×N vs 2·(g-1)/g×N).  Returns None when the layout
    prerequisites don't hold (caller falls back to the einsum+constraint
    path).
    """
    mesh = current_mesh()
    if not ACT_SEQ[0] or mesh is None:
        return None
    mdl = mesh.shape.get("model", 1)
    if mdl <= 1 or y.shape[1] % mdl or y.shape[2] % mdl or \
            w.shape[0] % mdl:
        return None
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    if y.shape[0] % max(mesh.shape.get("data", 1)
                        * mesh.shape.get("pod", 1), 1):
        dp = None

    def f(y_loc, w_loc):
        part = jnp.einsum("bsf,fd->bsd", y_loc, w_loc)
        return jax.lax.psum_scatter(part, "model", scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, None, "model"), P("model", None)),
        out_specs=P(dp, "model", None), check_vma=False)(y, w)

# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
             plus_one: bool = False) -> jnp.ndarray:
    """RMSNorm; ``plus_one`` selects the Gemma convention ((1+w)·x̂)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if plus_one else scale
    return (x * w).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None
               ) -> jnp.ndarray:
    """Rotate ``x`` (..., S, H, D) by position-dependent angles.

    ``positions``: (B, S) for standard RoPE, or (3, B, S) for Qwen2-VL
    M-RoPE, where the three planes carry temporal/height/width positions
    and ``mrope_sections`` gives the per-plane frequency-section sizes
    (in half-dims, summing to D/2).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    if mrope_sections is None:
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    else:
        if positions.ndim == 2:                        # text-only fallback
            positions = jnp.broadcast_to(positions[None],
                                         (3,) + positions.shape)
        parts = []
        start = 0
        for plane, sec in enumerate(mrope_sections):
            f = freqs[start:start + sec]
            parts.append(positions[plane][..., None].astype(jnp.float32) * f)
            start += sec
        angles = jnp.concatenate(parts, axis=-1)       # (B,S,D/2)
    cos = jnp.cos(angles)[..., None, :]                # (B,S,1,D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA; optional logit softcap and sliding window)
# ---------------------------------------------------------------------------
def _soft_cap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True,
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  q_positions: Optional[jnp.ndarray] = None,
                  kv_positions: Optional[jnp.ndarray] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Grouped-query attention.

    q: (B, Sq, H, D); k/v: (B, Sk, G, D) with H % G == 0.
    ``q_positions``/``kv_positions``: (B, Sq)/(B, Sk) absolute positions for
    masking (required when Sq != Sk, i.e. decode); default = aranges.
    """
    b, sq, h, d = q.shape
    _, sk, g, _ = k.shape
    group = h // g
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, g, group, d)
    scores = jnp.einsum("bsgqd,btgd->bgqst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = _soft_cap(scores, softcap)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk), (b, sk))
    pos_q = q_positions[:, None, None, :, None]        # (b,1,1,sq,1)
    pos_k = kv_positions[:, None, None, None, :]       # (b,1,1,1,sk)
    mask = jnp.ones((b, 1, 1, sq, sk), dtype=bool)
    if causal:
        mask &= pos_k <= pos_q
    if window is not None:
        mask &= pos_k > pos_q - window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqst,btgd->bsgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _write_rows(cache: jnp.ndarray, x: jnp.ndarray, pos: jnp.ndarray,
                layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``cache`` (B, S_max, G, D), or layer ``layer`` of the stacked pool
    (L, B, S_max, G, D) in place, with row ``i`` of ``x`` (B, s, G, D)
    written at positions ``pos[i]`` .. ``pos[i] + s - 1`` (every row from
    ``pos`` when it is a scalar)."""
    b, s = x.shape[:2]
    cols = jnp.expand_dims(pos, -1) + jnp.arange(s)
    idx = (jnp.arange(b)[:, None], cols)
    if layer is not None:
        idx = (layer,) + idx
    return cache.at[idx].set(x.astype(cache.dtype))


def attention_block(params, x, cfg, *, layer_is_local=None, positions=None,
                    kv_cache=None, cache_pos=None, layer=None):
    """Full attention sub-block: norm → qkv → rope → attn → out-proj.

    With ``kv_cache=(k, v)`` (B, S_max, G, D), runs in decode mode: writes
    the new K/V at ``cache_pos`` and attends over the cache.  A scalar
    ``cache_pos`` writes every row at that position; a (B,) vector writes
    each row at its own.  With ``layer``, ``kv_cache`` is the stacked
    (L, B, S_max, G, D) pool: the new K/V are written in place into layer
    ``layer``, attention reads that layer, and the pool is returned whole.
    Returns (out, new_kv_cache_or_None).
    """
    b, s, _ = x.shape
    h = rms_norm(x, params["ln"], plus_one=cfg.gemma_norm)
    h = constrain(h, ("dp", None, None))
    q = jnp.einsum("bsd,dhe->bshe", h, params["wq"])
    k = jnp.einsum("bsd,dge->bsge", h, params["wk"])
    v = jnp.einsum("bsd,dge->bsge", h, params["wv"])
    q = constrain(q, ("dp", None, "tp", None))
    k = constrain(k, ("dp", None, "tp", None))
    v = constrain(v, ("dp", None, "tp", None))

    if positions is None:
        if cache_pos is None:
            base = jnp.arange(s)
        elif jnp.ndim(cache_pos) == 0:
            base = cache_pos + jnp.arange(s)
        else:
            base = cache_pos[:, None] + jnp.arange(s)
        positions = jnp.broadcast_to(base, (b, s))
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], plus_one=cfg.gemma_norm)
        k = rms_norm(k, params["k_norm"], plus_one=cfg.gemma_norm)

    window = None
    if layer_is_local is not None and cfg.window is not None:
        # per-layer local/global alternation (Gemma2); layer_is_local is a
        # traced scalar → select the window mask arithmetically
        window_arr = jnp.where(layer_is_local, cfg.window, jnp.int32(2**30))
        window = window_arr
    scale = cfg.attn_scale or (1.0 / math.sqrt(cfg.head_dim))

    if kv_cache is None:
        out = gqa_attention(q, k, v, causal=True, window=window,
                            softcap=cfg.attn_softcap, scale=scale,
                            q_positions=positions, kv_positions=positions)
        new_cache = None
    else:
        ck, cv = kv_cache
        if layer is not None:
            ck = _write_rows(ck, k, cache_pos, layer)
            cv = _write_rows(cv, v, cache_pos, layer)
            k_l = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
        elif jnp.ndim(cache_pos) == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, axis=1)
            k_l, v_l = ck, cv
        else:
            ck = _write_rows(ck, k, cache_pos)
            cv = _write_rows(cv, v, cache_pos)
            k_l, v_l = ck, cv
        s_max = k_l.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s_max), (b, s_max))
        # mask out unwritten slots via position comparison (kv_pos > current)
        out = gqa_attention(q, k_l.astype(q.dtype), v_l.astype(q.dtype),
                            causal=True, window=window,
                            softcap=cfg.attn_softcap, scale=scale,
                            q_positions=positions, kv_positions=kv_pos)
        new_cache = (ck, cv)

    b2, s2, hh, ee = out.shape
    wo2 = params["wo"].reshape(hh * ee, -1)
    rp = row_parallel_out(out.reshape(b2, s2, hh * ee), wo2)
    if rp is not None:
        return rp, new_cache
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    out = constrain(out, act_axes())
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------
def mlp_block(params, x, cfg):
    h = rms_norm(x, params["ln"], plus_one=cfg.gemma_norm)
    h = constrain(h, ("dp", None, None))
    gate = jnp.einsum("bsd,df->bsf", h, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, params["w_up"])
    gate = constrain(gate, ("dp", None, "tp"))
    act = jax.nn.gelu(gate, approximate=True) if cfg.act == "gelu" \
        else jax.nn.silu(gate)
    rp = row_parallel_out(act * up, params["w_down"])
    if rp is not None:
        return rp
    out = jnp.einsum("bsf,fd->bsd", act * up, params["w_down"])
    return constrain(out, act_axes())
