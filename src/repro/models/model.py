"""Model assembly: init / forward / prefill / decode for every assigned
architecture family (dense, MoE, SSM, hybrid).

Design notes
------------
* Layers are stacked along a leading axis and iterated with
  ``jax.lax.scan`` so the lowered HLO stays small for 28–81-layer models
  (critical for the 40-cell dry-run compile budget).
* Per-layer heterogeneity (Gemma2 local/global alternation) is expressed
  as scanned flag arrays, not Python branches.
* ``tie_embeddings`` is honored as *intent only*: the lm_head is always a
  separate parameter so that the embedding can be D-sharded (cheap
  gather) while the head stays vocab-sharded (sharded logits/loss).
  Recorded in DESIGN.md §7.
* Modality archs (musicgen [audio], qwen2-vl [vlm]) take optional
  ``input_embeds`` (precomputed frame/patch embeddings — the frontend is
  a stub per spec) and, for M-RoPE, 3-plane ``positions``.
"""

from __future__ import annotations

import math
import os
from typing import Any
from typing import Dict
from typing import NamedTuple
from typing import Optional
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.configs import DENSE
from repro.configs import HYBRID
from repro.configs import MOE
from repro.configs import SSM
from repro.sharding import act_axes
from repro.sharding import constrain

from .layers import attention_block
from .layers import mlp_block
from .layers import rms_norm
from .moe import init_moe_params
from .moe import moe_ffn
from .ssm import Mamba2Cache
from .ssm import init_mamba2_cache
from .ssm import init_mamba2_params
from .ssm import mamba2_block

DTYPE = jnp.bfloat16

# Dry-run roofline accounting: XLA's HloCostAnalysis counts a while-loop
# body ONCE (trip count unknown to it), so scanned layer stacks under-
# report FLOPs/bytes by ~n_layers×.  launch/dryrun traces a second,
# fully-unrolled lowering (flag below) purely for cost analysis, while
# the scanned form is what compiles/ships.
UNROLL_SCANS = [os.environ.get("REPRO_UNROLL_SCANS", "0") == "1"]


def _scan(f, init, xs, **kw):
    return jax.lax.scan(f, init, xs,
                        unroll=True if UNROLL_SCANS[0] else 1, **kw)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------
def _init_attn(key, cfg: ArchConfig, n_layers: int):
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    ln_init = jnp.zeros if cfg.gemma_norm else jnp.ones
    p = {
        "ln": ln_init((n_layers, d), DTYPE),
        "wq": (jax.random.normal(ks[0], (n_layers, d, h, hd)) * s
               ).astype(DTYPE),
        "wk": (jax.random.normal(ks[1], (n_layers, d, g, hd)) * s
               ).astype(DTYPE),
        "wv": (jax.random.normal(ks[2], (n_layers, d, g, hd)) * s
               ).astype(DTYPE),
        "wo": (jax.random.normal(ks[3], (n_layers, h, hd, d))
               * (h * hd) ** -0.5).astype(DTYPE),
    }
    if cfg.qk_norm:
        p["q_norm"] = ln_init((n_layers, hd), DTYPE)
        p["k_norm"] = ln_init((n_layers, hd), DTYPE)
    return p


def _init_mlp(key, cfg: ArchConfig, n_layers: int, d_ff: int):
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    ln_init = jnp.zeros if cfg.gemma_norm else jnp.ones
    return {
        "ln": ln_init((n_layers, d), DTYPE),
        "w_gate": (jax.random.normal(ks[0], (n_layers, d, d_ff))
                   * d ** -0.5).astype(DTYPE),
        "w_up": (jax.random.normal(ks[1], (n_layers, d, d_ff))
                 * d ** -0.5).astype(DTYPE),
        "w_down": (jax.random.normal(ks[2], (n_layers, d_ff, d))
                   * d_ff ** -0.5).astype(DTYPE),
    }


def _squeeze0(tree):
    return jax.tree.map(lambda a: a[0] if a.ndim > 0 else a, tree)


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (v, d)) * d ** -0.5
                  ).astype(DTYPE),
        "ln_f": (jnp.zeros if cfg.gemma_norm else jnp.ones)((d,), DTYPE),
        "lm_head": (jax.random.normal(keys[1], (d, v)) * d ** -0.5
                    ).astype(DTYPE),
    }
    if cfg.family == DENSE:
        params["layers"] = {
            "attn": _init_attn(keys[2], cfg, cfg.n_layers),
            "mlp": _init_mlp(keys[3], cfg, cfg.n_layers, cfg.d_ff),
        }
    elif cfg.family == MOE:
        nd = cfg.moe.first_dense
        nm = cfg.n_layers - nd
        if nd:
            params["dense_layers"] = {
                "attn": _init_attn(keys[2], cfg, nd),
                "mlp": _init_mlp(keys[3], cfg, nd, cfg.d_ff),
            }
        moe_keys = jax.random.split(keys[4], nm)
        params["moe_layers"] = {
            "attn": _init_attn(keys[5], cfg, nm),
            "moe": jax.vmap(lambda k: init_moe_params(k, d, cfg.moe, DTYPE)
                            )(moe_keys),
        }
    elif cfg.family == SSM:
        lk = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: init_mamba2_params(k, d, cfg.ssm, DTYPE))(lk)
    elif cfg.family == HYBRID:
        period = cfg.hybrid_period
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        gk = jax.random.split(keys[2], n_groups * period)
        stacked = jax.vmap(
            lambda k: init_mamba2_params(k, d, cfg.ssm, DTYPE))(gk)
        params["mamba_groups"] = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]), stacked)
        if tail:
            tk = jax.random.split(keys[3], tail)
            params["mamba_tail"] = jax.vmap(
                lambda k: init_mamba2_params(k, d, cfg.ssm, DTYPE))(tk)
        params["shared_attn"] = _squeeze0(_init_attn(keys[4], cfg, 1))
        params["shared_mlp"] = _squeeze0(_init_mlp(keys[5], cfg, 1,
                                                   cfg.d_ff))
    else:
        raise ValueError(cfg.family)
    return params


def local_flags(cfg: ArchConfig, n_layers: Optional[int] = None
                ) -> jnp.ndarray:
    n = n_layers if n_layers is not None else cfg.n_layers
    if cfg.local_global_period is None or cfg.window is None:
        return jnp.zeros((n,), dtype=bool)
    idx = jnp.arange(n)
    # every `period`-th layer is global; the rest use the sliding window
    return (idx % cfg.local_global_period) != (cfg.local_global_period - 1)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def embed_tokens(params, tokens, cfg: ArchConfig,
                 input_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if input_embeds is not None:
        x = input_embeds.astype(DTYPE)
    else:
        x = params["embed"][tokens]
    if cfg.gemma_norm:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), DTYPE)
    return constrain(x, act_axes())


def lm_logits(params, x, cfg: ArchConfig) -> jnp.ndarray:
    x = rms_norm(x, params["ln_f"], plus_one=cfg.gemma_norm)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = constrain(logits, ("dp", None, "tp"))
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


# ---------------------------------------------------------------------------
# Forward (training / prefill-style full-sequence)
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg: ArchConfig, *,
            positions: Optional[jnp.ndarray] = None,
            input_embeds: Optional[jnp.ndarray] = None,
            remat: bool = True) -> jnp.ndarray:
    x = embed_tokens(params, tokens, cfg, input_embeds)

    if cfg.family == DENSE:
        x = _dense_stack(params["layers"], x, cfg, positions, remat,
                         local_flags(cfg))
    elif cfg.family == MOE:
        nd = cfg.moe.first_dense
        if nd:
            x = _dense_stack(params["dense_layers"], x, cfg, positions,
                             remat, local_flags(cfg, nd))
        x = _moe_stack(params["moe_layers"], x, cfg, positions, remat)
    elif cfg.family == SSM:
        x = _ssm_stack(params["layers"], x, cfg, remat)
    elif cfg.family == HYBRID:
        x = _hybrid_stack(params, x, cfg, positions, remat)
    return lm_logits(params, x, cfg)


def _maybe_remat(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def _dense_stack(layers, x, cfg, positions, remat, flags):
    def block(h, sc):
        pa, pm, fl = sc
        a, _ = attention_block(pa, h, cfg, layer_is_local=fl,
                               positions=positions)
        h = h + a
        h = h + mlp_block(pm, h, cfg)
        return h, None

    xs = (layers["attn"], layers["mlp"], flags)
    x, _ = _scan(_maybe_remat(block, remat), x, xs)
    return x


def _moe_stack(layers, x, cfg, positions, remat):
    def block(h, sc):
        pa, pm = sc
        a, _ = attention_block(pa, h, cfg, positions=positions)
        h = h + a
        h = h + moe_ffn(pm, h, cfg, cfg.moe)
        return h, None

    x, _ = _scan(_maybe_remat(block, remat), x,
                        (layers["attn"], layers["moe"]))
    return x


def _ssm_stack(layers, x, cfg, remat):
    def block(h, p):
        y, _ = mamba2_block(p, h, cfg.ssm)
        return h + y, None

    x, _ = _scan(_maybe_remat(block, remat), x, layers)
    return x


def _hybrid_stack(params, x, cfg, positions, remat):
    shared_attn = params["shared_attn"]
    shared_mlp = params["shared_mlp"]

    def mamba_layer(h, p):
        y, _ = mamba2_block(p, h, cfg.ssm)
        return h + y, None

    def group(h, gp):
        h, _ = _scan(mamba_layer, h, gp)
        a, _ = attention_block(shared_attn, h, cfg, positions=positions)
        h = h + a
        h = h + mlp_block(shared_mlp, h, cfg)
        return h, None

    x, _ = _scan(_maybe_remat(group, remat), x,
                        params["mamba_groups"])
    if "mamba_tail" in params:
        x, _ = _scan(_maybe_remat(mamba_layer, remat), x,
                            params["mamba_tail"])
    return x


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross-entropy, vocab-sharding-friendly (one-hot einsum +
    logsumexp keep the vocab axis sharded end-to-end)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(tg, lg.shape[-1], dtype=lg.dtype)
    gold = jnp.einsum("bsv,bsv->bs", lg, onehot)
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------
class Cache(NamedTuple):
    """Union cache: attention K/V (stacked over layers/apps) and/or SSM
    states (stacked over layers)."""
    k: Optional[jnp.ndarray] = None          # (L, B, S, G, hd)
    v: Optional[jnp.ndarray] = None
    conv_x: Optional[jnp.ndarray] = None     # (L, B, K-1, d_inner)
    conv_bc: Optional[jnp.ndarray] = None    # (L, B, K-1, 2GN)
    ssm: Optional[jnp.ndarray] = None        # (L, B, H, P, N)
    pos: Optional[jnp.ndarray] = None        # scalar or (B,) int32: next
                                             # position of each row


def _n_attn_apps(cfg: ArchConfig) -> int:
    if cfg.family == HYBRID:
        return cfg.n_layers // cfg.hybrid_period
    if cfg.family == SSM:
        return 0
    return cfg.n_layers


def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> Cache:
    k = v = conv_x = conv_bc = ssm = None
    n_attn = _n_attn_apps(cfg)
    if n_attn:
        shape = (n_attn, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        k = jnp.zeros(shape, DTYPE)
        v = jnp.zeros(shape, DTYPE)
    if cfg.family in (SSM, HYBRID):
        proto = init_mamba2_cache(batch, cfg.d_model, cfg.ssm, DTYPE)
        n = cfg.n_layers
        conv_x = jnp.zeros((n,) + proto.conv_x.shape, proto.conv_x.dtype)
        conv_bc = jnp.zeros((n,) + proto.conv_bc.shape, proto.conv_bc.dtype)
        ssm = jnp.zeros((n,) + proto.ssm.shape, proto.ssm.dtype)
    return Cache(k=k, v=v, conv_x=conv_x, conv_bc=conv_bc, ssm=ssm,
                 pos=jnp.zeros((), jnp.int32))


def cache_logical_axes(cfg: ArchConfig) -> Cache:
    """Logical sharding for the cache (used by launch/dryrun)."""
    has_ssm = cfg.family in (SSM, HYBRID)
    has_attn = bool(_n_attn_apps(cfg))
    return Cache(
        k=(None, "dp", "kvseq", None, None) if has_attn else None,
        v=(None, "dp", "kvseq", None, None) if has_attn else None,
        conv_x=(None, "dp", None, "tp") if has_ssm else None,
        conv_bc=(None, "dp", None, None) if has_ssm else None,
        ssm=(None, "dp", "tp", None, None) if has_ssm else None,
        pos=(),
    )


# ---------------------------------------------------------------------------
# Decode step (one new token against the cache)
# ---------------------------------------------------------------------------
def decode_step(params, tokens, cache: Cache, cfg: ArchConfig, *,
                input_embeds: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Cache]:
    """tokens (B, 1) → (logits (B, 1, V), updated cache).

    ``cache.pos`` is a scalar (every row at one position) or a (B,)
    vector (each row at its own); the returned ``pos`` keeps its shape.
    """
    b = tokens.shape[0]
    pos = cache.pos
    positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (b, 1))
    x = embed_tokens(params, tokens, cfg, input_embeds)

    if cfg.family == DENSE:
        x, nk, nv = _dense_decode(params["layers"], x, cfg, positions,
                                  cache.k, cache.v, pos, local_flags(cfg))
        new = Cache(k=nk, v=nv, pos=pos + 1)
    elif cfg.family == MOE:
        nd = cfg.moe.first_dense
        nk, nv = cache.k, cache.v
        if nd:
            x, nk, nv = _dense_decode(params["dense_layers"], x, cfg,
                                      positions, nk, nv, pos,
                                      local_flags(cfg, nd))
        x, nk, nv = _moe_decode(params["moe_layers"], x, cfg, positions,
                                nk, nv, pos, first=nd)
        new = Cache(k=nk, v=nv, pos=pos + 1)
    elif cfg.family == SSM:
        def block(h, sc):
            p, cx, cbc, st = sc
            y, nc = mamba2_block(p, h, cfg.ssm,
                                 cache=Mamba2Cache(conv_x=cx, conv_bc=cbc,
                                                   ssm=st))
            return h + y, (nc.conv_x, nc.conv_bc, nc.ssm)
        x, (ncx, ncbc, nssm) = _scan(
            block, x, (params["layers"], cache.conv_x, cache.conv_bc,
                       cache.ssm))
        new = Cache(conv_x=ncx, conv_bc=ncbc, ssm=nssm, pos=pos + 1)
    elif cfg.family == HYBRID:
        x, new = _hybrid_decode(params, x, cfg, positions, cache)
    return lm_logits(params, x, cfg), new


def _dense_decode(layers, x, cfg, positions, ck, cv, pos, flags):
    return _attn_decode(layers["attn"], layers["mlp"],
                        lambda p, h: mlp_block(p, h, cfg), x, cfg, positions,
                        ck, cv, pos, flags)


def _moe_decode(layers, x, cfg, positions, ck, cv, pos, first=0):
    return _attn_decode(layers["attn"], layers["moe"],
                        lambda p, h: moe_ffn(p, h, cfg, cfg.moe), x, cfg,
                        positions, ck, cv, pos, first=first)


def _attn_decode(attn, ffn_params, ffn, x, cfg, positions, ck, cv, pos,
                 flags=None, first=0):
    """Attention + ``ffn`` layers over the stacked (L, B, S, G, hd) pool,
    the first at pool layer ``first``.  The pool rides in the scan CARRY
    and each layer writes its new K/V row into it in place
    (``attention_block(..., layer=li)``): no per-layer slice, copy or
    write-back, and while-loop carries are updated in place by XLA, so the
    multi-GB cache is not double-buffered (§Perf iteration 3)."""
    def block(carry, sc):
        h, ck, cv, li = carry
        pa, pf, fl = sc
        a, (ck, cv) = attention_block(pa, h, cfg, layer_is_local=fl,
                                      positions=positions, kv_cache=(ck, cv),
                                      cache_pos=pos, layer=li)
        h = h + a
        h = h + ffn(pf, h)
        return (h, ck, cv, li + 1), None

    (x, nk, nv, _), _ = _scan(
        block, (x, ck, cv, jnp.asarray(first, jnp.int32)),
        (attn, ffn_params, flags))
    return x, nk, nv


def _hybrid_decode(params, x, cfg, positions, cache: Cache):
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    pos = cache.pos
    shared_attn, shared_mlp = params["shared_attn"], params["shared_mlp"]

    def mamba_layer(h, sc):
        p, cx, cbc, st = sc
        y, nc = mamba2_block(p, h, cfg.ssm,
                             cache=Mamba2Cache(conv_x=cx, conv_bc=cbc,
                                               ssm=st))
        return h + y, (nc.conv_x, nc.conv_bc, nc.ssm)

    n_main = n_groups * period

    def grp_view(a):
        return a[:n_main].reshape((n_groups, period) + a.shape[1:])

    def group(h, sc):
        gp, gcx, gcbc, gssm, k_a, v_a = sc
        h, (ncx, ncbc, nssm) = _scan(mamba_layer, h,
                                            (gp, gcx, gcbc, gssm))
        a, (nk, nv) = attention_block(shared_attn, h, cfg,
                                      positions=positions,
                                      kv_cache=(k_a, v_a), cache_pos=pos)
        h = h + a
        h = h + mlp_block(shared_mlp, h, cfg)
        return h, (ncx, ncbc, nssm, nk, nv)

    x, (ncx, ncbc, nssm, nk, nv) = _scan(
        group, x, (params["mamba_groups"], grp_view(cache.conv_x),
                   grp_view(cache.conv_bc), grp_view(cache.ssm),
                   cache.k, cache.v))
    ncx = ncx.reshape((n_main,) + ncx.shape[2:])
    ncbc = ncbc.reshape((n_main,) + ncbc.shape[2:])
    nssm = nssm.reshape((n_main,) + nssm.shape[2:])
    if "mamba_tail" in params:
        x, (tcx, tcbc, tssm) = _scan(
            mamba_layer, x,
            (params["mamba_tail"], cache.conv_x[n_main:],
             cache.conv_bc[n_main:], cache.ssm[n_main:]))
        ncx = jnp.concatenate([ncx, tcx])
        ncbc = jnp.concatenate([ncbc, tcbc])
        nssm = jnp.concatenate([nssm, tssm])
    return x, Cache(k=nk, v=nv, conv_x=ncx, conv_bc=ncbc, ssm=nssm,
                    pos=pos + 1)


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also fills the cache
# ---------------------------------------------------------------------------
def prefill(params, tokens, cfg: ArchConfig, *,
            positions: Optional[jnp.ndarray] = None,
            input_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Cache]:
    """Returns (last-token logits (B, V), cache filled to S)."""
    b, s = tokens.shape
    x = embed_tokens(params, tokens, cfg, input_embeds)

    if cfg.family == DENSE:
        ck, cv = _proto_kv(cfg, cfg.n_layers, b, s)
        x, nk, nv = _dense_prefill(params["layers"], x, cfg, positions,
                                   ck, cv, local_flags(cfg))
        cache = Cache(k=nk, v=nv, pos=jnp.asarray(s, jnp.int32))
    elif cfg.family == MOE:
        nd = cfg.moe.first_dense
        ks, vs = [], []
        if nd:
            ck, cv = _proto_kv(cfg, nd, b, s)
            x, nk, nv = _dense_prefill(params["dense_layers"], x, cfg,
                                       positions, ck, cv,
                                       local_flags(cfg, nd))
            ks.append(nk)
            vs.append(nv)
        ck, cv = _proto_kv(cfg, cfg.n_layers - nd, b, s)
        x, nk, nv = _moe_prefill(params["moe_layers"], x, cfg, positions,
                                 ck, cv)
        ks.append(nk)
        vs.append(nv)
        cache = Cache(k=jnp.concatenate(ks), v=jnp.concatenate(vs),
                      pos=jnp.asarray(s, jnp.int32))
    elif cfg.family == SSM:
        def block(h, sc):
            p, cx, cbc, st = sc
            y, nc = mamba2_block(p, h, cfg.ssm,
                                 cache=Mamba2Cache(conv_x=cx, conv_bc=cbc,
                                                   ssm=st))
            return h + y, (nc.conv_x, nc.conv_bc, nc.ssm)
        init = init_cache(cfg, b, 0)
        x, (ncx, ncbc, nssm) = _scan(
            block, x, (params["layers"], init.conv_x, init.conv_bc,
                       init.ssm))
        cache = Cache(conv_x=ncx, conv_bc=ncbc, ssm=nssm,
                      pos=jnp.asarray(s, jnp.int32))
    elif cfg.family == HYBRID:
        x, cache = _hybrid_prefill(params, x, cfg, positions, b, s)
    logits = lm_logits(params, x[:, -1:], cfg)[:, 0]
    return logits, cache


def _proto_kv(cfg, n, b, s):
    shape = (n, b, s, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, DTYPE), jnp.zeros(shape, DTYPE)


def _dense_prefill(layers, x, cfg, positions, ck, cv, flags):
    zero = jnp.zeros((), jnp.int32)

    def block(h, sc):
        pa, pm, fl, k_l, v_l = sc
        a, (nk, nv) = attention_block(pa, h, cfg, layer_is_local=fl,
                                      positions=positions,
                                      kv_cache=(k_l, v_l), cache_pos=zero)
        h = h + a
        h = h + mlp_block(pm, h, cfg)
        return h, (nk, nv)

    x, (nk, nv) = _scan(
        block, x, (layers["attn"], layers["mlp"], flags, ck, cv))
    return x, nk, nv


def _moe_prefill(layers, x, cfg, positions, ck, cv):
    zero = jnp.zeros((), jnp.int32)

    def block(h, sc):
        pa, pm, k_l, v_l = sc
        a, (nk, nv) = attention_block(pa, h, cfg, positions=positions,
                                      kv_cache=(k_l, v_l), cache_pos=zero)
        h = h + a
        h = h + moe_ffn(pm, h, cfg, cfg.moe)
        return h, (nk, nv)

    x, (nk, nv) = _scan(
        block, x, (layers["attn"], layers["moe"], ck, cv))
    return x, nk, nv


def _hybrid_prefill(params, x, cfg, positions, b, s):
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    zero = jnp.zeros((), jnp.int32)
    shared_attn, shared_mlp = params["shared_attn"], params["shared_mlp"]
    proto = init_mamba2_cache(b, cfg.d_model, cfg.ssm, DTYPE)

    def mamba_layer(h, p):
        y, nc = mamba2_block(p, h, cfg.ssm,
                             cache=Mamba2Cache(conv_x=proto.conv_x,
                                               conv_bc=proto.conv_bc,
                                               ssm=proto.ssm))
        return h + y, (nc.conv_x, nc.conv_bc, nc.ssm)

    ck, cv = _proto_kv(cfg, n_groups, b, s)

    def group(h, sc):
        gp, k_a, v_a = sc
        h, (ncx, ncbc, nssm) = _scan(mamba_layer, h, gp)
        a, (nk, nv) = attention_block(shared_attn, h, cfg,
                                      positions=positions,
                                      kv_cache=(k_a, v_a), cache_pos=zero)
        h = h + a
        h = h + mlp_block(shared_mlp, h, cfg)
        return h, (ncx, ncbc, nssm, nk, nv)

    x, (ncx, ncbc, nssm, nk, nv) = _scan(
        group, x, (params["mamba_groups"], ck, cv))
    n_main = n_groups * period
    ncx = ncx.reshape((n_main,) + ncx.shape[2:])
    ncbc = ncbc.reshape((n_main,) + ncbc.shape[2:])
    nssm = nssm.reshape((n_main,) + nssm.shape[2:])
    if "mamba_tail" in params:
        x, (tcx, tcbc, tssm) = _scan(mamba_layer, x,
                                            params["mamba_tail"])
        ncx = jnp.concatenate([ncx, tcx])
        ncbc = jnp.concatenate([ncbc, tcbc])
        nssm = jnp.concatenate([nssm, tssm])
    return x, Cache(k=nk, v=nv, conv_x=ncx, conv_bc=ncbc, ssm=nssm,
                    pos=jnp.asarray(s, jnp.int32))
