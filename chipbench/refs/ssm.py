"""Mamba-2 language model (state-space duality, arXiv:2405.21060), written
from the published description in float32, with the recurrence run one
token at a time:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

per head, after a causal depthwise convolution of x, B and C, and followed
by the gated RMS norm ``norm(y * silu(z))`` and the output projection.

The reference runs once the program's state is freed, layer by layer, on
all compared sequences at once (right-padded to one length: padding after
a sequence cannot change its earlier outputs).  Each layer's weights are
made again from the seed inside the layer's call.

``fp8=True`` rounds both operands of every matrix product to float8 (e4m3,
one scale per tensor) before a float32 product: the control, one precision
below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any
from typing import Dict
from typing import List

from chipbench.weights import Leaf
from chipbench.weights import index
from chipbench.weights import leaf_values
import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16
F32 = jnp.float32
PAD_T = 256             # sequences padded to a multiple of this
PAD_B = 8               # and the batch to a multiple of this
ROW_PAD = 256           # compared rows padded to a multiple of this


def _dims(c: Dict[str, Any]) -> Dict[str, Any]:
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    pad = c["pad_vocab_size_multiple"]
    return dict(d=d, di=di, n=s["d_state"], k=s["d_conv"], p=s["headdim"],
                g=s["ngroups"], h=di // s["headdim"], chunk=s["chunk_size"],
                layers=c["n_layer"], v=-(-c["vocab_size"] // pad) * pad,
                eps=c["norm_epsilon"], tied=bool(c["tie_embeddings"]))


def arch_fields(c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ArchConfig`` fields for configuration ``c``; a
    nested group is given as a dict of its fields."""
    m = _dims(c)
    return dict(n_layers=m["layers"], d_model=m["d"], vocab=m["v"],
                tie_embeddings=m["tied"],
                ssm=dict(d_state=m["n"], d_conv=m["k"],
                         expand=c["ssm_cfg"]["expand"], head_dim=m["p"],
                         chunk=m["chunk"], n_groups=m["g"]))


def n_layers(c: Dict[str, Any]) -> int:
    return c["n_layer"]


def vocab(c: Dict[str, Any]) -> int:
    """Token ids are drawn below the tokenizer's size, not the padding."""
    return c["vocab_size"]


def leaves(c: Dict[str, Any]) -> List[Leaf]:
    m = _dims(c)
    d, di, h, bc = m["d"], m["di"], m["h"], 2 * m["g"] * m["n"]
    k = m["k"]
    return [
        Leaf("embed", (m["v"], d), BF16, ("std", d ** -0.5), False),
        Leaf("ln_f", (d,), BF16, ("one_pm", 0.2), False),
        Leaf("layers/ln", (d,), BF16, ("one_pm", 0.2)),
        Leaf("layers/w_z", (d, di), BF16, ("std", d ** -0.5)),
        Leaf("layers/w_x", (d, di), BF16, ("std", d ** -0.5)),
        Leaf("layers/w_bc", (d, bc), BF16, ("std", d ** -0.5)),
        Leaf("layers/w_dt", (d, h), BF16, ("std", 0.5 * d ** -0.5)),
        Leaf("layers/conv_x_w", (k, di), BF16, ("std", 0.3)),
        Leaf("layers/conv_x_b", (di,), BF16, ("std", 0.1)),
        Leaf("layers/conv_bc_w", (k, bc), BF16, ("std", 0.3)),
        Leaf("layers/conv_bc_b", (bc,), BF16, ("std", 0.1)),
        Leaf("layers/dt_bias", (h,), BF16,
             ("softplus_inv_log_uniform", 1e-3, 1e-1)),
        Leaf("layers/a_log", (h,), F32, ("uniform", 0.0, math.log(16.0))),
        Leaf("layers/d_skip", (h,), F32, ("one_pm", 0.2)),
        Leaf("layers/out_ln", (di,), BF16, ("one_pm", 0.2)),
        Leaf("layers/w_out", (di, d), BF16, ("std", di ** -0.5)),
    ]


def finish(tree: Dict) -> Dict:
    """The published model ties its head to the embedding; the program
    keeps the head as a parameter of its own, given the same values."""
    tree["lm_head"] = tree["embed"].T
    return tree


# ---------------------------------------------------------------------------
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w, b):
    """Causal depthwise conv over time, then SiLU.  x (B, T, C), w (K, C)."""
    k = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(w[j] * xp[:, j:j + t] for j in range(k))
    return jax.nn.silu(out + b)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(cfg, key, layer, x, fp8):
    c = _cfg(cfg)
    m = _dims(c)
    lv = leaves(c)
    ix = index(lv)

    def w(name):
        return leaf_values(key, lv, ix["layers/" + name], layer).astype(F32)

    b, t, _ = x.shape
    h, p, n, g = m["h"], m["p"], m["n"], m["g"]
    res = _rms(x, w("ln"), m["eps"])
    z = _mm("btd,de->bte", res, w("w_z"), fp8)
    xr = _mm("btd,de->bte", res, w("w_x"), fp8)
    bc = _mm("btd,de->bte", res, w("w_bc"), fp8)
    dt = jax.nn.softplus(_mm("btd,dh->bth", res, w("w_dt"), fp8)
                         + w("dt_bias"))
    xs = _conv(xr, w("conv_x_w"), w("conv_x_b")).reshape(b, t, h, p)
    cbc = _conv(bc, w("conv_bc_w"), w("conv_bc_b"))
    rep = h // g
    bm = jnp.repeat(cbc[..., :g * n].reshape(b, t, g, n), rep, axis=2)
    cm = jnp.repeat(cbc[..., g * n:].reshape(b, t, g, n), rep, axis=2)
    a = -jnp.exp(w("a_log"))

    def tick(state, inp):
        x_t, dt_t, b_t, c_t = inp            # (b,h,p) (b,h) (b,h,n) (b,h,n)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI)

    init = jnp.zeros((b, h, p, n), F32)
    _, y = jax.lax.scan(tick, init, (xs.transpose(1, 0, 2, 3),
                                     dt.transpose(1, 0, 2),
                                     bm.transpose(1, 0, 2, 3),
                                     cm.transpose(1, 0, 2, 3)))
    y = y.transpose(1, 0, 2, 3) + w("d_skip")[:, None] * xs
    y = _rms(y.reshape(b, t, h * p) * jax.nn.silu(z), w("out_ln"), m["eps"])
    return x + _mm("bte,ed->btd", y, w("w_out"), fp8)


def _static(c: Dict[str, Any]):
    return (tuple((k, v) for k, v in sorted(c.items())
                  if not isinstance(v, (dict, list))),
            tuple(sorted(c["ssm_cfg"].items())))


def _cfg(static) -> Dict[str, Any]:
    top, ssm = static
    c = dict(top)
    c["ssm_cfg"] = dict(ssm)
    return c


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg, key, tokens):
    lv = leaves(_cfg(cfg))
    return leaf_values(key, lv, index(lv)["embed"], 0)[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(cfg, key, rows, fp8):
    c = _cfg(cfg)
    lv = leaves(c)
    ix = index(lv)
    ln = leaf_values(key, lv, ix["ln_f"], 0).astype(F32)
    emb = leaf_values(key, lv, ix["embed"], 0).astype(F32)
    return _mm("td,vd->tv", _rms(rows, ln, _dims(c)["eps"]), emb, fp8)


def logits(c: Dict[str, Any], key, seqs: List[np.ndarray],
           rows: List[np.ndarray], fp8: bool = False) -> List[jax.Array]:
    """Logits (len(rows[i]), vocab) at positions ``rows[i]`` of sequence
    ``seqs[i]``: the prediction of the token after each such position."""
    cfg = _static(c)
    t = -(-max(len(s) for s in seqs) // PAD_T) * PAD_T
    b = -(-len(seqs) // PAD_B) * PAD_B
    tokens = np.zeros((b, t), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = _embed(cfg, key, jnp.asarray(tokens))
    for layer in range(n_layers(c)):
        x = _layer(cfg, key, layer, x, fp8)
    bi = np.concatenate([np.full(len(r), i) for i, r in enumerate(rows)])
    ti = np.concatenate(rows)
    n = len(ti)
    pad = -(-n // ROW_PAD) * ROW_PAD - n
    bi, ti = np.pad(bi, (0, pad)), np.pad(ti, (0, pad))
    out = _head(cfg, key, x[jnp.asarray(bi), jnp.asarray(ti)], fp8)[:n]
    return jnp.split(out, np.cumsum([len(r) for r in rows])[:-1])
