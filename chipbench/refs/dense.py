"""Dense decoder with grouped-query attention, RoPE and SwiGLU (the Mistral
family), written from the published description in float32.

The reference runs once the program's state is freed, layer by layer, on
all compared sequences at once: they are laid end to end in one token
stream, and attention is masked to each sequence's own earlier tokens.
Each layer's weights are made again from the seed (``weights``) inside the
layer's call, so no more than one layer is held at a time.

``fp8=True`` rounds both operands of every matrix product to float8 (e4m3,
one scale per tensor) before a float32 product: the control, one precision
below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools
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
Q_BLOCK = 512           # query rows per attention block
PAD_TO = 2048           # token stream padded to a multiple of this
ROW_PAD = 256           # compared rows padded to a multiple of this


def arch_fields(c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ArchConfig`` fields for configuration ``c``."""
    return dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]),
                tie_embeddings=bool(c["tie_word_embeddings"]))


def n_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"]


def vocab(c: Dict[str, Any]) -> int:
    return c["vocab_size"]


def leaves(c: Dict[str, Any]) -> List[Leaf]:
    d, h, g = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    e, f, v = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    return [
        # unit-variance rows: at the program's own d**-0.5 scale, bf16
        # rounding of the residual stream erases a token's embedding
        # under the first layer's output (PERF.md, PR 12 findings)
        Leaf("embed", (v, d), BF16, ("std", 1.0), False),
        Leaf("ln_f", (d,), BF16, ("one_pm", 0.2), False),
        Leaf("lm_head", (d, v), BF16, ("std", d ** -0.5), False),
        Leaf("layers/attn/ln", (d,), BF16, ("one_pm", 0.2)),
        Leaf("layers/attn/wq", (d, h, e), BF16, ("std", d ** -0.5)),
        Leaf("layers/attn/wk", (d, g, e), BF16, ("std", d ** -0.5)),
        Leaf("layers/attn/wv", (d, g, e), BF16, ("std", d ** -0.5)),
        Leaf("layers/attn/wo", (h, e, d), BF16, ("std", (h * e) ** -0.5)),
        Leaf("layers/mlp/ln", (d,), BF16, ("one_pm", 0.2)),
        Leaf("layers/mlp/w_gate", (d, f), BF16, ("std", d ** -0.5)),
        Leaf("layers/mlp/w_up", (d, f), BF16, ("std", d ** -0.5)),
        Leaf("layers/mlp/w_down", (f, d), BF16, ("std", f ** -0.5)),
    ]


def finish(tree: Dict) -> Dict:
    return tree


# ---------------------------------------------------------------------------
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (T, H, e), pos (T,)."""
    e = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = pos.astype(jnp.float32)[:, None] * inv               # (T, e/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    x1, x2 = x[..., : e // 2], x[..., e // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, pos, seg, fp8):
    t, h, e = q.shape
    g = k.shape[1]
    r = h // g
    scale = e ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        pq = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK)
        sq = jax.lax.dynamic_slice_in_dim(seg, i * Q_BLOCK, Q_BLOCK)
        s = _mm("qgrd,kgd->grqk", qb.reshape(Q_BLOCK, g, r, e), k,
                fp8) * scale
        ok = (seg[None, :] == sq[:, None]) & (pos[None, :] <= pq[:, None])
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return _mm("grqk,kgd->qgrd", p, v, fp8).reshape(Q_BLOCK, h, e)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, h, e)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(cfg, key, layer, x, pos_seg, fp8):
    c = dict(cfg)
    lv = leaves(c)
    ix = index(lv)

    def w(path):
        return leaf_values(key, lv, ix[path], layer).astype(jnp.float32)

    pos, seg = pos_seg
    eps = c["rms_norm_eps"]
    hN = _rms(x, w("layers/attn/ln"), eps)
    q = _rope(_mm("td,dhe->the", hN, w("layers/attn/wq"), fp8), pos,
              c["rope_theta"])
    k = _rope(_mm("td,dge->tge", hN, w("layers/attn/wk"), fp8), pos,
              c["rope_theta"])
    v = _mm("td,dge->tge", hN, w("layers/attn/wv"), fp8)
    a = _attention(q, k, v, pos, seg, fp8)
    x = x + _mm("the,hed->td", a, w("layers/attn/wo"), fp8)
    hN = _rms(x, w("layers/mlp/ln"), eps)
    gate = _mm("td,df->tf", hN, w("layers/mlp/w_gate"), fp8)
    up = _mm("td,df->tf", hN, w("layers/mlp/w_up"), fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up,
                   w("layers/mlp/w_down"), fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg, key, tokens):
    lv = leaves(dict(cfg))
    return leaf_values(key, lv, index(lv)["embed"], 0)[tokens].astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(cfg, key, rows, fp8):
    c = dict(cfg)
    lv = leaves(c)
    ix = index(lv)
    ln = leaf_values(key, lv, ix["ln_f"], 0).astype(jnp.float32)
    head = leaf_values(key, lv, ix["lm_head"], 0).astype(jnp.float32)
    return _mm("td,dv->tv", _rms(rows, ln, c["rms_norm_eps"]), head, fp8)


def logits(c: Dict[str, Any], key, seqs: List[np.ndarray],
           rows: List[np.ndarray], fp8: bool = False) -> List[jax.Array]:
    """Logits (len(rows[i]), vocab) at positions ``rows[i]`` of sequence
    ``seqs[i]``: the prediction of the token after each such position."""
    cfg = tuple((k, v) for k, v in sorted(c.items())
                if not isinstance(v, (dict, list)))
    total = sum(len(s) for s in seqs)
    t = -(-total // PAD_TO) * PAD_TO
    tokens = np.zeros(t, np.int32)
    pos = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    starts, o = [], 0
    for i, s in enumerate(seqs):
        tokens[o:o + len(s)] = s
        pos[o:o + len(s)] = np.arange(len(s))
        seg[o:o + len(s)] = i
        starts.append(o)
        o += len(s)
    x = _embed(cfg, key, jnp.asarray(tokens))
    ps = (jnp.asarray(pos), jnp.asarray(seg))
    for layer in range(n_layers(c)):
        x = _layer(cfg, key, layer, x, ps, fp8)
    flat = np.concatenate([st + r for st, r in zip(starts, rows)])
    n = len(flat)
    flat = np.pad(flat, (0, -(-n // ROW_PAD) * ROW_PAD - n))
    out = _head(cfg, key, x[jnp.asarray(flat)], fp8)[:n]
    return jnp.split(out, np.cumsum([len(r) for r in rows])[:-1])
