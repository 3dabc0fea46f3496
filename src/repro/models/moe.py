"""Fine-grained Mixture-of-Experts FFN (DeepSeekMoE-style).

Shared experts (always active) + routed experts with top-k gating and
capacity-based token dropping.  Distribution: expert parallelism over the
``model`` mesh axis via ``shard_map`` — tokens stay on their data shard
(no cross-data traffic); every model shard routes the *same* local tokens
to *its* slice of experts and a single ``psum`` over ``model`` combines
routed and shared-expert partial outputs.  This is the EP pattern whose
collective cost equals one TP all-reduce, chosen over dispatch all-to-all
because the paper-assigned MoE configs (64 experts, top-6) are
fine-grained: every token activates ~6/64 experts, so expert-local gather
+ psum moves strictly less data than a full token exchange.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import current_mesh
from repro.sharding import logical_spec

from .layers import rms_norm


def _route(xt: jnp.ndarray, w_gate: jnp.ndarray, top_k: int):
    """Top-k routing with renormalized weights. xt (T, D) → (w, idx)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        w_gate.astype(jnp.float32))
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx


def _routed_experts(xt, w, idx, w1, w3, w2, e0: int, capacity: int,
                    act):
    """Compute routed-expert outputs for local experts [e0, e0+E_loc).

    xt (T, D); w/idx (T, k); expert weights (E_loc, D, F)/(E_loc, F, D).
    Returns (T, D) partial output covering only local experts.
    """
    T = xt.shape[0]
    e_loc = w1.shape[0]
    eids = e0 + jnp.arange(e_loc)
    onehot = idx[None, :, :] == eids[:, None, None]          # (E,T,k)
    w_e = jnp.einsum("etk,tk->et", onehot.astype(w.dtype), w)  # (E,T)
    selected = w_e > 0
    # first-come-first-served capacity: earlier tokens win slots
    prio = jnp.where(selected, (T - jnp.arange(T))[None, :].astype(
        jnp.float32), -jnp.inf)
    cap = min(capacity, T)
    top_prio, tok_ids = jax.lax.top_k(prio, cap)              # (E, C)
    valid = jnp.isfinite(top_prio)
    tok_ids = jnp.where(valid, tok_ids, 0)
    gw = jnp.take_along_axis(w_e, tok_ids, axis=1) * valid    # (E, C)

    xg = xt[tok_ids]                                          # (E, C, D)
    h = act(jnp.einsum("ecd,edf->ecf", xg, w1)) \
        * jnp.einsum("ecd,edf->ecf", xg, w3)
    y = jnp.einsum("ecf,efd->ecd", h, w2)                     # (E, C, D)
    y = y * gw[..., None].astype(y.dtype)
    out = jnp.zeros_like(xt)
    out = out.at[tok_ids.reshape(-1)].add(y.reshape(-1, xt.shape[1]))
    return out


def _shared_experts(xt, p, act):
    h = act(jnp.einsum("td,df->tf", xt, p["sh_gate"])) \
        * jnp.einsum("td,df->tf", xt, p["sh_up"])
    return jnp.einsum("tf,fd->td", h, p["sh_down"])


def _moe_shard(x, p, *, spec, act, axis: Optional[str]):
    """Per-shard body (also the single-device path with axis=None)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    w, idx = _route(xt, p["w_gate"], spec.top_k)
    e_loc = p["w1"].shape[0]
    e0 = jax.lax.axis_index(axis) * e_loc if axis else 0
    capacity = max(int(spec.capacity_factor * xt.shape[0] * spec.top_k
                       / spec.n_experts), 4)
    out = _routed_experts(xt, w, idx, p["w1"], p["w3"], p["w2"], e0,
                          capacity, act)
    if spec.n_shared:
        out = out + _shared_experts(xt, p, act)
    if axis:
        out = jax.lax.psum(out, axis)
    return out.reshape(b, s, d).astype(x.dtype)


def moe_ffn(params, x, cfg, spec):
    """MoE FFN block (includes its pre-norm).  x (B, S, D)."""
    act = (partial(jax.nn.gelu, approximate=True) if cfg.act == "gelu"
           else jax.nn.silu)
    h = rms_norm(x, params["ln"], plus_one=cfg.gemma_norm)
    mesh = current_mesh()
    body = {k: v for k, v in params.items() if k != "ln"}
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        dp_spec = logical_spec(("dp", None, None), mesh, shape=h.shape)
        pspecs = {
            "w_gate": P(), "w1": P("model"), "w3": P("model"),
            "w2": P("model"),
            "sh_gate": P(None, "model"), "sh_up": P(None, "model"),
            "sh_down": P("model", None),
        }
        in_specs = (dp_spec, {k: pspecs[k] for k in body})
        fn = jax.shard_map(
            partial(_moe_shard, spec=spec, act=act, axis="model"),
            mesh=mesh, in_specs=in_specs, out_specs=dp_spec,
            check_vma=False)
        return fn(h, body)
    return _moe_shard(h, body, spec=spec, act=act, axis=None)


def init_moe_params(key, d_model: int, spec, dtype=jnp.bfloat16):
    e, f = spec.n_experts, spec.d_ff_expert
    fs = spec.n_shared * spec.d_ff_expert
    ks = jax.random.split(key, 7)
    s_in = d_model ** -0.5
    s_out = f ** -0.5
    p = {
        "ln": jnp.ones((d_model,), dtype),
        "w_gate": (jax.random.normal(ks[0], (d_model, e)) * s_in
                   ).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d_model, f)) * s_in
               ).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d_model, f)) * s_in
               ).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, f, d_model)) * s_out
               ).astype(dtype),
    }
    if spec.n_shared:
        p["sh_gate"] = (jax.random.normal(ks[4], (d_model, fs)) * s_in
                        ).astype(dtype)
        p["sh_up"] = (jax.random.normal(ks[5], (d_model, fs)) * s_in
                      ).astype(dtype)
        p["sh_down"] = (jax.random.normal(ks[6], (fs, d_model))
                        * fs ** -0.5).astype(dtype)
    return p
