"""Required work of the Mamba-2 language model: operations and bytes that
any implementation of a call must spend, from the configuration's shapes.
Weights and convolution states are bfloat16, the SSM state float32.  The
scan's required work is that of the token-by-token recurrence (about five
operations per state element per token); the head runs on the last prompt
token only in prefill, and on every token in decode."""

from __future__ import annotations

from typing import Any
from typing import Dict
from typing import Sequence
from typing import Tuple

BYTES = 2
STATE_BYTES = 4


def _dims(c: Dict[str, Any]):
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    bc = 2 * s["ngroups"] * s["d_state"]
    h = di // s["headdim"]
    pad = c["pad_vocab_size_multiple"]
    v = -(-c["vocab_size"] // pad) * pad
    return c["n_layer"], d, di, bc, h, s["headdim"], s["d_state"], \
        s["d_conv"], v


def layer_matmul_params(c: Dict[str, Any]) -> int:
    _, d, di, bc, h, _, _, _, _ = _dims(c)
    return d * (2 * di + bc + h) + di * d


def _state_elems(c):
    n_l, _, _, _, h, p, n, _, _ = _dims(c)
    return n_l * h * p * n


def decode(c: Dict[str, Any], ctx: Sequence[int]) -> Tuple[float, float]:
    """One token for each of ``len(ctx)`` sequences (the state's size does
    not depend on the context)."""
    n_l, d, di, bc, _, _, _, k, v = _dims(c)
    b = len(ctx)
    w = n_l * layer_matmul_params(c) + d * v
    flops = b * (2 * w + 5 * _state_elems(c) + n_l * 2 * k * (di + bc))
    conv_state = n_l * (k - 1) * (di + bc) * BYTES
    data = (w * BYTES + b * d * BYTES
            + b * 2 * (_state_elems(c) * STATE_BYTES + conv_state))
    return float(flops), float(data)


def prefill(c: Dict[str, Any], t: int) -> Tuple[float, float]:
    n_l, d, di, bc, _, _, _, k, v = _dims(c)
    w = n_l * layer_matmul_params(c) + d * v
    flops = (2 * n_l * layer_matmul_params(c) * t + 2 * d * v
             + t * (5 * _state_elems(c) + n_l * 2 * k * (di + bc)))
    conv_state = n_l * (k - 1) * (di + bc) * BYTES
    data = (w * BYTES + t * d * BYTES
            + _state_elems(c) * STATE_BYTES + conv_state)
    return float(flops), float(data)
