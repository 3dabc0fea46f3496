"""Required work of the dense GQA decoder: operations and bytes that any
implementation of a call must spend, from the configuration's shapes.
Weights are bfloat16 (2 bytes), as are keys and values.  The head runs on
the last prompt token only in prefill, and on every token in decode."""

from __future__ import annotations

from typing import Any
from typing import Dict
from typing import Sequence
from typing import Tuple

BYTES = 2


def _dims(c: Dict[str, Any]):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"])


def layer_matmul_params(c: Dict[str, Any]) -> int:
    _, d, h, g, e, f, _ = _dims(c)
    return d * h * e * 2 + d * g * e * 2 + 3 * d * f


def decode(c: Dict[str, Any], ctx: Sequence[int]) -> Tuple[float, float]:
    """One token for each sequence; ``ctx[i]`` keys attended by sequence i
    (its earlier tokens and the new one)."""
    n_l, d, h, g, e, _, v = _dims(c)
    n = len(ctx)
    w = n_l * layer_matmul_params(c) + d * v
    kv_token = n_l * 2 * g * e * BYTES
    flops = n * 2 * w + sum(4 * k * h * e * n_l for k in ctx)
    data = (w * BYTES + n * d * BYTES              # weights, embedding rows
            + sum(ctx) * kv_token                  # keys and values read
            + n * kv_token)                        # the new ones written
    return float(flops), float(data)


def prefill(c: Dict[str, Any], t: int) -> Tuple[float, float]:
    """A prompt of ``t`` tokens: causal attention, keys and values written,
    logits for the last token."""
    n_l, d, h, g, e, _, v = _dims(c)
    w = n_l * layer_matmul_params(c) + d * v
    flops = (2 * n_l * layer_matmul_params(c) * t + 2 * d * v
             + n_l * 4 * h * e * t * (t + 1) / 2)
    data = (w * BYTES + t * d * BYTES + t * n_l * 2 * g * e * BYTES)
    return float(flops), float(data)
