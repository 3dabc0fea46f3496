"""Joins the driver's log with the reduced trace, for the per-layer
readers under ``metrics/``.

The driver's steps and the trace's step spans are the same steps in the
same order, as are the prefills it logged and the prefill calls in the
trace (the engine prefills queued requests first come, first served).
"""

from __future__ import annotations

from typing import List
from typing import Tuple


def window_steps(ctx) -> List:
    return [s for s in ctx.log.steps if s.start < ctx.log.t_end]


def prefill_calls(ctx) -> List[Tuple[object, int]]:
    """(trace call, prompt length) for every prefill in the window."""
    calls = [c for c in ctx.red.calls
             if c.kind == "prefill" and c.program is not None]
    done = sorted((s for s in ctx.log.served if s.prefill_at is not None
                   and s.prefill_at <= ctx.log.t_end),
                  key=lambda s: s.prefill_at)
    return [(c, s.prompt_len) for c, s in zip(calls, done)]


def decode_steps(ctx) -> List[Tuple[object, float]]:
    """(logged step, decode-program device ns) for every step of the
    window that decoded."""
    dev = {}
    for c in ctx.red.calls:
        if c.kind == "decode" and c.program is not None and c.step >= 0:
            dev[c.step] = dev.get(c.step, 0.0) + c.program.dur
    steps = window_steps(ctx)[:len(ctx.red.steps)]
    return [(s, dev[i]) for i, s in enumerate(steps)
            if s.decode_ctx and i in dev]


def bound_s(ctx, flops: float, data: float) -> float:
    """Least time the chip could take: operations at peak or bytes at the
    memory's bandwidth, whichever is longer."""
    return max(flops / ctx.peak.bf16_flops, data / ctx.peak.hbm_bytes_s)
