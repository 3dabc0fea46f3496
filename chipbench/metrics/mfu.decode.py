"""Decode's share of the chip's peak FLOP/s: the operations decode
requires (work/<family>.py) over the wall time of the decode phase of the
engine steps (from the step's first decode call to its end, host clock),
over the peak (peaks.py)."""

from chipbench.readers import window_steps


def read(ctx):
    steps = [s for s in window_steps(ctx)
             if s.decode_ctx and s.first_decode_at is not None]
    if not steps:
        return None
    flops = sum(ctx.work.decode(ctx.config, s.decode_ctx)[0] for s in steps)
    wall = sum(s.end - s.first_decode_at for s in steps)
    return 100.0 * flops / wall / ctx.peak.bf16_flops
