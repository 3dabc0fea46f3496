"""Prefill's share of the chip's peak FLOP/s: the operations prefill
requires (work/<family>.py) over the wall time of the admit phase of the
engine steps (from the step's start to its first decode call, host clock),
over the peak (peaks.py)."""

from chipbench.readers import window_steps


def read(ctx):
    steps = [s for s in window_steps(ctx) if s.prefill_lens]
    if not steps:
        return None
    flops = sum(ctx.work.prefill(ctx.config, n)[0]
                for s in steps for n in s.prefill_lens)
    wall = sum((s.first_decode_at or s.end) - s.start for s in steps)
    return 100.0 * flops / wall / ctx.peak.bf16_flops
