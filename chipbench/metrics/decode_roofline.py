"""Share of its roofline that decode reached: per engine step, the least
time the chip could take to decode one token for each active sequence
(the weights read once, the live keys and values or states, the writes;
work/<family>.py, peaks.py), over the device time of that step's decode
programs (device trace), summed over the window's steps."""

from chipbench.readers import bound_s
from chipbench.readers import decode_steps


def read(ctx):
    steps = decode_steps(ctx)
    if not steps:
        return None
    least = sum(bound_s(ctx, *ctx.work.decode(ctx.config, s.decode_ctx))
                for s, _ in steps)
    return 100.0 * least / (sum(ns for _, ns in steps) * 1e-9)
