"""Share of its roofline that prefill reached: the least time the chip
could take for causal prefill at each prompt's length (work/<family>.py,
peaks.py), over the prefill program's device time (device trace), summed
over the window's prefills."""

from chipbench.readers import bound_s
from chipbench.readers import prefill_calls


def read(ctx):
    pairs = prefill_calls(ctx)
    if not pairs:
        return None
    least = sum(bound_s(ctx, *ctx.work.prefill(ctx.config, n))
                for _, n in pairs)
    return 100.0 * least / (sum(c.program.dur for c, _ in pairs) * 1e-9)
