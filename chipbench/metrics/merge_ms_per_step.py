"""Device time of the engine's cache merges (``serve_merge_slots``
programs, found by name in the device trace) per window step that
decoded, in ms."""

from chipbench.pspans import by_step
from chipbench.pspans import decoding_steps


def read(ctx):
    steps = decoding_steps(ctx.red)
    merges = by_step(ctx.red, "serve_merge_slots")
    if not steps or not any(merges):
        return None
    return sum(m.dur for j in steps for m in merges[j]) * 1e-6 / len(steps)
