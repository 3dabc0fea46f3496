"""Prefill-program device time per 1000 prompt tokens (device trace)."""

from chipbench.readers import prefill_calls


def read(ctx):
    pairs = prefill_calls(ctx)
    if not pairs:
        return None
    ns = sum(c.program.dur for c, _ in pairs)
    return ns * 1e-6 / sum(n for _, n in pairs) * 1e3
