"""Decode groups per window step that decoded (the ``groups`` attribute
of the engine's ``serve.step`` spans): one decode call per position
group.  The engine's own count of what ``decode_calls_per_step`` counts
with a wrapper around its private attribute.  A run whose engine has no
tracer reports nothing."""

from chipbench.pspans import decoding_step_spans


def read(ctx):
    steps = decoding_step_spans(ctx)
    if not steps:
        return None
    return sum(s.attrs["groups"] for s in steps) / len(steps)
