"""Device values the engine read back to the host per window step that
decoded (the ``host_reads`` attribute of its ``serve.step`` spans): the
first token of each request admitted and the token of each slot decoded.
A run whose engine has no tracer reports nothing."""

from chipbench.pspans import decoding_step_spans


def read(ctx):
    steps = decoding_step_spans(ctx)
    if not steps:
        return None
    return sum(s.attrs["host_reads"] for s in steps) / len(steps)
