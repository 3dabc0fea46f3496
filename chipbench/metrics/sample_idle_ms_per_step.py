"""Device idle time inside steps while the host samples one group's
tokens and launches the next group's decode, per window step that
decoded, in ms: the idle while ``serve.sample`` (the per-slot reads of
the sampled tokens, the retire bookkeeping) or ``serve.decode`` (the
token batch and the decode call) is the innermost span the engine has
open, from the profiler's host plane joined with the device trace.  The
two are summed because the profiler's host/device alignment moves up to
1 ms a group between them.  A run whose engine has no tracer reports
nothing."""

from chipbench import pspans

SPANS = ("serve.sample", "serve.decode")


def read(ctx):
    rec = pspans.record(ctx)
    steps = pspans.decoding_steps(ctx.red)
    if rec is None or not rec.spans or not steps:
        return None
    idle = pspans.idle_by_span(ctx.red, rec.spans, steps)
    return sum(idle.get(s, 0.0) for s in SPANS) * 1e-6 / len(steps)
