"""Median time a request admitted in the window waited in the engine's
queue: its ``serve.queue`` span, from ``add_request`` to the start of its
admission (host clock).  Unlike ``queue_wait_ms_p50`` it leaves out how
late the generator added the request and the step in flight when it was
due.  A run whose engine has no tracer reports nothing."""

import numpy as np

from chipbench.pspans import record


def read(ctx):
    rec = record(ctx)
    if rec is None:
        return None
    lo, hi = ctx.log.t0 * 1e9, ctx.log.t_end * 1e9
    waits = [s.dur for s in rec.tracer.spans
             if s.name == "serve.queue" and lo <= s.end < hi]
    return float(np.median(waits)) * 1e-6 if waits else None
