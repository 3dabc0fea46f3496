"""Median wait from a request's due time to the start of its prefill
(host clock, traced run): the scheduler's queue plus the step it arrived
in."""

import numpy as np


def read(ctx):
    log = ctx.log
    waits = [s.prefill_at - s.due for s in log.served
             if s.prefill_at is not None and s.prefill_at <= log.t_end]
    return float(np.median(waits)) * 1e3 if waits else None
