"""Median device time of one decode-program call (device trace)."""

import numpy as np


def read(ctx):
    d = [c.program.dur for c in ctx.red.calls
         if c.kind == "decode" and c.program is not None]
    return float(np.median(d)) * 1e-6 if d else None
