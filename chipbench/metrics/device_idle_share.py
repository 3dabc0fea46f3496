"""Share of the time the engine had work (inside its steps) in which no
operation ran on the device (device trace joined with the step spans)."""


def read(ctx):
    red = ctx.red
    total = sum(s.dur for s in red.steps)
    if not total or not red.busy:
        return None
    return 100.0 * (1.0 - red.busy_within(red.steps) / total)
