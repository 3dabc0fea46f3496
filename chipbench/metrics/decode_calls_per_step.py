"""Decode-program calls per engine step that decoded (counted by the
driver's wrapper around the engine's decode call)."""


def read(ctx):
    steps = [s for s in ctx.log.steps
             if s.start < ctx.log.t_end and s.decode_calls]
    if not steps:
        return None
    return sum(s.decode_calls for s in steps) / len(steps)
