"""Peak device memory in use over set-up and window (the device's own
``peak_bytes_in_use``), in GB."""


def read(ctx):
    return ctx.memory_peak / 1e9 if ctx.memory_peak else None
