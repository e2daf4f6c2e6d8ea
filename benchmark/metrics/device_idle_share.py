"""1 minus the union of device-op intervals over the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.window_s:
        return None
    return 1.0 - ctx.trace.busy_s / ctx.trace.window_s
