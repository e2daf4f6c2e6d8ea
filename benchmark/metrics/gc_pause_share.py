"""Share of the traced window spent in garbage-collector pauses, every
generation: the program's `gc.collect` regions (the pauses the
Runtime.GcSeconds gauges count) over the window."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    return None if r is None else r.seconds("gc.collect") / r.window_s
