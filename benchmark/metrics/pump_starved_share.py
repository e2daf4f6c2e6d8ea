"""Share of the traced window the pump found nothing to drain and
nothing pending: the program's `notary.starved` regions (the episodes
Notary.PumpStarved counts) over the window."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    return None if r is None else r.seconds("notary.starved") / r.window_s
