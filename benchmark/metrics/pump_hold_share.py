"""Share of the traced window the pump held a pending batch below its
cap inside the batching deadline: the program's `notary.hold` regions
(the episodes Notary.PumpHold counts) over the window."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    return None if r is None else r.seconds("notary.hold") / r.window_s
