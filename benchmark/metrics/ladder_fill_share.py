"""Real signature rows over the padded rows the EC ladders computed for
them, over the launches of the traced window: the `rows` and `batch`
the program's `verify.launch` regions carry (the counts
DeviceAccounting records as `requests` and `rows`)."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    if r is None:
        return None
    padded = r.stat_sum("verify.launch", "batch")
    return r.stat_sum("verify.launch", "rows") / padded if padded else None
