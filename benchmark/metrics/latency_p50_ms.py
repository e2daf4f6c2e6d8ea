"""Median (nearest rank) of reply time minus due time over every
request due in the window."""


def read(ctx):
    if not ctx.latencies_s:
        return None
    v = sorted(ctx.latencies_s)
    return 1e3 * v[max(0, -(-len(v) // 2) - 1)]
