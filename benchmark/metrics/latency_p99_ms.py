"""99th percentile, nearest rank, of reply time minus due time over
every request due in the window (replies up to a minute after the
close count with their wait)."""


def read(ctx):
    if not ctx.latencies_s:
        return None
    v = sorted(ctx.latencies_s)
    return 1e3 * v[max(0, -(-99 * len(v) // 100) - 1)]
