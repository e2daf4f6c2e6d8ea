"""99th percentile, nearest rank, of how late the benchmark's own load
generator handed each frame to intake, against its due time."""


def read(ctx):
    if not ctx.lateness_s:
        return None
    v = sorted(ctx.lateness_s)
    return 1e3 * v[max(0, -(-99 * len(v) // 100) - 1)]
