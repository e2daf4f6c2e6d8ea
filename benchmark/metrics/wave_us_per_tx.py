"""Sharded flush seconds per notarisation: seconds of the program's
`notary.wave` regions inside the traced window (stage, dispatch and
consume of every shard a wave flushes) over the transactions of the
waves that start there (their `frames`). None for a program that marks
no wave."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    if r is None:
        return None
    frames = r.stat_sum("notary.wave", "frames")
    return 1e6 * r.seconds("notary.wave") / frames if frames else None
