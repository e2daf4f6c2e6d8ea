"""How unevenly the shard router spreads each wave: the plane's shard
count times the deepest shard's transactions, over all the wave's
transactions, summed over the `notary.wave` regions that start in the
traced window (their `n_shards`, `max_frames` and `frames`). 1.0:
every wave balanced over every shard; n_shards: one shard per wave.
None for a program that marks no wave."""

from benchmark import regions


def read(ctx):
    r = regions.load(ctx, __file__)
    if r is None:
        return None
    frames = r.stat_sum("notary.wave", "frames")
    if not frames:
        return None
    lo, hi = r.window
    deepest = sum(st.get("n_shards", 0) * st.get("max_frames", 0)
                  for s, _, st in r.events.get("notary.wave", ())
                  if lo <= s < hi)
    return deepest / frames
