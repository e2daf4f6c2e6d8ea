"""Wire intake seconds per frame: the decode, Merkle-id and staging
intervals of every ingest batch (the intervals the ingest.decode,
ingest.merkle_id and ingest.stage spans carry), read through the
pipeline's perf seam over the window."""


def read(ctx):
    p = ctx.ingest
    if p is None or not p.frames:
        return None
    return 1e6 * sum(p.seconds.values()) / p.frames
