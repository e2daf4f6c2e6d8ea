"""Uniqueness seconds per notarisation: Notary.FlushPhase.commit plus stream_commit (which, streamed, also validates and waits on each chunk)."""

PHASES = ("commit", "stream_commit")


def read(ctx):
    reg = ctx.registry
    n = reg["Notary.RequestsBatched"]
    seconds = sum(reg["phase." + p][0] for p in PHASES)
    if not n or not any(reg["phase." + p][1] for p in PHASES):
        return None
    return 1e6 * seconds / n
