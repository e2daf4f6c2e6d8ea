"""Batch signing and reply scatter seconds per notarisation (Notary.FlushPhase.sign_scatter)."""

PHASES = ("sign_scatter",)


def read(ctx):
    reg = ctx.registry
    n = reg["Notary.RequestsBatched"]
    seconds = sum(reg["phase." + p][0] for p in PHASES)
    if not n or not any(reg["phase." + p][1] for p in PHASES):
        return None
    return 1e6 * seconds / n
