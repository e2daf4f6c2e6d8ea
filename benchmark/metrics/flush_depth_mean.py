"""Mean flush depth: Notary.RequestsBatched over Notary.BatchesDispatched
during the window."""


def read(ctx):
    reg = ctx.registry
    n = reg["Notary.BatchesDispatched"]
    return reg["Notary.RequestsBatched"] / n if n else None
