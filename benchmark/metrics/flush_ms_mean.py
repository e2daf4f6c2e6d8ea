"""Mean flush wall: every Notary.FlushPhase timer summed over the
window, per flush dispatched."""


def read(ctx):
    reg = ctx.registry
    n = reg["Notary.BatchesDispatched"]
    if not n:
        return None
    return 1e3 * sum(v[0] for k, v in reg.items()
                     if k.startswith("phase.")) / n
