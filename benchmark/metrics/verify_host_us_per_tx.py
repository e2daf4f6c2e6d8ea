"""Host seconds of verify dispatch per notarisation: the Notary.FlushPhase stage, dispatch and resolve_verify timers over the window."""

PHASES = ("stage", "dispatch", "resolve_verify")


def read(ctx):
    reg = ctx.registry
    n = reg["Notary.RequestsBatched"]
    seconds = sum(reg["phase." + p][0] for p in PHASES)
    if not n or not any(reg["phase." + p][1] for p in PHASES):
        return None
    return 1e6 * seconds / n
