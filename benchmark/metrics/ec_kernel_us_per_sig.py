"""Device time of the EC ladders per real, unpadded signature
dispatched in the traced window (the verifier's per-device row
counter). The ladders are the program's only Pallas kernels
(crypto/pallas_ec.py), so their ops are the trace's tpu_custom_calls."""

KERNEL = r"tpu_custom_call"


def read(ctx):
    rows = ctx.registry["sig_rows"]
    if ctx.trace is None or not rows:
        return None
    t = ctx.trace.kernel_s(KERNEL)
    return 1e6 * t / rows if t else None
