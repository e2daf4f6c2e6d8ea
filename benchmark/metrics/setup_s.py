"""Process start to the window's first request: imports, native codec,
frame pool, store copy, compile or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
