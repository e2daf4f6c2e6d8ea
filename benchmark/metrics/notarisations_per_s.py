"""Requests answered inside the window (accepts, invalid-signature
rejects and conflicts alike; the run's check holds every answer to
construction), per second of the window."""


def read(ctx):
    return ctx.answered_in_window / ctx.window_s
