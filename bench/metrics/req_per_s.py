"""Requests completed in the window over the window's length (host clock;
each replay segment ends in a blocking read of its counts)."""


def read(ctx):
    return ctx.attempted / ctx.window_s if ctx.attempted else None
