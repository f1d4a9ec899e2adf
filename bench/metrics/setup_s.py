"""Process start to window start: loading, traffic, warm fill, compiling
or reading back every program of the timed path, warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
