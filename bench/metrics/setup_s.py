"""Process start to the first measured operation: imports, device start,
inputs, compilation or cache loading, and the warm-up calls."""


def read(ctx):
    return ctx.setup_s
