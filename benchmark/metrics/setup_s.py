"""setup_s: seconds from the start of the process to the start of the
measured window: imports, inputs from the seed, solver set-up and the
one warm-up step (which traces and, with a cold cache, compiles)."""


def read(ctx):
    return ctx.setup_s
