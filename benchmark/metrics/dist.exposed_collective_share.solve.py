"""dist.exposed_collective_share.solve: per chip, the time in collective
operations (all-reduce, all-gather, collective-permute, ...) during
which no other operation runs on that chip, over the traced window; the
mean over the chips.  None where no collective ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.exposed_collective_share()
