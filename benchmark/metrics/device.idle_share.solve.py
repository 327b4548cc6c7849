"""device.idle_share.solve: 1 - (union of device-operation intervals over
the traced window), the mean over the chips used; solve cells."""


def read(ctx):
    if ctx.trace is None or ctx.traffic.get("coefficients") is not None:
        return None
    return ctx.trace.idle_share()
