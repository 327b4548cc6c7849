"""device.idle_share.step: 1 - (union of device-operation intervals over
the traced window), the mean over the chips used; re-setup cells."""


def read(ctx):
    if ctx.trace is None or ctx.traffic.get("coefficients") is None:
        return None
    return ctx.trace.idle_share()
