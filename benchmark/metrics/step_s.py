"""step_s: window seconds over the time steps completed in it.  A step is
replace_coefficients (with the host-to-device upload of the new values)
-> resetup -> one solve, ending when x is on the host."""


def read(ctx):
    done = [r for r in ctx.records if "error" not in r]
    if not done or ctx.traffic.get("coefficients") is None:
        return None
    return ctx.window_s / len(done)
