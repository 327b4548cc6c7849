"""solve_s: window seconds over the solves completed in it.  Each solve
is of a fixed operator, with a fresh right-hand side and a zero initial
guess, and ends when x is on the host."""


def read(ctx):
    done = [r for r in ctx.records if "error" not in r]
    if not done or ctx.traffic.get("coefficients") is not None:
        return None
    return ctx.window_s / len(done)
