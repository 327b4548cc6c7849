"""amg.resetup_s.step: mean seconds of Solver.resetup per step, on the
host clock, each ended by waiting for every live device array."""


def read(ctx):
    ts = [r["resetup_s"] for r in ctx.records if "resetup_s" in r]
    return sum(ts) / len(ts) if ts else None
