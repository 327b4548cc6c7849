"""krylov.iterations.solve: mean Krylov iterations per solve in the
window, as each SolveResult reports them (the f64 refinement passes
included, since the solver counts its inner iterations across them)."""


def read(ctx):
    its = [r["iterations"] for r in ctx.records if "iterations" in r]
    return sum(its) / len(its) if its else None
