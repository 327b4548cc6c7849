"""krylov.iteration_s.solve: window seconds over the Krylov iterations of
the solves completed in it, each with its preconditioner and the host's
f64 refinement.  It stands beside solve_s as the steadier number: a seed
changes how many iterations its right-hand sides take, not what one
costs."""


def read(ctx):
    its = sum(r.get("iterations", 0) for r in ctx.records
              if "error" not in r)
    if not its or ctx.traffic.get("coefficients") is not None:
        return None
    return ctx.window_s / its
