"""dist.reduce_share.solve: share of the devices' busy time spent in
``all-reduce`` operations, the cross-chip sums of the Krylov method's
dot products and norms and of the f64 refinement's residual norm; solve
cells.

On a TPU v5e 2x2 (100^3 in four z-slabs) they run synchronously, one
operation each, several partial sums stacked into one (1,649 a chip in
a 50-iteration solve)::

    %all-reduce.223 = (f32[1]{0:T(128)}, f32[1]{0:T(128)},
        f32[1]{0:T(128)}) all-reduce(f32[1]{0:T(128)} %bitcast.1, ...),
        channel_id=4, replica_groups=[1,4]<=[4], ...

The ``-start`` / ``-done`` halves of an asynchronous all-reduce count
the same way.

Summed durations over summed busy time, over the chips of the traced
window; None where no all-reduce ran.
"""
import re

REDUCE = re.compile(r"^all-reduce(?:-start|-done)?(?:\.\d+)?$")


def is_reduce(op) -> bool:
    return bool(REDUCE.match(op.name))


def read(ctx):
    if ctx.trace is None or ctx.traffic.get("coefficients") is not None:
        return None
    if not ctx.trace.select(is_reduce):
        return None
    return ctx.trace.busy_share(is_reduce)
