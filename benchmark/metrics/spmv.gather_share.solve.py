"""spmv.gather_share.solve: share of the device's busy time spent in
gathers, the ``ell/gather`` fallback SpMV pack of the classical coarse
levels; solve cells.

The TPU trace names an operation by its HLO instruction, and XLA fuses
the pack's ``x[cols]`` read into an anonymous fusion, e.g. (64^3
classical, TPU v5e)::

    %fusion.572 = f32[3145728]{...} fusion(f32[12288]{...} %a,
        s32[3145728]{...} %b), kind=kCustom, ...

The index array may be padded to a whole tile of 1,024 (64^3
aggregation with multicolor DILU, TPU v5e)::

    %fusion.17557 = f32[5481960]{...} fusion(f32[83060]{...} %a,
        s32[5482496]{...} %b), kind=kCustom, ...

So an operation counts as a gather when its name says so, or when it
reads a float vector through an int32 index array: its result is a
float array of the index array's shape (padded or not), and it has a
float operand of another shape.  All-gathers are collectives and do not
count.
"""
import re

NAMED = re.compile(r"(^|_)gather")
COLLECTIVE = re.compile(r"all[-_]?gather", re.IGNORECASE)
SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
FLOAT = ("f32", "f64", "bf16", "f16")
#: the TPU's tile of a 1-D array, to which an index array may be padded
TILE = 1024


def shaped_like(idx: str, out: str) -> bool:
    """The index array has the result's shape, or, in one dimension, the
    result's length padded up to a whole tile."""
    if idx == out:
        return True
    if not idx.isdigit() or not out.isdigit():
        return False
    n = int(out)
    return n < int(idx) == -(-n // TILE) * TILE


def indexed_read(text: str) -> bool:
    """``f32[N] op(f32[M] x, s32[N] idx)``: a float result shaped like
    an int32 operand, read from a float operand of another shape."""
    shapes = SHAPE.findall(text.split(", kind=")[0])
    if len(shapes) < 3 or shapes[0][0] not in FLOAT:
        return False
    out, ops = shapes[0], shapes[1:]
    return (any(dt == "s32" and shaped_like(dims, out[1])
                for dt, dims in ops)
            and any(dt in FLOAT and dims != out[1] for dt, dims in ops))


def is_gather(op) -> bool:
    if COLLECTIVE.search(op.name):
        return False
    return bool(NAMED.search(op.name)) or (
        op.name.startswith("fusion") and indexed_read(op.text))


def read(ctx):
    if ctx.trace is None or ctx.traffic.get("coefficients") is not None:
        return None
    return ctx.trace.busy_share(is_gather)
