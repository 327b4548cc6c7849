"""dia_spmv_roofline: the DIA SpMV kernel's share of its HBM roofline, in %.

Each execution of the Pallas DIA kernel in the traced window is one
event on a device plane, whose HLO text gives the shapes of its result
y (rows/128, 128), its operands x (padded, ., 128) and the value planes
(diagonals, rows/128, 128), and the memory space of each.  The least
bytes it must move through HBM (``roofline.dia_apply_bytes``: each
stored value, x and y once) count only the arrays that live in HBM:
XLA may keep an array in the on-chip alternate memory (layout ``S(1)``),
whose bandwidth is not in the peak table, so no bound is claimed for
it.  The share is the least time for those bytes at the chip's peak HBM
bandwidth over the device time of the executions that move any; the
kernel does f32 multiply-adds on the vector units, so HBM bytes, not
operations, bound it.  None where no execution touches HBM.
"""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import roofline  # noqa: E402

#: the kernel's HLO instruction, named after its jitted wrapper
KERNEL = re.compile(r"^_dia_spmv_call")
#: an array shape with its layout: dtype[dims]{layout}
SHAPE = re.compile(r"\b(f32|bf16)\[([\d,]+)\]\{([^}]*)\}")
ITEMSIZE = {"f32": 4, "bf16": 2}


def hbm_bytes(op):
    """Least HBM bytes of one execution, from the shapes and memory
    spaces in its HLO text (y, then x, then the value planes); None when
    the text does not show them."""
    shapes = SHAPE.findall(op.text)
    if len(shapes) < 3:
        return None
    (ydt, ydims, ylay), (xdt, _x, xlay), (vdt, vdims, vlay) = shapes[:3]
    vd = [int(d) for d in vdims.split(",")]
    if len(vd) != 3:
        return None
    nd, rows = vd[0], vd[1] * vd[2]
    in_hbm = [("S(" not in lay) for lay in (vlay, xlay, ylay)]
    vals = roofline.dia_apply_bytes(rows, nd, ITEMSIZE[vdt]) \
        - roofline.dia_apply_bytes(rows, 0, ITEMSIZE[vdt])
    return (vals * in_hbm[0] + rows * ITEMSIZE[xdt] * in_hbm[1]
            + rows * ITEMSIZE[ydt] * in_hbm[2])


def apply_flops(op):
    """Operations of one execution (a multiply and an add per stored
    value), from the value planes' shape."""
    vdims = SHAPE.findall(op.text)[2][1].split(",")
    return roofline.dia_apply_flops(int(vdims[1]) * int(vdims[2]),
                                    int(vdims[0]))


def read(ctx):
    if ctx.trace is None or ctx.device.get("platform") != "tpu":
        return None
    moved, flops, seconds = 0, 0, 0.0
    for o in ctx.trace.select(lambda o: bool(KERNEL.search(o.name))):
        b = hbm_bytes(o)
        if b:
            moved += b
            flops += apply_flops(o)
            seconds += o.end - o.start
    if not moved:
        return None
    share, _bound = roofline.roofline_share(
        float(flops), float(moved), seconds,
        roofline.peak(ctx.device["kind"]))
    return 100.0 * share
