"""dist.halo_share.solve: share of the devices' busy time spent in the
collectives of the halo exchange between chips; solve cells.

The sharded SpMV sends each neighbour its boundary values with one
``ppermute`` per ring distance, which the TPU runs asynchronously: a
``-start`` and a ``-done`` operation on the chip's line of XLA
operations, the transfer itself on the interconnect between them.  On a
TPU v5e 2x2 (100^3 in four z-slabs, the two neighbour planes of one
SpMV, 6,126 of each pair a chip in a 50-iteration solve)::

    %collective-permute-start.130 = (f32[20000]{0:T(1024)},
        f32[20000]{0:T(1024)}, u32[]{:S(2)}, u32[]{:S(2)})
        collective-permute-start(f32[20000]{0:T(1024)} %fusion.2135),
        channel_id=1, source_target_pairs={{0,3},{1,0},{2,1},{3,2}}
    %collective-permute-done.130 = f32[20000]{0:T(1024)}
        collective-permute-done(... %collective-permute-start.130)

A dense link set takes one ``all-gather`` instead, counted the same
way, synchronous or split.  The gather of the send buffer and the read
of the received halo are fusions of their own and do not count here.

Summed durations over summed busy time, over the chips of the traced
window; None where no such operation ran.
"""
import re

HALO = re.compile(r"^(?:collective-permute|all-gather)(?:-start|-done)?"
                  r"(?:\.\d+)?$")


def is_halo(op) -> bool:
    return bool(HALO.match(op.name))


def read(ctx):
    if ctx.trace is None or ctx.traffic.get("coefficients") is not None:
        return None
    if not ctx.trace.select(is_halo):
        return None
    return ctx.trace.busy_share(is_halo)
