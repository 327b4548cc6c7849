"""The Distributed layer's readers on synthetic traces of four chips:
``dist.halo_share.solve``, ``dist.reduce_share.solve`` and
``dist.exposed_collective_share.solve``, each against arithmetic done by
hand.

One device line holds one operation at a time, or operations nested in
one that holds them.  A collective whose wait shows as its own
operation (the ``-done`` half of an asynchronous one, or a synchronous
all-reduce) is exposed; one that holds other operations is overlapped
with them for their length."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402

trace = spec.local("trace")
HALO = spec.reader("dist.halo_share.solve")
REDUCE = spec.reader("dist.reduce_share.solve")
EXPOSED = spec.reader("dist.exposed_collective_share.solve")
GATHER = spec.reader("spmv.gather_share.solve")
SOLVE = {"rhs_pool": 16, "coefficients": None}
RESETUP = {"rhs_pool": 16, "coefficients": {"kind": "lognormal"}}


class Ctx:
    def __init__(self, tr, traffic=SOLVE):
        self.trace, self.traffic = tr, traffic


def _device(e: float):
    """One chip's window of 10 s, its halo wait and all-reduce late by
    ``e``: compute, an asynchronous halo exchange (start, compute, done),
    an exposed all-reduce, an all-gather that holds 1 s of compute, and
    compute again."""
    Op = trace.Op
    return [Op("fusion.1", 0.0, 3.0, ""),
            Op("collective-permute-start.1", 3.0, 3.1, ""),
            Op("fusion.2", 3.1, 5.0, ""),
            Op("collective-permute-done.1", 5.0, 5.4 + e, ""),
            Op("all-reduce.3", 5.5 + e, 6.0 + e, ""),
            Op("all-gather.4", 6.5, 8.5, ""),
            Op("fusion.5", 7.0, 8.0, ""),
            Op("fusion.6", 9.0, 9.5, "")]


LATE = (0.0, 0.1, 0.2, 0.3)


@pytest.fixture
def four_chips():
    return trace.Trace({d: _device(e) for d, e in enumerate(LATE)},
                       [trace.Span("bench/window", 0.0, 10.0),
                        trace.Span("bench/solve", 0.0, 9.8)])


def test_halo_share_counts_the_exchange_that_holds_no_other_op(four_chips):
    # busy per chip: [0, 5.4+e] + [5.5+e, 6+e] + [6.5, 8.5] + [9, 9.5]
    busy = sum(8.4 + e for e in LATE)
    # the start and the done of the collective-permute; the all-gather
    # holds fusion.5, so only what it holds counts (as compute)
    halo = sum(0.1 + 0.4 + e for e in LATE)
    assert HALO.read(Ctx(four_chips)) == pytest.approx(halo / busy)


def test_reduce_share_counts_the_all_reduces(four_chips):
    busy = sum(8.4 + e for e in LATE)
    assert REDUCE.read(Ctx(four_chips)) == pytest.approx(4 * 0.5 / busy)


def test_exposed_share_leaves_out_what_compute_overlaps(four_chips):
    # exposed per chip: the start 0.1, the done 0.4+e, the all-reduce
    # 0.5 and the all-gather's 2 s less the 1 s it overlaps; over 10 s
    per_chip = [(0.1 + 0.4 + e + 0.5 + 1.0) / 10.0 for e in LATE]
    assert EXPOSED.read(Ctx(four_chips)) == pytest.approx(
        sum(per_chip) / 4)


def test_collectives_are_not_gathers(four_chips):
    # the SpMV pack reader counts no collective and no plain fusion
    assert GATHER.read(Ctx(four_chips)) == 0.0


@pytest.mark.parametrize("reader", [HALO, REDUCE, EXPOSED])
def test_no_collective_no_number(reader):
    Op = trace.Op
    tr = trace.Trace({d: [Op("fusion.1", 0.0, 1.0, "")] for d in range(4)},
                     [trace.Span("bench/window", 0.0, 2.0)])
    assert reader.read(Ctx(tr)) is None
    assert reader.read(Ctx(None)) is None


@pytest.mark.parametrize("reader", [HALO, REDUCE])
def test_solve_cells_only(reader, four_chips):
    assert reader.read(Ctx(four_chips, RESETUP)) is None


@pytest.mark.parametrize("name,halo,reduce", [
    ("collective-permute-start.1", True, False),
    ("collective-permute-done", True, False),
    ("collective-permute.7", True, False),
    ("all-gather.2", True, False),
    ("all-gather-start", True, False),
    ("all-reduce.2", False, True),
    ("all-reduce-done.4", False, True),
    ("reduce-scatter.1", False, False),
    ("fusion.12", False, False),
    ("copy-start.3", False, False),
])
def test_names(name, halo, reduce):
    op = trace.Op(name, 0.0, 1.0, "")
    assert HALO.is_halo(op) is halo
    assert REDUCE.is_reduce(op) is reduce
