"""The trace reducer, on synthetic intervals and on a small trace
recorded on a TPU v5e (``data/fixture.xplane.pb``: one FGMRES +
aggregation AMG solve of the 32^3 7-point Poisson, inside the
benchmark's own ``bench/window``, ``bench/solve`` and ``bench/fetch``
spans)."""
import collections
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402

trace = spec.local("trace")
FIXTURE = BENCH / "tests" / "data" / "fixture.xplane.pb"


def test_interval_arithmetic():
    m = trace.merge([(3, 4), (0, 1), (0.5, 2), (5, 6)])
    assert m == [(0, 2), (3, 4), (5, 6)]
    assert trace.length(m) == 4
    assert trace.subtract([(0, 10)], [(1, 2), (4, 5)]) == [
        (0, 1), (2, 4), (5, 10)]
    assert trace.subtract([(0, 1)], [(0, 1)]) == []
    assert trace.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def _synthetic():
    Op = trace.Op
    ops = {0: [Op("fusion.1", 1.0, 2.0, ""),
               Op("all-reduce.2", 2.5, 3.5, ""),
               Op("fusion.3", 3.0, 3.2, "")],
           1: [Op("fusion.1", 1.0, 3.0, "")]}
    spans = [trace.Span("bench/window", 0.0, 4.0),
             trace.Span("bench/solve", 0.6, 3.6),
             trace.Span("bench/fetch", 3.6, 3.9)]
    return trace.Trace(ops, spans)


def test_busy_idle_and_collectives_on_synthetic_trace():
    t = _synthetic()
    assert t.window == (0.0, 4.0)
    assert t.busy_s() == pytest.approx((2.0 + 2.0) / 2)
    assert t.idle_share() == pytest.approx(0.5)
    # device 0: collective 2.5-3.5, compute covers 3.0-3.2 -> 0.8 s
    assert t.exposed_collective_share() == pytest.approx((0.8 / 4) / 2)
    gaps = t.idle_gaps(0)
    assert gaps[0][0] == "harness" and gaps[0][1] == pytest.approx(1.0)
    assert ("solve", pytest.approx(0.5)) in [(g[0], g[1]) for g in gaps]


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.is_file():
        pytest.fail(f"missing {FIXTURE}")
    return trace.load(str(FIXTURE))


def test_recorded_trace_reads_device_planes_only(recorded):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    per_dev = collections.Counter()
    host_events = 0
    for plane in pd.planes:
        for line in plane.lines:
            n = sum(1 for e in line.events if e.duration_ns > 0)
            if trace.DEVICE_PLANE_RE.match(plane.name) \
                    and line.name == trace.OPS_LINE:
                per_dev[plane.name] += n
            elif plane.name.startswith("/host"):
                host_events += n
    assert recorded.devices == [0]
    assert host_events > 0                  # host threads were there
    assert sum(len(v) for v in recorded.ops.values()) == sum(
        per_dev.values())


def test_recorded_trace_window_and_busy(recorded):
    assert {s.name for s in recorded.spans} >= {
        "bench/window", "bench/solve", "bench/fetch"}
    busy = recorded.busy_s()
    assert 0 < busy < recorded.window_s
    assert 0 < recorded.idle_share() < 1
    labels = {g[0] for g in recorded.idle_gaps()}
    assert labels <= {"solve", "fetch", "harness"}


def test_recorded_trace_kernel_roofline(recorded):
    """The DIA kernel's executions are found by name on the device plane.
    At 32^3 XLA keeps every operand in the on-chip alternate memory
    (``S(1)``), so no execution moves HBM bytes and the roofline reader
    reports nothing rather than a share above its bound."""
    reader = spec.reader("dia_spmv_roofline")
    ops = recorded.select(lambda o: bool(reader.KERNEL.search(o.name)))
    assert ops and not any(o.container for o in ops)
    assert all(reader.hbm_bytes(o) == 0 for o in ops)
    assert any(o.container and o.name.startswith("while")
               for v in recorded.ops.values() for o in v)

    class Ctx:
        trace = recorded
        device = {"platform": "tpu", "kind": "TPU v5 lite"}
    assert reader.read(Ctx) is None
    # the same execution with its arrays in HBM moves every value, x and y
    hbm = trace.Op(ops[0].name, 0.0, 1e-5, ops[0].text.replace("S(1)", ""))
    assert reader.hbm_bytes(hbm) == 32 ** 3 * (7 + 2) * 4
    Ctx.trace = trace.Trace({0: [hbm]},
                            [trace.Span("bench/window", 0.0, 1e-5)])
    assert reader.read(Ctx) == pytest.approx(
        100 * 32 ** 3 * 9 * 4 / 819e9 / 1e-5)


def test_gather_reader_finds_fused_gathers():
    """The fused ``x[cols]`` read of the ell/gather pack, as the TPU
    traces of the 64^3 classical and aggregation solves name it (the
    latter with its index array padded to a whole tile), counts; a fused
    elementwise op, an index array longer than one tile's padding, and
    an all-gather do not."""
    g = spec.reader("spmv.gather_share.solve")
    Op = trace.Op
    fused = ("%fusion.572 = f32[3145728]{0:T(1024)S(1)} fusion(f32[12288]"
             "{0:T(1024)S(1)} %subtract_multiply_fusion.14, s32[3145728]"
             "{0:T(1024)S(1)} %broadcast_clamp_fusion.29), kind=kCustom, "
             "calls=%fused_computation.3")
    elementwise = ("%fusion.9 = f32[4096]{0} fusion(f32[4096]{0} %a, "
                   "f32[4096]{0} %b), kind=kLoop, calls=%fused_computation")
    padded = ("%fusion.17557 = f32[5481960]{0:T(1024)S(1)} fusion(f32[83060]"
              "{0:T(1024)S(1)} %multiply_add_fusion.150, s32[5482496]"
              "{0:T(1024)S(1)} %custom-call.9230), kind=kCustom, "
              "calls=%fused_computation.1557")
    overlong = padded.replace("s32[5482496]", "s32[5483520]")
    assert g.is_gather(Op("fusion.572", 0, 1, fused))
    assert g.is_gather(Op("fusion.17557", 0, 1, padded))
    assert not g.is_gather(Op("fusion.17557", 0, 1, overlong))
    assert g.is_gather(Op("gather.3", 0, 1, "%gather.3 = f32[8]{0} ..."))
    assert not g.is_gather(Op("fusion.9", 0, 1, elementwise))
    assert not g.is_gather(Op("all-gather.1", 0, 1, "%all-gather.1 = ..."))
