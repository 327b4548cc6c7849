"""Device-time attribution (telemetry/scopes.py + deviceprof.py):
the named-scope contract, the profiler-trace correlator, the anatomy
arithmetic, and the emit → schema-validation round trip."""
import gzip
from pathlib import Path

import pytest

from amgx_tpu import telemetry
from amgx_tpu.telemetry import deviceprof, proftrace, scopes
from tests.conftest import synthetic_trace_events

pytestmark = pytest.mark.deviceprof

#: one FGMRES + aggregation AMG solve of the 32^3 7-point Poisson,
#: recorded on a TPU v5e (the benchmark's trace-reader fixture)
XPLANE_FIXTURE = (Path(__file__).resolve().parents[1] / "benchmark"
                  / "tests" / "data" / "fixture.xplane.pb")


# ------------------------------------------------------- scope contract
def test_scope_name_sanitises_and_validates():
    assert scopes.scope_name("spmv", "ell/binned-block") == \
        "amgx/spmv/ell/binned_block"
    assert scopes.scope_name("cycle", "level3/pre_smooth") == \
        "amgx/cycle/level3/pre_smooth"
    assert scopes.validate("amgx/smoother/block_jacobi")
    assert not scopes.validate("amgx/cycle")           # no leaf
    assert not scopes.validate("amgx/bogus/thing")     # unknown area
    assert not scopes.validate("AMGX/spmv/dia3")       # case matters
    with pytest.raises(ValueError):
        scopes.scope_name("bogus", "x")


def test_every_registered_pack_yields_a_valid_scope():
    for pack in scopes.SPMV_PACKS:
        assert scopes.validate(scopes.scope_name("spmv", pack))


def test_scope_is_a_jax_named_scope():
    import jax.numpy as jnp
    with scopes.scope("spmv", "dia3"):
        x = jnp.ones(3) + 1
    assert float(x.sum()) == 6.0


def test_canonicalize_trims_xla_op_pollution():
    c = scopes.canonicalize
    assert c("amgx/cycle/level0/pre_smooth/fusion") == \
        "amgx/cycle/level0/pre_smooth"
    assert c("amgx/cycle/coarse_solve/custom_call") == \
        "amgx/cycle/coarse_solve"
    assert c("amgx/spmv/dia/slices/while/body/dot") == \
        "amgx/spmv/dia/slices"
    assert c("amgx/krylov/reduce") == "amgx/krylov/reduce"
    assert c("amgx/krylov/bogus_stage") is None
    assert c("amgx/dist/not_halo") is None
    assert c("amgx/cycle/levelx/pre_smooth") is None
    assert c("not/a/scope") is None


def test_extract_scopes_splits_nested_annotation_stacks():
    raw = ("amgx/cycle/level0/pre_smooth/amgx/smoother/block_jacobi/"
           "amgx/spmv/dia3/fusion.3")
    assert scopes.extract_scopes(raw) == [
        "amgx/cycle/level0/pre_smooth",
        "amgx/smoother/block_jacobi",
        "amgx/spmv/dia3",
    ]
    # dots/hyphens terminate the match — XLA suffixes never leak in
    assert scopes.extract_scopes("amgx/krylov/reduce/all-reduce.1") == \
        ["amgx/krylov/reduce"]
    assert scopes.extract_scopes("nothing here") == []


# ---------------------------------------------------- anatomy arithmetic
def test_anatomy_ground_truth(chrome_trace):
    a = deviceprof.measure_anatomy(chrome_trace)
    assert a["measured"] is True
    assert a["scope_version"] == scopes.SCOPE_VERSION
    assert a["total_device_s"] == pytest.approx(330e-6)
    assert a["attributed_s"] == pytest.approx(320e-6)
    assert a["unattributed_s"] == pytest.approx(10e-6)
    assert a["n_devices"] == 1
    lv0, lv1 = a["levels"]["0"], a["levels"]["1"]
    assert lv0["pre_smooth"] == pytest.approx(100e-6)
    assert lv0["restrict"] == pytest.approx(50e-6)
    assert lv0["prolong"] == pytest.approx(60e-6)
    assert lv0["post_smooth"] == pytest.approx(40e-6)
    assert lv0["total_s"] == pytest.approx(250e-6)     # union, no gaps
    assert lv1["total_s"] == pytest.approx(70e-6)
    assert a["coarse_s"] == pytest.approx(20e-6)
    assert a["smoothers"]["block_jacobi"] == pytest.approx(100e-6)
    assert a["krylov"]["reduce"] == pytest.approx(30e-6)
    assert a["dist"]["halo_exchange"] == pytest.approx(20e-6)
    # every reported scope honours the contract
    assert a["scopes"]
    for s in a["scopes"]:
        assert scopes.validate(s), s


def test_spmv_and_dist_by_level(chrome_trace):
    """Each SpMV pack and distributed stage is charged to the cycle
    level it ran in: a sharded level's interior path (``sharded_gather``
    here) and its boundary correction, nested under the level, the
    smoother and the pack; the Krylov method's own halo exchange falls
    outside the cycle."""
    dd = ("amgx/cycle/level1/post_smooth/amgx/smoother/multicolor_dilu/"
          "amgx/spmv/sharded/gather/amgx/dist/boundary/fusion.11")
    events = chrome_trace["traceEvents"] + [
        {"ph": "X", "pid": 0, "tid": 1, "ts": 400, "dur": 15,
         "name": "fusion.11", "args": {"name": dd}}]
    a = deviceprof.measure_anatomy(events)
    assert a["spmv_by_level"] == {
        "0": {"dia/slices": pytest.approx(100e-6)},
        "1": {"sharded/gather": pytest.approx(15e-6)}}
    assert a["dist_by_level"] == {
        "1": {"boundary": pytest.approx(15e-6)},
        "outside": {"halo_exchange": pytest.approx(20e-6)}}
    assert a["dist"]["boundary"] == pytest.approx(15e-6)


def test_per_level_sum_within_ten_percent_of_total(chrome_trace):
    """The acceptance criterion: levels + coarse ≈ total device time
    (levels 0 and 1 deliberately overlap in the fixture, so the sum
    honestly exceeds the union — but within the tolerance)."""
    a = deviceprof.measure_anatomy(chrome_trace)
    level_sum = sum(lv["total_s"] for lv in a["levels"].values()) \
        + a["coarse_s"]
    assert abs(level_sum - a["total_device_s"]) \
        <= 0.10 * a["total_device_s"]


def test_attribution_identity(chrome_trace):
    a = deviceprof.measure_anatomy(chrome_trace)
    assert a["attributed_s"] + a["unattributed_s"] == \
        pytest.approx(a["total_device_s"])


def test_measured_bandwidth_joins_cost_and_dispatch(chrome_trace):
    # the fixture is a TPU trace: give the chip's peak explicitly (this
    # process runs on the CPU, which has none)
    from amgx_tpu.telemetry.peaks import PEAKS
    # the pack's call site runs 4 times in the trace: executions are
    # counted there, not from traced dispatches
    site = "amgx/cycle/level1/pre_smooth/amgx/spmv/dia/slices/"
    events = [dict(ph="X", pid=0, tid=1, ts=1000 + 50 * i + 25 * k,
                   dur=25, name=f"fusion.2{k}",
                   args={"name": site + f"fusion.2{k}"})
              for i in range(4) for k in (0, 1)]
    a = deviceprof.measure_anatomy(
        {"traceEvents": chrome_trace["traceEvents"] + events},
        pack_bytes={"dia": 8000},              # op_cost base kind
        peak_gbs=PEAKS["TPU v5 lite"].hbm_gbs)
    e = a["spmv"]["dia/slices"]
    assert e["device_s"] == pytest.approx(300e-6)
    assert e["bytes_per_apply"] == 8000
    # one execution of the fixture's site, four of the new one
    assert e["executions"] == 5
    # 8000 B × 5 / 300 µs = 0.1333 GB/s
    assert e["measured_gbs"] == pytest.approx(0.13, abs=0.005)
    assert e["roofline_fraction"] == pytest.approx(
        8000 * 5 / 300e-6 / 1e9 / a["hbm_peak_gbs"], abs=1e-6)


def test_bandwidth_absent_without_stats(chrome_trace):
    a = deviceprof.measure_anatomy(chrome_trace)
    assert "measured_gbs" not in a["spmv"]["dia/slices"]


# ------------------------------------------- degraded inputs stay honest
def test_empty_trace_is_a_stub():
    a = deviceprof.measure_anatomy({"traceEvents": []})
    assert a["measured"] is False
    assert a["total_device_s"] == 0.0
    assert a["levels"] == {} and a["spmv"] == {}


def test_unscoped_trace_is_a_stub():
    a = deviceprof.measure_anatomy({"traceEvents": [
        {"ph": "X", "pid": 0, "ts": 0, "dur": 10, "name": "fusion.1"},
    ]})
    assert a["measured"] is False
    assert a["total_device_s"] == pytest.approx(10e-6)
    assert a["attributed_s"] == 0.0


def test_malformed_trace_inputs():
    assert deviceprof.measure_anatomy(None)["measured"] is False
    assert deviceprof.measure_anatomy(42)["measured"] is False
    assert deviceprof.measure_anatomy(
        "/nonexistent/trace.json")["measured"] is False
    assert deviceprof.measure_anatomy(
        {"traceEvents": "garbage"})["measured"] is False


def test_trace_file_discovery(tmp_path):
    """A profiler log directory resolves to its newest ``.xplane.pb``;
    a chrome-trace file at the path is no capture."""
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.trace.json.gz").write_bytes(gzip.compress(b"{}"))
    assert proftrace.find_xplane_file(str(tmp_path)) is None
    assert deviceprof.measure_anatomy(str(tmp_path))["measured"] is False
    p = d / "host.xplane.pb"
    p.write_bytes(XPLANE_FIXTURE.read_bytes())
    assert proftrace.find_xplane_file(str(tmp_path)) == str(p)
    a = deviceprof.measure_anatomy(str(tmp_path))
    assert a["measured"] is True
    assert a["total_device_s"] == pytest.approx(0.004580665, abs=1e-9)


# ------------------------------------------------ recorder ring plumbing
def test_pack_stats_from_ring_records():
    records = [
        {"kind": "event", "name": "op_cost",
         "attrs": {"pack": "dia", "bytes_per_apply": 1000}},
        {"kind": "event", "name": "op_cost",
         "attrs": {"pack": "dia", "bytes_per_apply": 9000}},
        {"kind": "counter", "name": "amgx_spmv_dispatch_total",
         "labels": {"pack": "dia/slices"}, "value": 3},
        {"kind": "counter", "name": "amgx_spmv_dispatch_total",
         "labels": {"pack": "dia/slices"}, "value": 2},
        {"kind": "counter", "name": "amgx_other", "value": 7},
    ]
    pb = deviceprof.pack_stats(records)
    assert pb == {"dia": 9000}          # biggest descriptor wins


def test_emit_round_trip_validates_and_counts(chrome_trace):
    with telemetry.capture() as cap:
        a = deviceprof.capture_anatomy(chrome_trace, records=[])
        deviceprof.emit(a)
    evs = [r for r in cap.records
           if r["kind"] == "event" and r["name"] == "device_anatomy"]
    assert len(evs) == 1
    # the event passes the exporter's schema validation verbatim
    telemetry.validate_record(
        {"kind": "event", "name": "device_anatomy", "seq": 1, "t": 0.0,
         "tid": 0, "sid": None, "attrs": evs[0]["attrs"]})
    # per-scope device seconds landed on the registered counter (the
    # per-scope values double-count nesting by design — the counter is
    # a per-scope tally, not a wall total)
    tot = cap.counter_total("amgx_device_time_seconds_total")
    assert tot == pytest.approx(sum(a["scopes"].values()))
    assert tot > 0


def test_validator_rejects_contract_violations():
    good = deviceprof.measure_anatomy({"traceEvents": []})
    rec = {"kind": "event", "name": "device_anatomy", "seq": 1,
           "t": 0.0, "tid": 0, "sid": None, "attrs": dict(good)}
    telemetry.validate_record(rec)
    bad = dict(good, scopes={"not/a/scope": 1.0})
    with pytest.raises(ValueError, match="violates"):
        telemetry.validate_record(dict(rec, attrs=bad))
    with pytest.raises(ValueError, match="measured"):
        telemetry.validate_record(
            dict(rec, attrs={k: v for k, v in good.items()
                             if k != "measured"}))


def test_emit_noop_when_disabled(chrome_trace):
    telemetry.disable()
    telemetry.clear()
    a = deviceprof.measure_anatomy(chrome_trace)
    deviceprof.emit(a)          # must not raise, must not record
    assert not [r for r in telemetry.records()
                if r.get("name") == "device_anatomy"]


def test_top_scopes(chrome_trace):
    a = deviceprof.measure_anatomy(chrome_trace)
    top = deviceprof.top_scopes(a, n=2)
    assert len(top) == 2
    assert top[0][1] >= top[1][1]
    names = [t[0] for t in top]
    assert "amgx/cycle/level0/pre_smooth" in names


# ------------------------------------------------- downstream consumers
def test_chrome_tracefile_draws_device_counter_track(tmp_path,
                                                     chrome_trace):
    with telemetry.capture():
        deviceprof.emit(deviceprof.measure_anatomy(chrome_trace))
        trace = telemetry.chrome_trace()
    telemetry.validate_chrome_trace(trace)
    tracks = [e for e in trace["traceEvents"]
              if e.get("ph") == "C"
              and str(e.get("name", "")).startswith("device_s ")]
    assert tracks, "device_anatomy event produced no counter track"
    assert any("amgx/cycle/level0/pre_smooth" in e["name"]
               for e in tracks)


def test_doctor_renders_device_anatomy(tmp_path, chrome_trace):
    from amgx_tpu.telemetry import doctor
    path = tmp_path / "trace.jsonl"
    telemetry.enable()
    try:
        telemetry.clear()
        a = deviceprof.measure_anatomy(
            chrome_trace, pack_bytes={"dia": 8000})
        deviceprof.emit(a)
        telemetry.dump_jsonl(str(path))
    finally:
        telemetry.disable()
        telemetry.clear()
    d = doctor.diagnose([str(path)])
    assert d["device"] is not None
    assert d["device"]["measured"] is True
    text = doctor.render(d)
    assert "Device anatomy" in text
    assert "dia/slices" in text
    # --diff against itself: device pairs present, no device drifts
    dd = doctor.diff(d, d)
    assert dd["device"] is not None
    assert not [x for x in dd["drifts"] if x.startswith("device time")]
    assert "device anatomy (A vs B" in doctor.render_diff(dd)


def test_overlap_shares_the_fixture(chrome_trace):
    """Satellite check: overlap.measure and the anatomy read the SAME
    synthetic capture consistently."""
    from amgx_tpu.telemetry import overlap
    m = overlap.measure(chrome_trace)
    assert m is not None
    assert m["overlap_fraction"] == pytest.approx(0.6)
    assert m["comm_s"] == pytest.approx(50e-6)
    assert m["compute_s"] == pytest.approx(310e-6)
    a = deviceprof.measure_anatomy(chrome_trace)
    # comm ops carry scopes in the fixture, so both comm slices are
    # attributed device time too
    assert a["krylov"]["reduce"] + a["dist"]["halo_exchange"] == \
        pytest.approx(m["comm_s"])


def test_peak_table_never_assumes_a_device():
    """One peak table keyed by device_kind: the v5e entry carries the
    published numbers, an unknown accelerator raises, the CPU has no
    device roofline (not measured)."""
    import types

    from amgx_tpu.telemetry import peaks
    v5e = peaks.peak("TPU v5 lite")
    assert (v5e.hbm_gbs, v5e.bf16_tflops, v5e.ici_gbs) == (819.0, 197.0,
                                                           200.0)
    with pytest.raises(peaks.UnknownDevice):
        peaks.device_peak(types.SimpleNamespace(platform="tpu",
                                                device_kind="TPU v9"))
    assert peaks.device_peak() is None       # this process is on the CPU
    a = deviceprof.measure_anatomy(
        {"traceEvents": []}, pack_bytes={"dia": 1})
    assert a["hbm_peak_gbs"] is None
