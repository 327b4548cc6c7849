"""Structured telemetry (amgx_tpu/telemetry/) + profiler/logging
satellites: span/event recording, metrics registry, exporters, solver
wiring, divergence bookkeeping, and the TimerMap / ProfilerTree /
level-gated-logging regressions."""
import io
import json

import numpy as np
import pytest
import scipy.sparse as sp

import amgx_tpu as amgx
from amgx_tpu import telemetry
from amgx_tpu.errors import SolveStatus
from amgx_tpu.utils import logging as amgx_logging
from amgx_tpu.utils import profiler as amgx_profiler

pytestmark = pytest.mark.telemetry


def poisson2d(n):
    I = sp.identity(n)
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    return sp.csr_matrix(sp.kron(I, T) + sp.kron(T, I))


AMG_CFG = (
    "config_version=2, solver(out)=PCG, out:max_iters=60, "
    "out:monitor_residual=1, out:tolerance=1e-8, "
    "out:convergence=RELATIVE_INI, "
    "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
    "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
    "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
    "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER")


@pytest.fixture
def clean_logging():
    yield
    amgx_logging.register_print_callback(None)
    amgx_logging.set_verbosity(3)


# ------------------------------------------------------------- satellites
def test_timermap_toc_without_tic_returns_zero():
    tm = amgx_profiler.TimerMap()
    amgx_profiler._TOC_WARNED = False
    with pytest.warns(RuntimeWarning, match="without a matching tic"):
        assert tm.toc("never_ticked") == 0.0
    # no aggregate entry was recorded for the phantom timer
    assert tm.get("never_ticked") == 0.0
    assert "never_ticked" not in tm._timers
    assert "never_ticked" not in tm.report()
    # warn-once: the second offence is silent
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert tm.toc("never_ticked") == 0.0
    # a real tic/toc still aggregates
    tm.tic("real")
    assert tm.toc("real") >= 0.0
    assert "real" in tm._timers


def test_profiler_scope_raising_body_keeps_stack_balanced():
    tree = amgx_profiler.ProfilerTree()
    with pytest.raises(RuntimeError):
        with tree.scope("outer"):
            raise RuntimeError("boom")
    assert len(tree._stack) == 1 and tree._stack[0] is tree.root
    assert tree.root.children["outer"].count == 1
    # the tree is reusable after the exception
    with tree.scope("outer"):
        pass
    assert tree.root.children["outer"].count == 2


def test_profiler_scope_annotation_failure_keeps_stack_balanced(
        monkeypatch):
    import jax

    class Boom:
        def __init__(self, name):
            pass

        def __enter__(self):
            raise RuntimeError("annotation enter failed")

        def __exit__(self, *a):
            return False

    tree = amgx_profiler.ProfilerTree()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Boom)
    with pytest.raises(RuntimeError, match="annotation enter failed"):
        with tree.scope("ann"):
            pass  # pragma: no cover - never reached
    assert len(tree._stack) == 1 and tree._stack[0] is tree.root
    # the failed enter never started the timer, so no count either
    assert tree.root.children["ann"].count == 0


def test_logging_level_gating(clean_logging):
    got = []
    amgx_logging.register_print_callback(got.append)
    amgx_logging.set_verbosity(1)
    amgx_logging.amgx_output("essential\n")            # level 1 default
    amgx_logging.amgx_output("table\n", level=2)       # gated away
    amgx_logging.amgx_output("debug\n", level=3)       # gated away
    assert got == ["essential\n"]
    amgx_logging.set_verbosity(2)
    amgx_logging.amgx_output("table\n", level=2)
    assert got == ["essential\n", "table\n"]
    amgx_logging.set_verbosity(0)
    amgx_logging.amgx_output("anything\n")
    assert got == ["essential\n", "table\n"]
    # error output is never gated
    amgx_logging.error_output("err\n")
    assert got[-1] == "err\n"


def test_verbosity_level_config_knob(clean_logging):
    """An explicit verbosity_level in the config drives the gated
    output stream (the registry default must not clobber a
    programmatically-set verbosity)."""
    got = []
    amgx_logging.register_print_callback(got.append)
    amgx_logging.set_verbosity(2)
    # default-valued config: the programmatic verbosity survives
    amgx.create_solver(amgx.AMGConfig(AMG_CFG))
    assert amgx_logging.get_verbosity() == 2
    # explicit knob: config wins
    amgx.create_solver(amgx.AMGConfig(
        AMG_CFG + ", out:verbosity_level=1"))
    assert amgx_logging.get_verbosity() == 1


def test_grid_stats_print_gated_at_level2(clean_logging):
    A = poisson2d(16)
    cfg = amgx.AMGConfig(AMG_CFG + ", amg:print_grid_stats=1")
    got = []
    amgx_logging.register_print_callback(got.append)
    amgx_logging.set_verbosity(1)
    amgx.create_solver(cfg).setup(amgx.Matrix(A))
    assert not any("Grid Complexity" in m for m in got)
    amgx_logging.set_verbosity(2)
    amgx.create_solver(cfg).setup(amgx.Matrix(A))
    assert any("Grid Complexity" in m for m in got)


# --------------------------------------------------------------- tentpole
def test_capture_records_full_solve_trace():
    """Acceptance: one AMG solve with telemetry on yields setup+solve
    spans, per-level hierarchy gauges, the SpMV pack-selection counter
    and per-iteration residual records."""
    A = poisson2d(24)
    cfg = amgx.AMGConfig(AMG_CFG + ", out:telemetry=1")
    with telemetry.capture() as cap:
        slv = amgx.create_solver(cfg)
        slv.setup(amgx.Matrix(A))
        res = slv.solve(np.ones(A.shape[0]))
    assert res.status == SolveStatus.SUCCESS
    # phase spans: one top-level setup per solver in the stack, one solve
    assert cap.spans("setup") and cap.spans("solve")
    assert all(s["dur"] >= 0 for s in cap.spans())
    # hierarchy gauges: rows/nnz per level + complexities
    levels = cap.gauge_last("amgx_hierarchy_levels")
    assert levels and levels >= 2
    rows = {r["labels"]["level"]: r["value"]
            for r in cap.metric_records("amgx_level_rows")}
    nnz = {r["labels"]["level"]: r["value"]
           for r in cap.metric_records("amgx_level_nnz")}
    assert set(rows) == set(range(int(levels))) == set(nnz)
    assert rows[0] == A.shape[0] and nnz[0] == A.nnz
    assert all(rows[i + 1] < rows[i] for i in range(int(levels) - 1))
    assert cap.gauge_last("amgx_operator_complexity") > 1.0
    assert cap.gauge_last("amgx_grid_complexity") > 1.0
    # SpMV pack-selection counter fired
    packs = cap.counter_totals("amgx_spmv_dispatch_total", label="pack")
    assert packs and sum(packs.values()) > 0
    # per-iteration residuals: initial + one per iteration, decreasing
    resid = cap.events("residual")
    assert len(resid) == res.iterations + 1
    assert [r["attrs"]["iteration"] for r in resid] == \
        list(range(res.iterations + 1))
    assert resid[-1]["attrs"]["norm"] < resid[0]["attrs"]["norm"]
    # solve summary gauges
    assert cap.gauge_last("amgx_solve_iterations") == res.iterations
    relres = cap.gauge_last("amgx_solve_final_relres")
    assert relres is not None and relres <= 1e-8
    assert 0 < cap.gauge_last("amgx_solve_convergence_rate") < 1
    assert cap.counter_total("amgx_solves_total", status="SUCCESS") == 1


def test_span_nesting_ids_are_consistent():
    with telemetry.capture() as cap:
        with telemetry.span("outer", label="x"):
            with telemetry.span("inner"):
                telemetry.event("ping", k=1)
    begins = {r["name"]: r for r in cap.kind("span_begin")}
    assert begins["inner"]["parent"] == begins["outer"]["sid"]
    assert begins["outer"]["attrs"] == {"label": "x"}
    (ping,) = cap.events("ping")
    assert ping["sid"] == begins["inner"]["sid"]
    ends = {r["name"]: r for r in cap.spans()}
    assert ends["outer"]["dur"] >= ends["inner"]["dur"] >= 0


def test_zero_overhead_when_off():
    """With telemetry off, instruments record nothing at all."""
    assert not telemetry.is_enabled()
    before = len(telemetry.records())
    reg_before = telemetry.registry().snapshot()
    A = poisson2d(12)
    slv = amgx.create_solver(amgx.AMGConfig(AMG_CFG))
    slv.setup(amgx.Matrix(A))
    slv.solve(np.ones(A.shape[0]))
    assert len(telemetry.records()) == before
    assert telemetry.registry().snapshot() == reg_before


def test_jsonl_roundtrip_and_schema(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    cfg = amgx.AMGConfig(AMG_CFG + f", out:telemetry=1, "
                         f"out:telemetry_path={path}")
    prev = telemetry.is_enabled()
    try:
        A = poisson2d(16)
        slv = amgx.create_solver(cfg)
        slv.setup(amgx.Matrix(A))
        res = slv.solve(np.ones(A.shape[0]))
    finally:
        if not prev:
            telemetry.disable()
    with open(path) as f:
        lines = f.readlines()
    n = telemetry.validate_jsonl(lines)
    assert n >= 10
    recs = [json.loads(l) for l in lines]
    assert recs[0]["kind"] == "meta" and \
        recs[0]["schema"] == telemetry.SCHEMA_VERSION
    kinds = {r["kind"] for r in recs}
    assert {"span_begin", "span_end", "event", "counter",
            "gauge"} <= kinds
    names = {r["name"] for r in recs}
    assert {"setup", "solve", "residual", "hierarchy",
            "amgx_spmv_dispatch_total", "amgx_level_rows"} <= names
    # incremental flush: a second solve appends, header not repeated
    telemetry.enable()
    try:
        slv.solve(np.ones(A.shape[0]))
    finally:
        if not prev:
            telemetry.disable()
    with open(path) as f:
        lines2 = f.readlines()
    assert len(lines2) > len(lines)
    assert telemetry.validate_jsonl(lines2) == len(lines2)
    assert sum(json.loads(l)["kind"] == "meta" for l in lines2) == 1


def test_validate_record_rejects_drift():
    good = {"kind": "event", "name": "x", "seq": 1, "t": 0.0, "tid": 1,
            "attrs": {}}
    telemetry.validate_record(good)
    for breaker in ({"kind": "nope"}, {"name": ""}, {"seq": None},
                    {"attrs": None}):
        bad = dict(good, **breaker)
        with pytest.raises(ValueError):
            telemetry.validate_record(bad)
    with pytest.raises(ValueError):
        telemetry.validate_record({"kind": "meta", "name": "amgx",
                                   "schema": -1})


def test_prometheus_snapshot_format():
    telemetry.reset()
    with telemetry.capture():
        telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia/slices")
        telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia/slices")
        telemetry.gauge_set("amgx_solve_iterations", 7)
        telemetry.hist_observe("amgx_solve_seconds", 0.25)
    text = telemetry.prometheus_text()
    assert "# TYPE amgx_spmv_dispatch_total counter" in text
    assert 'amgx_spmv_dispatch_total{pack="dia/slices"} 2.0' in text
    assert "# TYPE amgx_solve_iterations gauge" in text
    assert "amgx_solve_iterations 7.0" in text
    assert "# TYPE amgx_solve_seconds histogram" in text
    assert 'amgx_solve_seconds_bucket{le="0.5"} 1' in text
    assert "amgx_solve_seconds_count 1" in text
    assert "amgx_solve_seconds_sum 0.25" in text
    telemetry.reset()


def test_metric_names_are_registered():
    """Every metric an instrument emits must be in the versioned METRICS
    list (the names are a stable contract)."""
    A = poisson2d(16)
    with telemetry.capture() as cap:
        slv = amgx.create_solver(amgx.AMGConfig(
            AMG_CFG + ", out:telemetry=1"))
        slv.setup(amgx.Matrix(A))
        slv.solve(np.ones(A.shape[0]))
    for r in cap.metric_records():
        assert r["name"] in telemetry.METRICS, r["name"]


def test_capture_summary_aggregates():
    with telemetry.capture() as cap:
        with telemetry.span("phase"):
            telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia")
            telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia")
            telemetry.gauge_set("amgx_solve_iterations", 3)
    s = cap.summary()
    assert s["spans"]["phase"]["count"] == 1
    assert s["spans"]["phase"]["total_s"] >= 0
    assert s["counters"]["amgx_spmv_dispatch_total{pack=dia}"] == 2
    assert s["gauges"]["amgx_solve_iterations"] == 3.0


def test_capture_truncation_flag_and_scoped_ring_size():
    from amgx_tpu.telemetry import recorder
    size0 = recorder._STATE.ring_size
    with telemetry.capture(ring_size=8) as cap:
        for i in range(20):
            telemetry.event("tick", i=i)
    assert cap.truncated and len(cap.records) == 8
    assert recorder._STATE.ring_size == size0   # resize was scoped
    with telemetry.capture() as cap2:
        telemetry.event("tock")
    assert not cap2.truncated and len(cap2.records) == 1


def test_capture_restores_prior_state():
    assert not telemetry.is_enabled()
    with telemetry.capture():
        assert telemetry.is_enabled()
        with telemetry.capture():
            assert telemetry.is_enabled()
        assert telemetry.is_enabled()    # outer capture still active
    assert not telemetry.is_enabled()


def test_phase_metrics_are_toplevel_only():
    """One user-facing setup()/solve() must contribute exactly one
    sample to the phase histograms even though nested smoother/coarse
    solver setups re-enter Solver.setup (their spans still nest in the
    trace for the time breakdown)."""
    A = poisson2d(16)
    with telemetry.capture() as cap:
        slv = amgx.create_solver(amgx.AMGConfig(AMG_CFG))
        slv.setup(amgx.Matrix(A))
        slv.solve(np.ones(A.shape[0]))
    assert len(cap.metric_records("amgx_setup_seconds",
                                  kind="hist")) == 1
    assert len(cap.metric_records("amgx_solve_seconds",
                                  kind="hist")) == 1
    # the nested spans are still there, distinguished by the attr
    setups = {r["attrs"]["toplevel"] for r in cap.kind("span_begin")
              if r["name"] == "setup"}
    assert setups == {True, False}


def test_validate_jsonl_rejects_bare_nonfinite_tokens():
    meta = json.dumps({"kind": "meta", "name": "amgx-telemetry",
                       "schema": telemetry.SCHEMA_VERSION})
    bad = ('{"kind": "event", "name": "x", "seq": 1, "t": 0.0, '
           '"tid": 1, "attrs": {"norm": Infinity}}')
    with pytest.raises(ValueError, match="bare Infinity"):
        telemetry.validate_jsonl([meta, bad])


def test_level_gauges_cleared_on_shallower_rebuild():
    """A shallower re-setup must not leave the previous hierarchy's
    deeper level gauges dangling in the registry snapshot."""
    reg = telemetry.registry()
    A = poisson2d(24)
    with telemetry.capture():
        amgx.create_solver(amgx.AMGConfig(AMG_CFG)).setup(amgx.Matrix(A))
        deep = int(reg.get_gauge("amgx_hierarchy_levels"))
        assert deep >= 3
        assert reg.get_gauge("amgx_level_rows", level=deep - 1) is not None
        shallow_cfg = amgx.AMGConfig(
            AMG_CFG.replace("amg:max_levels=10", "amg:max_levels=2"))
        amgx.create_solver(shallow_cfg).setup(amgx.Matrix(A))
        assert int(reg.get_gauge("amgx_hierarchy_levels")) == 2
        assert reg.get_gauge("amgx_level_rows", level=0) is not None
        assert reg.get_gauge("amgx_level_rows", level=deep - 1) is None
        assert reg.get_gauge("amgx_level_nnz", level=deep - 1) is None


# ----------------------------------------------- divergence (satellite 4)
def test_divergence_history_status_and_event_agree(tmp_path):
    """solvers/base.py residual-history post-processing: a diverging
    Jacobi solve must truncate the history at the non-finite row, set
    the DIVERGED status via the non-finite check (RELATIVE_MAX's
    nrm_max filtering must survive the inf rows), and emit a telemetry
    divergence event that agrees with both."""
    path = str(tmp_path / "div.jsonl")
    # Jacobi iteration matrix has spectral radius 10 — the residual
    # grows 10x per sweep and overflows f64 to inf within ~310 sweeps
    A = sp.csr_matrix(np.array([[1.0, 10.0], [10.0, 1.0]]))
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=BLOCK_JACOBI, out:max_iters=400, "
        "out:monitor_residual=1, out:store_res_history=1, "
        "out:tolerance=1e-10, out:convergence=RELATIVE_MAX, "
        "out:relaxation_factor=1.0, out:telemetry=1, "
        f"out:telemetry_path={path}")
    with telemetry.capture() as cap:
        slv = amgx.create_solver(cfg)
        slv.setup(amgx.Matrix(A))
        res = slv.solve(np.ones(2))
    assert res.status == SolveStatus.DIVERGED
    assert not np.all(np.isfinite(res.residual_norm))
    h = np.atleast_2d(res.residual_history)
    # truncated to iterations actually run (+ the initial residual row)
    assert h.shape[0] == res.iterations + 1
    assert res.iterations < 400            # stopped at the overflow
    assert not np.all(np.isfinite(h[-1]))  # last row is the blow-up
    assert np.all(np.isfinite(h[:-1]))     # every earlier row is finite
    (div,) = cap.events("divergence")
    assert div["attrs"]["iteration"] == res.iterations
    assert not np.isfinite(div["attrs"]["norm"])
    assert cap.counter_total("amgx_solve_diverged_total") == 1
    assert cap.counter_total("amgx_solves_total", status="DIVERGED") == 1
    # residual trail matches the history row count
    assert len(cap.events("residual")) == res.iterations + 1
    # the trace file stays STRICT JSON despite the inf norms: non-finite
    # floats are written as string tokens, never bare NaN/Infinity
    def no_bare_const(s):
        raise AssertionError(f"bare {s} token in the JSONL trace")
    with open(path) as f:
        lines = f.readlines()
    recs = [json.loads(l, parse_constant=no_bare_const) for l in lines]
    assert telemetry.validate_jsonl(lines) == len(lines)
    div_recs = [r for r in recs if r["kind"] == "event"
                and r["name"] == "divergence"]
    assert div_recs and div_recs[0]["attrs"]["norm"] == "Infinity"


def test_validate_jsonl_multi_session_append():
    """A file appended by two processes holds one meta header per
    session and seq restarts after each — the validator accepts it."""
    meta = json.dumps({"kind": "meta", "name": "amgx-telemetry",
                       "schema": telemetry.SCHEMA_VERSION})

    def ev(seq):
        return json.dumps({"kind": "event", "name": "x", "seq": seq,
                           "t": 0.0, "tid": 1, "attrs": {}})

    assert telemetry.validate_jsonl(
        [meta, ev(4), ev(5), meta, ev(1), ev(2)]) == 6
    # within one session, seq must still increase
    with pytest.raises(ValueError, match="seq not increasing"):
        telemetry.validate_jsonl([meta, ev(5), ev(1)])


# ------------------------------------ PR 3: observability layer tests
def test_prometheus_label_escaping():
    """Label values with backslash, double-quote and newline must be
    escaped per the text exposition format or the series line is
    unparseable (regression for the exporter's raw f-string)."""
    telemetry.reset()
    with telemetry.capture():
        telemetry.counter_inc("amgx_spmv_dispatch_total",
                              pack='we\\ird"pack\nname')
    text = telemetry.prometheus_text()
    assert 'pack="we\\\\ird\\"pack\\nname"' in text
    # the rendered text stays line-parseable: no raw newline inside a
    # label value (every line is either a comment or name{...} value)
    for line in text.splitlines():
        assert line.startswith("#") or " " in line
    telemetry.reset()


def test_ring_overflow_dropped_counter(tmp_path):
    """The recorder counts evicted records; flush surfaces the drop as
    a ring_overflow event; the doctor reports the truncation."""
    telemetry.reset()
    path = str(tmp_path / "overflow.jsonl")
    with telemetry.capture(ring_size=8) as cap:
        for i in range(20):
            telemetry.event("tick", i=i)
        assert telemetry.dropped_count() == 12
        telemetry.flush_jsonl(path)
    assert cap.dropped >= 12 and cap.truncated
    with open(path) as f:
        lines = f.readlines()
    assert telemetry.validate_jsonl(lines) == len(lines)
    recs = [json.loads(l) for l in lines]
    (meta,) = [r for r in recs if r["kind"] == "meta"]
    assert meta["dropped"] >= 12            # surfaced in flush output
    ov = [r for r in recs if r["kind"] == "event"
          and r["name"] == "ring_overflow"]
    assert ov and ov[0]["attrs"]["dropped"] >= 12
    assert ov[0]["attrs"]["ring_size"] == 8
    from amgx_tpu.telemetry import doctor
    d = doctor.diagnose([path])
    assert d["dropped_records"] >= 12
    assert any("truncated" in h for h in d["hints"])
    assert "DROPPED" in doctor.render(d)
    telemetry.reset()


def test_meta_header_identifies_session(tmp_path):
    """Session meta headers carry the process/session identity and the
    paired clock sample that make multi-process aggregation and
    Chrome-trace alignment well-defined."""
    import os as _os
    path = str(tmp_path / "meta.jsonl")
    with telemetry.capture():
        telemetry.event("ping")
        telemetry.dump_jsonl(path)
    meta = json.loads(open(path).readline())
    assert meta["pid"] == _os.getpid()
    assert isinstance(meta["session"], str) and meta["session"]
    assert isinstance(meta["t_perf"], float)
    assert isinstance(meta["t_unix"], float)
    assert meta["t_unix"] > 1e9             # a real wall-clock sample
    assert telemetry.validate_jsonl(open(path).readlines()) == 2


def test_chrome_trace_export_structure():
    """Spans become complete (X) slices with the begin attrs as args,
    events become instants, counters become running-sum counter
    tracks — and the whole thing validates structurally."""
    with telemetry.capture() as cap:
        with telemetry.span("outer", phase="setup"):
            with telemetry.span("inner"):
                telemetry.event("mark", k=1)
        telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia")
        telemetry.counter_inc("amgx_spmv_dispatch_total", pack="dia")
    trace = telemetry.chrome_trace(cap.records)
    n = telemetry.validate_chrome_trace(trace)
    assert n == len(trace["traceEvents"])
    by_ph = {}
    for e in trace["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    xs = {e["name"]: e for e in by_ph["X"]}
    assert xs["outer"]["args"] == {"phase": "setup"}
    assert xs["outer"]["dur"] >= xs["inner"]["dur"] >= 0
    # nesting preserved on the timeline
    assert xs["outer"]["ts"] <= xs["inner"]["ts"]
    (mark,) = [e for e in by_ph["i"] if e["name"] == "mark"]
    assert mark["args"] == {"k": 1}
    ctr = [e for e in by_ph["C"]
           if e["name"] == "amgx_spmv_dispatch_total{pack=dia}"]
    assert [e["args"]["value"] for e in ctr] == [1, 2]   # running sum
    assert json.loads(json.dumps(trace, allow_nan=False))


def test_chrome_trace_from_multi_session_file(tmp_path):
    """A JSONL file holding two sessions renders one process track per
    session (pid from the meta header)."""
    path = str(tmp_path / "two.jsonl")
    with telemetry.capture() as cap:
        with telemetry.span("work"):
            pass
    telemetry.dump_jsonl(path, cap.records)
    # second session: same records, another pid (simulating rank 1)
    lines = open(path).readlines()
    meta2 = json.loads(lines[0])
    meta2["pid"] = meta2["pid"] + 1
    meta2["session"] = "deadbeef0002"
    with open(path, "a") as f:
        f.write(json.dumps(meta2) + "\n")
        for l in lines[1:]:
            f.write(l)
    trace = telemetry.chrome_trace(path)
    telemetry.validate_chrome_trace(trace)
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert len(pids) == 2
    procs = [e for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert len(procs) == 2


def test_aggregate_sessions_roundtrip(tmp_path):
    """aggregate_sessions merges multi-session JSONL (separate files
    AND one concatenated file) into one view: counter sums, span
    totals, record counts all mesh-wide."""
    p1 = str(tmp_path / "rank0.jsonl")
    p2 = str(tmp_path / "rank1.jsonl")
    with telemetry.capture() as c1:
        with telemetry.span("solve"):
            telemetry.counter_inc("amgx_halo_bytes_total", 100.0,
                                  ring=1, op="dist_spmv")
    telemetry.dump_jsonl(p1, c1.records)
    with telemetry.capture() as c2:
        with telemetry.span("solve"):
            telemetry.counter_inc("amgx_halo_bytes_total", 40.0,
                                  ring=1, op="dist_spmv")
        telemetry.event("residual", iteration=0, norm=1.0)
    telemetry.dump_jsonl(p2, c2.records)

    agg = telemetry.aggregate_sessions([p1, p2])
    assert agg["n_sessions"] == 2
    assert agg["n_records"] == len(c1.records) + len(c2.records)
    key = ("amgx_halo_bytes_total",
           (("op", "dist_spmv"), ("ring", 1)))
    assert agg["counters"][key] == 140.0
    assert agg["spans"]["solve"]["count"] == 2
    assert agg["events"]["residual"] == 1

    # concatenated single-file layout (what a shared telemetry_path
    # appended by two processes produces) aggregates identically
    cat = str(tmp_path / "both.jsonl")
    with open(cat, "w") as f:
        f.write(open(p1).read())
        f.write(open(p2).read())
    agg2 = telemetry.aggregate_sessions(cat)
    assert agg2["n_sessions"] == 2
    assert agg2["counters"][key] == 140.0
    # sessions keep their identity (meta headers round-trip)
    assert [s["meta"]["session"] for s in agg2["sessions"]] == \
        [s["meta"]["session"] for s in agg["sessions"]]


def test_costmodel_descriptors():
    """Static cost descriptors: bytes/FLOPs per apply and padding waste
    for the dia and ell packs, plus the rollup and roofline helpers."""
    from amgx_tpu.core.matrix import pack_device, padded_entries
    from amgx_tpu.telemetry import costmodel

    A = poisson2d(16)                       # 256 rows, 5-pt: 5 diagonals
    Ad = pack_device(A, 1, np.float64)
    assert Ad.fmt == "dia"
    assert padded_entries(Ad) == 5 * 256
    c = costmodel.spmv_cost(Ad, nnz=A.nnz)
    assert c["pack"] == "dia" and not c["estimated"]
    assert c["flops_per_apply"] == 2 * A.nnz
    assert c["bytes_per_apply"] == (5 + 2) * 256 * 8
    assert c["padding_waste"] == pytest.approx(5 * 256 / A.nnz,
                                               abs=1e-4)

    Ae = pack_device(A, 1, np.float64, dia_max_diags=0)   # force ELL
    assert Ae.fmt == "ell"
    ce = costmodel.spmv_cost(Ae, nnz=A.nnz)
    K = Ae.ell_width
    assert ce["padded_entries"] == 256 * K
    assert ce["bytes_per_apply"] == \
        256 * K * 8 + 256 * K * 4 + 2 * 256 * 8
    # estimated when nnz unknown: waste reads 1.0 against the slots
    assert costmodel.spmv_cost(Ae)["estimated"]

    roll = costmodel.hierarchy_cost([c, ce])
    assert roll["total_bytes_per_cycle"] == \
        c["bytes_per_apply"] + ce["bytes_per_apply"]
    assert roll["total_flops_per_cycle"] == 2 * 2 * A.nnz
    gbs = costmodel.achieved_gbs(c["bytes_per_apply"], 1e-6)
    assert gbs == pytest.approx(c["bytes_per_apply"] / 1e-6 / 1e9)
    assert costmodel.roofline_fraction(409.5, 819.0) == \
        pytest.approx(0.5)


def test_costmodel_halo_formulas_match_partition():
    """Halo wire bytes / useful entries from the pack metadata equal
    the analytic boundary sizes of the partition (no mesh needed —
    duck-typed pack)."""
    import types

    import scipy.sparse as _sp

    from amgx_tpu.distributed.partition import build_partition
    from amgx_tpu.io import poisson5pt
    from amgx_tpu.telemetry import costmodel

    A = _sp.csr_matrix(poisson5pt(8, 8))
    part = build_partition(A, 4)
    fake = types.SimpleNamespace(
        n_parts=4, block_dim=1, dtype=np.float64,
        send_idx=part.send_idx, halo_src=part.halo_src,
        dists=part.dists,
        send_idx2=part.rings[1].send_idx,
        halo_src2=part.rings[1].halo_src, dists2=part.rings[1].dists,
        halo_counts=tuple(int(c) for c in part.halo_count),
        halo_counts2=tuple(int(c) for c in part.rings[1].halo_count))
    assert costmodel.halo_entries(fake, ring=1) == \
        int(sum(part.halo_count))
    B = part.send_idx.shape[1]
    hops = len(part.dists)
    assert costmodel.halo_wire_bytes(fake, ring=1) == \
        4 * hops * B * 8
    # ring 2 reads its own maps
    assert costmodel.halo_entries(fake, ring=2) == \
        int(sum(part.rings[1].halo_count))


def test_op_cost_event_emitted_once_per_operator():
    from amgx_tpu.ops.spmv import spmv
    import jax.numpy as jnp

    from amgx_tpu.core.matrix import pack_device
    A = poisson2d(12)
    Ad = pack_device(A, 1, np.float64)
    x = jnp.ones(A.shape[0])
    with telemetry.capture() as cap:
        spmv(Ad, x)
        spmv(Ad, x)          # same operator: no second op_cost event
    evs = cap.events("op_cost")
    assert len(evs) == 1
    a = evs[0]["attrs"]
    assert a["pack"] == "dia" and a["bytes_per_apply"] > 0
    assert cap.counter_total("amgx_spmv_dispatch_total",
                             pack="dia/slices") == 2


def test_doctor_detects_residual_plateau(tmp_path):
    """A synthesized trace whose residual stops decreasing earns the
    plateau hint; a healthy one does not."""
    from amgx_tpu.telemetry import doctor

    def trace_with(norms, path):
        with telemetry.capture() as cap:
            for i, n in enumerate(norms):
                telemetry.event("residual", iteration=i, norm=n)
        telemetry.dump_jsonl(path, cap.records)

    stuck = str(tmp_path / "stuck.jsonl")
    trace_with([1.0, 0.5, 0.25] + [0.2 * 0.999 ** i for i in range(12)],
               stuck)
    d = doctor.diagnose([stuck])
    assert d["convergence"]["plateau"] is not None
    assert any("plateau" in h for h in d["hints"])

    healthy = str(tmp_path / "ok.jsonl")
    trace_with([10.0 ** -i for i in range(10)], healthy)
    d2 = doctor.diagnose([healthy])
    assert d2["convergence"]["plateau"] is None
    assert not any("plateau" in h for h in d2["hints"])


def test_doctor_reports_binned_budget_fallback(tmp_path):
    """The pallas_csr plan rejection event turns into the concrete
    'over padding budget by N×' doctor hint."""
    from amgx_tpu.telemetry import doctor
    with telemetry.capture() as cap:
        telemetry.event("binned_plan_rejected", rows=5000, nnz=10000,
                        padded=210000, pad_cap=10.0, over_budget=2.1)
    path = str(tmp_path / "rej.jsonl")
    telemetry.dump_jsonl(path, cap.records)
    d = doctor.diagnose([path])
    assert any("over padding budget by 2.1×" in h for h in d["hints"])


def test_doctor_cli_main(tmp_path, capsys):
    """`python -m amgx_tpu.telemetry.doctor` entry: report on stdout,
    usage error without args, --json machine output."""
    from amgx_tpu.telemetry import doctor
    path = str(tmp_path / "t.jsonl")
    with telemetry.capture() as cap:
        with telemetry.span("solve"):
            telemetry.event("residual", iteration=0, norm=1.0)
    telemetry.dump_jsonl(path, cap.records)
    assert doctor.main([path]) == 0
    out = capsys.readouterr().out
    assert "amgx solve doctor" in out
    assert doctor.main([path, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["records"] == len(cap.records)
    assert doctor.main([]) == 2


def test_distributed_instruments_are_noop_when_off():
    """The halo-exchange instruments are one-attribute-check no-ops on
    a disabled recorder (acceptance criterion)."""
    import types

    from amgx_tpu.distributed import matrix as dmat
    assert not telemetry.is_enabled()
    before = len(telemetry.records())
    reg_before = telemetry.registry().snapshot()
    # a pack stub that would CRASH if the gated body ran
    dmat._tel_exchange(types.SimpleNamespace(), 1, "dist_spmv")
    dmat._tel_dist_spmv(types.SimpleNamespace())
    assert len(telemetry.records()) == before
    assert telemetry.registry().snapshot() == reg_before


# ------------------------------------------------------------------- capi
def test_capi_time_getters():
    from amgx_tpu import capi
    from amgx_tpu.errors import RC
    rc, cfgh = capi.AMGX_config_create(
        AMG_CFG + ", out:store_res_history=1")
    assert rc == RC.OK
    rc, rsrc = capi.AMGX_resources_create_simple(cfgh)
    rc, mtx = capi.AMGX_matrix_create(rsrc, "hDDI")
    rc, slvh = capi.AMGX_solver_create(rsrc, "hDDI", cfgh)
    A = poisson2d(16)
    n = A.shape[0]
    assert capi.AMGX_matrix_upload_all(
        mtx, n, A.nnz, 1, 1, A.indptr, A.indices, A.data) == RC.OK
    rc, t = capi.AMGX_solver_get_solve_time(slvh)
    assert rc == RC.OK and t == 0.0
    rc, rhs = capi.AMGX_vector_create(rsrc, "hDDI")
    rc, sol = capi.AMGX_vector_create(rsrc, "hDDI")
    capi.AMGX_vector_upload(rhs, n, 1, np.ones(n))
    capi.AMGX_vector_set_zero(sol, n, 1)
    assert capi.AMGX_solver_setup(slvh, mtx) == RC.OK
    assert capi.AMGX_solver_solve(slvh, rhs, sol) == RC.OK
    rc, t_setup = capi.AMGX_solver_get_setup_time(slvh)
    assert rc == RC.OK and t_setup > 0.0
    rc, t_solve = capi.AMGX_solver_get_solve_time(slvh)
    assert rc == RC.OK and t_solve > 0.0
    rc, snap = capi.AMGX_solver_get_telemetry_snapshot(slvh)
    assert rc == RC.OK and isinstance(snap, str)
