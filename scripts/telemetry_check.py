#!/usr/bin/env python
"""Telemetry schema smoke check.

Runs one AMG solve with telemetry enabled, writes the JSONL trace, and
validates every record against the documented schema
(``amgx_tpu.telemetry.export.validate_record`` — the same authority the
tests use).  Exits nonzero on any drift: a missing required span, a
record that stopped validating, a metric name that left the versioned
``METRICS`` list.  Cheap enough for CI (runs on CPU in seconds).

Usage: python scripts/telemetry_check.py [trace.jsonl]
       (default: a temp file, removed on success)
"""
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fail(msg: str):
    print(f"telemetry_check: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    import numpy as np
    import scipy.sparse as sp

    import amgx_tpu as amgx
    from amgx_tpu import telemetry

    keep = len(sys.argv) > 1
    if keep:
        path = sys.argv[1]
    else:
        fd, path = tempfile.mkstemp(suffix=".jsonl",
                                    prefix="amgx_telemetry_")
        os.close(fd)
        os.unlink(path)     # solver appends; start from nothing

    n = 24
    I = sp.identity(n)
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    A = sp.csr_matrix(sp.kron(I, T) + sp.kron(T, I))

    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        f"out:telemetry=1, out:telemetry_path={path}")
    slv = amgx.create_solver(cfg)
    slv.setup(amgx.Matrix(A))
    res = slv.solve(np.ones(A.shape[0]))
    if int(res.status) != 0:
        fail(f"smoke solve did not converge (status {res.status})")

    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        fail(f"trace file was not written: {e}")

    # 1. every record validates, header first, seq strictly increasing
    try:
        n_rec = telemetry.validate_jsonl(lines)
    except (ValueError, json.JSONDecodeError) as e:
        fail(str(e))
    recs = [json.loads(l) for l in lines if l.strip()]

    # 2. metric names are the versioned contract
    for r in recs:
        if r["kind"] in ("counter", "gauge", "hist") and \
                r["name"] not in telemetry.METRICS:
            fail(f"unregistered metric name {r['name']!r} "
                 "(update telemetry.METRICS and the README list)")

    # 3. required content of a telemetry=1 AMG solve
    names_by_kind = {}
    for r in recs:
        names_by_kind.setdefault(r["kind"], set()).add(r["name"])
    for kind, name in (("span_end", "setup"), ("span_end", "solve"),
                       ("event", "hierarchy"), ("event", "residual"),
                       ("counter", "amgx_spmv_dispatch_total"),
                       ("gauge", "amgx_level_rows"),
                       ("gauge", "amgx_level_nnz"),
                       ("gauge", "amgx_operator_complexity"),
                       ("gauge", "amgx_grid_complexity"),
                       ("gauge", "amgx_solve_iterations"),
                       ("gauge", "amgx_solve_final_relres")):
        if name not in names_by_kind.get(kind, ()):
            fail(f"trace is missing required {kind} {name!r}")

    # 4. span begin/end pairing balances per sid
    open_sids = set()
    for r in recs:
        if r["kind"] == "span_begin":
            open_sids.add(r["sid"])
        elif r["kind"] == "span_end":
            if r["sid"] not in open_sids:
                fail(f"span_end without begin: sid {r['sid']}")
            open_sids.remove(r["sid"])
    if open_sids:
        fail(f"unclosed spans: sids {sorted(open_sids)}")

    # 5. residual trail is consistent with the reported iterations
    resid = [r for r in recs if r["kind"] == "event"
             and r["name"] == "residual"]
    if len(resid) != res.iterations + 1:
        fail(f"{len(resid)} residual records for {res.iterations} "
             "iterations (+1 initial expected)")

    # 6. the Prometheus snapshot renders
    text = telemetry.prometheus_text()
    if "amgx_spmv_dispatch_total" not in text or "# TYPE" not in text:
        fail("prometheus snapshot is missing expected series")

    # 7. the Chrome-trace export is structurally valid trace-event JSON
    # (one process track, spans as X slices, counters as C tracks) and
    # survives a strict-JSON round trip — what Perfetto actually loads
    trace = telemetry.chrome_trace(path)
    try:
        n_ev = telemetry.validate_chrome_trace(trace)
    except ValueError as e:
        fail(f"chrome trace: {e}")
    phases = {e["ph"] for e in trace["traceEvents"]}
    if not {"X", "i", "C", "M"} <= phases:
        fail(f"chrome trace is missing event phases: {phases}")
    names = {e["name"] for e in trace["traceEvents"]}
    if "setup" not in names or "solve" not in names:
        fail("chrome trace is missing the setup/solve slices")
    json.loads(json.dumps(trace, allow_nan=False))   # strict round trip

    # 8. the solve doctor ingests the trace and reports the sections the
    # acceptance criteria name (phase breakdown, cost model, packs)
    from amgx_tpu.telemetry import doctor
    diag = doctor.diagnose([path])
    for key, cond in (("phases", bool(diag["phases"])),
                      ("packs", bool(diag["packs"])),
                      ("levels", bool(diag["levels"])),
                      ("records", diag["records"] == n_rec - 1)):
        if not cond:
            fail(f"doctor diagnosis missing/inconsistent: {key}")
    report = doctor.render(diag)
    for section in ("phase breakdown", "hierarchy cost model",
                    "SpMV pack choices", "convergence"):
        if section not in report:
            fail(f"doctor report is missing the {section!r} section")

    # 9. convergence forensics (forensics=1): the instrumented cycle
    # emits schema-valid cycle-anatomy events, the probes fire, and the
    # doctor's convergence + diff sections render from them
    telemetry.reset()
    telemetry.disable()
    path_f = path + ".forensics"
    if os.path.exists(path_f):
        os.unlink(path_f)
    cfg_f = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        f"forensics=1, out:telemetry=1, out:telemetry_path={path_f}")
    slv_f = amgx.create_solver(cfg_f)
    slv_f.setup(amgx.Matrix(A))
    res_f = slv_f.solve(np.ones(A.shape[0]))
    if int(res_f.status) != 0:
        fail(f"forensics smoke solve did not converge ({res_f.status})")
    with open(path_f) as f:
        lines_f = f.readlines()
    try:
        telemetry.validate_jsonl(lines_f)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"forensics trace: {e}")
    recs_f = [json.loads(l) for l in lines_f if l.strip()]
    ev_names = {r["name"] for r in recs_f if r["kind"] == "event"}
    for name in ("cycle_level", "cycle_coarse", "forensics_probe",
                 "solve_forensics"):
        if name not in ev_names:
            fail(f"forensics trace is missing {name!r} events")
    for r in recs_f:
        if r["kind"] == "event" and r["name"] == "cycle_level":
            a = r["attrs"]
            if not all(isinstance(a.get(k), (int, float))
                       for k in ("entry", "pre", "coarse", "post")):
                fail(f"cycle_level event missing cut-point norms: {a}")
    diag_f = doctor.diagnose([path_f])
    fr = diag_f.get("forensics")
    if not fr or not fr.get("levels") or fr.get("weakest") is None:
        fail("doctor forensics section is empty for a forensics trace")
    report_f = doctor.render(diag_f)
    for section in ("convergence forensics", "hierarchy quality probes",
                    "weakest component"):
        if section not in report_f:
            fail(f"doctor report is missing the {section!r} "
                 "forensics section")
    dd = doctor.diff(diag_f, diag_f)
    report_d = doctor.render_diff(dd)
    for section in ("convergence (A vs B)", "cycle anatomy"):
        if section not in report_d:
            fail(f"doctor diff report is missing {section!r}")
    import contextlib
    import io
    with contextlib.redirect_stdout(io.StringIO()) as diff_out:
        rc_diff = doctor.main([path_f, "--diff", path_f])
    if rc_diff != 0 or "convergence diff" not in diff_out.getvalue():
        fail("doctor --diff CLI failed")

    # 10. setup profiler (setup_profile=1): the trace carries
    # schema-valid setup_phase/setup_profile events, the attribution
    # covers most of the setup wall, and the doctor "setup" section
    # renders with the execute/compile/transfer/host split
    telemetry.reset()
    telemetry.disable()
    telemetry.setup_profile.disable()
    path_s = path + ".setup_profile"
    if os.path.exists(path_s):
        os.unlink(path_s)
    cfg_s = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL, "
        "amg:selector=PMIS, amg:interpolator=D1, amg:max_iters=1, "
        "amg:max_levels=10, amg:smoother(sm)=JACOBI_L1, "
        "sm:max_iters=1, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER, setup_profile=1, "
        f"out:telemetry=1, out:telemetry_path={path_s}")
    slv_s = amgx.create_solver(cfg_s)
    slv_s.setup(amgx.Matrix(A))
    slv_s.solve(np.ones(A.shape[0]))
    with open(path_s) as f:
        lines_s = f.readlines()
    try:
        telemetry.validate_jsonl(lines_s)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"setup-profile trace: {e}")
    recs_s = [json.loads(l) for l in lines_s if l.strip()]
    ev_s = {r["name"] for r in recs_s if r["kind"] == "event"}
    for name in ("setup_phase", "setup_profile"):
        if name not in ev_s:
            fail(f"setup-profile trace is missing {name!r} events")
    comps = {r["attrs"]["component"] for r in recs_s
             if r["kind"] == "event" and r["name"] == "setup_phase"}
    for comp in ("rap", "upload", "smoother_setup", "coarse_solver"):
        if comp not in comps:
            fail(f"setup-profile trace is missing the {comp!r} phase "
                 f"(saw: {sorted(comps)})")
    diag_s = doctor.diagnose([path_s])
    setup = diag_s.get("setup")
    if not setup or not setup.get("phases"):
        fail("doctor setup section is empty for a setup_profile trace")
    cov = (setup.get("summary") or {}).get("coverage")
    if not isinstance(cov, (int, float)) or cov < 0.5:
        fail(f"setup attribution coverage too low: {cov}")
    report_s = doctor.render(diag_s)
    if "setup attribution" not in report_s:
        fail("doctor report is missing the setup attribution section")
    for word in ("compile", "transfer", "execute", "host"):
        if word not in report_s:
            fail(f"setup attribution split is missing {word!r}")
    telemetry.setup_profile.disable()

    # 11. device setup engine (device_setup=1): the trace carries the
    # schema-valid device_rap/spgemm setup phases, the RAP path counter
    # splits device vs host, and a forced fallback emits a schema-valid
    # device_setup_fallback event the doctor surfaces with its reason
    telemetry.reset()
    telemetry.disable()
    telemetry.setup_profile.disable()
    from amgx_tpu.amg.device_setup import reset_engine
    reset_engine()
    path_d = path + ".device_setup"
    if os.path.exists(path_d):
        os.unlink(path_d)
    cfg_d = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL, "
        "amg:selector=PMIS, amg:interpolator=D1, amg:max_iters=1, "
        "amg:max_levels=10, amg:smoother(sm)=JACOBI_L1, "
        "sm:max_iters=1, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER, setup_profile=1, "
        "device_setup=1, device_setup_min_rows=0, "
        f"out:telemetry=1, out:telemetry_path={path_d}")
    slv_d = amgx.create_solver(cfg_d)
    slv_d.setup(amgx.Matrix(A))
    slv_d.solve(np.ones(A.shape[0]))
    with open(path_d) as f:
        lines_d = f.readlines()
    try:
        telemetry.validate_jsonl(lines_d)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"device-setup trace: {e}")
    recs_d = [json.loads(l) for l in lines_d if l.strip()]
    comps_d = {r["attrs"]["component"] for r in recs_d
               if r["kind"] == "event" and r["name"] == "setup_phase"}
    for comp in ("device_rap", "spgemm"):
        if comp not in comps_d:
            fail(f"device-setup trace is missing the {comp!r} phase "
                 f"(saw: {sorted(comps_d)})")
    rap_paths = {lbl for r in recs_d if r["kind"] == "counter"
                 and r["name"] == "amgx_device_rap_total"
                 for lbl in [r["labels"].get("path")]}
    if "device" not in rap_paths:
        fail(f"no device-path RAP counted (paths: {sorted(rap_paths)})")
    # forced fallback: a min-rows gate above the fine grid keeps every
    # level on host and must leave an auditable reason
    telemetry.reset()
    telemetry.disable()
    telemetry.setup_profile.disable()
    path_d2 = path_d + ".fallback"
    if os.path.exists(path_d2):
        os.unlink(path_d2)
    cfg_d2 = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL, "
        "amg:selector=PMIS, amg:interpolator=D1, amg:max_iters=1, "
        "amg:max_levels=10, amg:smoother(sm)=JACOBI_L1, "
        "sm:max_iters=1, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER, setup_profile=1, "
        "device_setup=1, device_setup_min_rows=100000000, "
        f"out:telemetry=1, out:telemetry_path={path_d2}")
    slv_d2 = amgx.create_solver(cfg_d2)
    slv_d2.setup(amgx.Matrix(A))
    slv_d2.solve(np.ones(A.shape[0]))
    with open(path_d2) as f:
        lines_d2 = f.readlines()
    try:
        telemetry.validate_jsonl(lines_d2)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"device-setup fallback trace: {e}")
    recs_d2 = [json.loads(l) for l in lines_d2 if l.strip()]
    fb = [r["attrs"] for r in recs_d2 if r["kind"] == "event"
          and r["name"] == "device_setup_fallback"]
    if not fb or not all(a.get("reason") == "small" for a in fb):
        fail(f"expected 'small' fallback events, saw: {fb[:3]}")
    diag_d2 = doctor.diagnose([path_d2])
    if not diag_d2.get("setup_fallbacks"):
        fail("doctor diagnosis is missing setup_fallbacks")
    if "device setup fallbacks" not in doctor.render(diag_d2):
        fail("doctor report is missing the device setup fallbacks "
             "section")
    telemetry.setup_profile.disable()

    # 12. live serving observability (ISSUE 9): a live SolveService
    # with telemetry on emits schema-valid request_trace/slo_window
    # events, its /metrics + /healthz endpoint answers while it
    # serves, and the doctor's SLO section renders from the trace
    telemetry.reset()
    telemetry.disable()
    import urllib.request

    from amgx_tpu.serve.service import SolveService
    path_o = path + ".serve_obs"
    if os.path.exists(path_o):
        os.unlink(path_o)
    cfg_o = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        "serve_workers=2, serve_batch_window_ms=2, "
        "slo_latency_ms=60000, slo_target=0.99, "
        f"out:telemetry=1, out:telemetry_path={path_o}")
    svc = SolveService(cfg_o)
    try:
        url = svc.start_endpoint(0)   # ephemeral port, loopback only
        mo = amgx.Matrix(A)
        import numpy as _np
        pend = [svc.submit(mo, _np.ones(A.shape[0])) for _ in range(6)]
        for p in pend:
            if p.wait(timeout=120.0) is None:
                fail(f"serving smoke request failed: rc={p.rc} "
                     f"{p.error}")
        st = svc.stats()              # publishes amgx_slo_* + slo_window
        if st["slo"]["attainment"] != 1.0:
            fail(f"serving smoke attainment != 1.0: {st['slo']}")
        if not st["phase_split"].get("solve", {}).get("count"):
            fail(f"phase split missing solve: {st['phase_split']}")
        mtxt = urllib.request.urlopen(url + "/metrics",
                                      timeout=10).read().decode()
        for name in ("amgx_serve_phase_seconds", "amgx_slo_attainment",
                     "amgx_serve_batch_size"):
            if name not in mtxt:
                fail(f"/metrics scrape is missing {name!r}")
        hz = json.loads(urllib.request.urlopen(url + "/healthz",
                                               timeout=10).read())
        for key in ("ok", "accepting", "queue_depth", "queue_capacity",
                    "inflight", "overloaded", "slo_attainment"):
            if key not in hz:
                fail(f"/healthz is missing {key!r}: {hz}")
        if hz["overloaded"] or not hz["accepting"]:
            fail(f"idle service reads unhealthy: {hz}")
        telemetry.flush_jsonl(path_o)
    finally:
        svc.shutdown()
    with open(path_o) as f:
        lines_o = f.readlines()
    try:
        telemetry.validate_jsonl(lines_o)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"serving trace: {e}")
    recs_o = [json.loads(l) for l in lines_o if l.strip()]
    traces = [r["attrs"] for r in recs_o if r["kind"] == "event"
              and r["name"] == "request_trace"]
    if len(traces) < 6:
        fail(f"expected >= 6 request_trace events, saw {len(traces)}")
    for a in traces:
        offs = list(a["marks"].values())
        if offs != sorted(offs):
            fail(f"request_trace mark offsets not monotone: {a}")
        if abs(sum(a["phases"].values()) - a["latency_s"]) > 5e-6:
            fail(f"request_trace phases do not telescope: {a}")
    if not any(r["kind"] == "event" and r["name"] == "slo_window"
               for r in recs_o):
        fail("serving trace is missing the slo_window event")
    diag_o = doctor.diagnose([path_o])
    slo_d = diag_o.get("slo")
    if not slo_d or slo_d.get("outcomes", {}).get("ok", 0) < 6:
        fail(f"doctor SLO section empty/short: {slo_d}")
    if "SLO (windowed attainment" not in doctor.render(diag_o):
        fail("doctor report is missing the SLO section")
    trace_o = telemetry.chrome_trace(path_o)
    telemetry.validate_chrome_trace(trace_o)
    if not any(e["ph"] == "b" and e.get("cat") == "request"
               for e in trace_o["traceEvents"]):
        fail("chrome trace is missing async request slices")

    # 13. mixed precision (ISSUE 10): the cost-model events are
    # dtype-labeled (level_cost/op_cost schema now REQUIRES pack, dtype
    # and itemsize — validate_jsonl above enforces it on every trace),
    # a bf16-hierarchy solve reports bfloat16 levels in the events, the
    # gauges and the doctor's cost-model table, and the all-f32 trace
    # from section 1 earns the "try mixed precision" hint
    def _cost_events(recs_):
        return [r["attrs"] for r in recs_ if r["kind"] == "event"
                and r["name"] == "level_cost"]

    lv_64 = _cost_events(recs)
    if not lv_64:
        fail("section-1 trace has no level_cost events")
    if len({a.get("dtype") for a in lv_64}) != 1:
        fail(f"section-1 level_cost dtypes inconsistent: "
             f"{[a.get('dtype') for a in lv_64]}")
    # an all-f32 bandwidth-class hierarchy earns the hint …
    telemetry.reset()
    telemetry.disable()
    path_32 = path + ".f32"
    if os.path.exists(path_32):
        os.unlink(path_32)
    cfg_32 = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-5, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        "krylov_dtype=float32, "
        f"out:telemetry=1, out:telemetry_path={path_32}")
    slv_32 = amgx.create_solver(cfg_32)
    slv_32.setup(amgx.Matrix(A))
    slv_32.solve(np.ones(A.shape[0]))
    with open(path_32) as f:
        lines_32 = f.readlines()
    try:
        telemetry.validate_jsonl(lines_32)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"f32 trace: {e}")
    recs_32 = [json.loads(l) for l in lines_32 if l.strip()]
    lv_f32 = _cost_events(recs_32)
    if not all(a.get("dtype") == "float32" for a in lv_f32):
        fail(f"f32 trace level_cost dtypes drifted: "
             f"{[a.get('dtype') for a in lv_f32]}")
    diag_32 = doctor.diagnose([path_32])
    if not any("hierarchy_dtype=bfloat16" in h
               for h in diag_32.get("hints", ())):
        fail("doctor did not hint mixed precision for the "
             "bandwidth-bound all-f32 hierarchy")
    # … while a bf16 one reports bfloat16 levels and no hint
    telemetry.reset()
    telemetry.disable()
    path_m = path + ".mixed"
    if os.path.exists(path_m):
        os.unlink(path_m)
    cfg_m = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-6, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        "amg:hierarchy_dtype=bfloat16, "
        f"out:telemetry=1, out:telemetry_path={path_m}")
    slv_m = amgx.create_solver(cfg_m)
    slv_m.setup(amgx.Matrix(A))
    res_m = slv_m.solve(np.ones(A.shape[0]))
    if int(res_m.status) != 0:
        fail(f"mixed-precision smoke solve did not converge "
             f"({res_m.status})")
    with open(path_m) as f:
        lines_m = f.readlines()
    try:
        telemetry.validate_jsonl(lines_m)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"mixed-precision trace: {e}")
    recs_m = [json.loads(l) for l in lines_m if l.strip()]
    lv_m = _cost_events(recs_m)
    if not any(a.get("dtype") == "bfloat16" for a in lv_m):
        fail(f"bf16-hierarchy trace has no bfloat16 level_cost events: "
         f"{[a.get('dtype') for a in lv_m]}")
    if not all(isinstance(a.get("itemsize"), int) for a in lv_m):
        fail("level_cost events are missing the itemsize field")
    bf_gauge = [r for r in recs_m if r["kind"] == "gauge"
                and r["name"] == "amgx_level_spmv_bytes"
                and r["labels"].get("dtype") == "bfloat16"]
    if not bf_gauge:
        fail("no bfloat16-labeled amgx_level_spmv_bytes gauge recorded")
    diag_m = doctor.diagnose([path_m])
    if any("hierarchy_dtype=bfloat16" in h
           for h in diag_m.get("hints", ())):
        fail("doctor hinted mixed precision for an already-bf16 "
             "hierarchy")
    report_m = doctor.render(diag_m)
    if "dtype" not in report_m or "bfloat16" not in report_m:
        fail("doctor cost-model table is missing the dtype column / "
             "bfloat16 levels")

    # 14. multi-lane serving scale-out (ISSUE 11): lane-labeled gauges
    # reach /metrics with per-lane rows, /healthz carries the
    # lane-aware body schema (503 only when every lane saturates), the
    # request_trace events carry lane + route, and the doctor's
    # lane-imbalance section fires on a hoarding lane while a balanced
    # fleet stays silent
    telemetry.reset()
    telemetry.disable()
    path_l = path + ".lanes"
    if os.path.exists(path_l):
        os.unlink(path_l)
    cfg_l = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=10, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        "serve_workers=2, serve_batch_window_ms=2, serve_lanes=2, "
        f"out:telemetry=1, out:telemetry_path={path_l}")
    svc_l = SolveService(cfg_l)
    try:
        if len(svc_l.lanes) != 2:
            fail(f"serve_lanes=2 built {len(svc_l.lanes)} lanes")
        url_l = svc_l.start_endpoint(0)
        import scipy.sparse as _sp
        from amgx_tpu.io import poisson5pt as _p5
        mo1 = amgx.Matrix(A)
        mo2 = amgx.Matrix(_sp.csr_matrix(_p5(12, 12)))
        import numpy as _np
        pend_l = []
        for mm in (mo1, mo2):
            pend_l += [svc_l.submit(mm, _np.ones(mm.shape[0]))
                       for _ in range(3)]
        for p in pend_l:
            if p.wait(timeout=120.0) is None:
                fail(f"lane smoke request failed: rc={p.rc} {p.error}")
        st_l = svc_l.stats()
        if len(st_l["lanes"]) != 2 or "router" not in st_l:
            fail(f"stats() missing lanes/router: {list(st_l)}")
        mtxt_l = urllib.request.urlopen(url_l + "/metrics",
                                        timeout=10).read().decode()
        for row in ('amgx_serve_lane_sessions{lane="0"}',
                    'amgx_serve_lane_sessions{lane="1"}',
                    'amgx_serve_lane_queue_depth{lane='):
            if row not in mtxt_l:
                fail(f"/metrics scrape is missing per-lane row "
                     f"{row!r}")
        hz_l = json.loads(urllib.request.urlopen(url_l + "/healthz",
                                                 timeout=10).read())
        for key in ("lanes", "lanes_total", "lanes_overloaded",
                    "saturated_lanes", "overloaded"):
            if key not in hz_l:
                fail(f"/healthz missing lane-aware key {key!r}: "
                     f"{sorted(hz_l)}")
        if hz_l["lanes_total"] != 2 or len(hz_l["lanes"]) != 2:
            fail(f"/healthz lane count wrong: {hz_l}")
        for lh in hz_l["lanes"]:
            for key in ("lane", "accepting", "queue_depth",
                        "overloaded", "sessions"):
                if key not in lh:
                    fail(f"/healthz lane entry missing {key!r}: {lh}")
        telemetry.flush_jsonl(path_l)
    finally:
        svc_l.shutdown()
    with open(path_l) as f:
        lines_l = f.readlines()
    try:
        telemetry.validate_jsonl(lines_l)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"lane trace: {e}")
    recs_l = [json.loads(l) for l in lines_l if l.strip()]
    traces_l = [r["attrs"] for r in recs_l if r["kind"] == "event"
                and r["name"] == "request_trace"]
    if not traces_l or not all("lane" in a and "route" in a
                               for a in traces_l):
        fail("request_trace events are missing lane/route attrs")
    lane_gauges = {r["labels"].get("lane") for r in recs_l
                   if r["kind"] == "gauge"
                   and r["name"] == "amgx_serve_lane_sessions"}
    if not {"0", "1"} <= {str(v) for v in lane_gauges}:
        fail(f"lane-labeled session gauges incomplete: {lane_gauges}")
    diag_l = doctor.diagnose([path_l])
    if not diag_l.get("serving_lanes"):
        fail("doctor diagnose has no serving_lanes section for a "
             "multi-lane trace")
    if "serving lanes" not in doctor.render(diag_l):
        fail("doctor report is missing the serving-lanes section")
    # the imbalance hint, both ways: a hoarding lane fires it …
    telemetry.reset()
    telemetry.disable()
    path_li = path + ".lanes_imb"
    if os.path.exists(path_li):
        os.unlink(path_li)
    telemetry.enable(ring_size=4096)
    telemetry.gauge_set("amgx_serve_lane_sessions", 8, lane=0)
    telemetry.gauge_set("amgx_serve_lane_sessions", 1, lane=1)
    telemetry.flush_jsonl(path_li)
    telemetry.disable()
    diag_imb = doctor.diagnose([path_li])
    if not any("lane imbalance" in h for h in diag_imb.get("hints", ())):
        fail(f"doctor did not flag an 8-vs-1 session imbalance: "
             f"{diag_imb.get('hints')}")
    # … while a balanced fleet stays silent
    telemetry.reset()
    path_lb = path + ".lanes_bal"
    if os.path.exists(path_lb):
        os.unlink(path_lb)
    telemetry.enable(ring_size=4096)
    telemetry.gauge_set("amgx_serve_lane_sessions", 4, lane=0)
    telemetry.gauge_set("amgx_serve_lane_sessions", 4, lane=1)
    telemetry.flush_jsonl(path_lb)
    telemetry.disable()
    diag_bal = doctor.diagnose([path_lb])
    if any("lane imbalance" in h for h in diag_bal.get("hints", ())):
        fail(f"doctor flagged imbalance on a balanced fleet: "
             f"{diag_bal.get('hints')}")

    # 15. pod-scale distributed AMG (ISSUE 12): a real distributed
    # classical solve (child process on the forced 8-device CPU mesh —
    # the parent's jax backend is already initialised single-device)
    # emits schema-valid dist_overlap / dist_agglomerate /
    # halo_exchange events, and the doctor renders the "distributed
    # levels" section; then the halo-bound hint BOTH WAYS on synthetic
    # traces (bound trace fires it, balanced trace stays silent)
    import subprocess
    path_dd = path + ".dist"
    if os.path.exists(path_dd):
        os.unlink(path_dd)
    env_d = dict(os.environ, JAX_PLATFORMS="cpu")
    env_d["XLA_FLAGS"] = (env_d.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8")
    r_d = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--dist-child",
         path_dd], env=env_d, capture_output=True, text=True,
        timeout=900)
    if r_d.returncode != 0:
        fail(f"distributed child failed rc={r_d.returncode}:\n"
             f"{r_d.stderr[-2000:]}")
    with open(path_dd) as f:
        lines_dd = f.readlines()
    try:
        telemetry.validate_jsonl(lines_dd)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"distributed trace: {e}")
    recs_dd = [json.loads(l) for l in lines_dd if l.strip()]
    ov_dd = [r["attrs"] for r in recs_dd if r["kind"] == "event"
             and r["name"] == "dist_overlap"]
    ag_dd = [r["attrs"] for r in recs_dd if r["kind"] == "event"
             and r["name"] == "dist_agglomerate"]
    if not ov_dd:
        fail("distributed trace has no dist_overlap events")
    if not ag_dd:
        fail("distributed trace has no dist_agglomerate events "
             "(the child's threshold should have triggered)")
    if not any(a["to_parts"] < a["from_parts"] for a in ag_dd):
        fail(f"dist_agglomerate events never shrink the mesh: {ag_dd}")
    if not any(a.get("submesh_parts", 99) < a.get("n_parts", 0)
               for a in ov_dd):
        fail(f"no dist_overlap event shows an agglomerated sub-mesh: "
             f"{[(a.get('level'), a.get('submesh_parts')) for a in ov_dd]}")
    if not any(r["kind"] == "counter"
               and r["name"] == "amgx_device_rap_total"
               and r["labels"].get("path") == "dist"
               for r in recs_dd):
        fail("distributed trace never counted "
             "amgx_device_rap_total{path=dist} — the shard-local "
             "device Galerkin did not run")
    diag_dd = doctor.diagnose([path_dd])
    if not diag_dd["distributed"].get("levels"):
        fail("doctor diagnose has no distributed levels for the "
             "distributed trace")
    if not diag_dd["distributed"].get("agglomerations"):
        fail("doctor diagnose lost the dist_agglomerate events")
    rep_dd = doctor.render(diag_dd)
    if "distributed levels" not in rep_dd or \
            "agglomerated level" not in rep_dd:
        fail("doctor report is missing the distributed-levels section")
    # the halo-bound hint, both ways: a bound level fires it …
    telemetry.reset()
    telemetry.disable()
    path_db = path + ".dist_bound"
    if os.path.exists(path_db):
        os.unlink(path_db)
    telemetry.enable(ring_size=4096)
    telemetry.event("dist_overlap", level=2, n_parts=8,
                    active_parts=8, submesh_parts=8, rows=256,
                    rows_per_part=32, interior_bytes=10000,
                    halo_wire_bytes=90000, halo_local_ratio=9.0,
                    est_interior_s=1e-8, est_halo_s=6e-8,
                    overlap_fraction=0.17, halo_bound=True,
                    measured=False)
    telemetry.flush_jsonl(path_db)
    telemetry.disable()
    diag_db = doctor.diagnose([path_db])
    if not any("dist_agglomerate_min_rows" in h
               for h in diag_db.get("hints", ())):
        fail(f"doctor did not recommend the agglomeration threshold "
             f"for a halo-bound level: {diag_db.get('hints')}")
    # … while a balanced trace stays silent
    telemetry.reset()
    path_dbal = path + ".dist_bal"
    if os.path.exists(path_dbal):
        os.unlink(path_dbal)
    telemetry.enable(ring_size=4096)
    telemetry.event("dist_overlap", level=0, n_parts=8,
                    active_parts=8, submesh_parts=8, rows=200000,
                    rows_per_part=25000, interior_bytes=9000000,
                    halo_wire_bytes=90000, halo_local_ratio=0.01,
                    est_interior_s=1e-5, est_halo_s=6e-8,
                    overlap_fraction=1.0, halo_bound=False,
                    measured=False)
    telemetry.flush_jsonl(path_dbal)
    telemetry.disable()
    diag_dbal = doctor.diagnose([path_dbal])
    if any("dist_agglomerate_min_rows" in h
           for h in diag_dbal.get("hints", ())):
        fail(f"doctor recommended agglomeration for a balanced trace: "
             f"{diag_dbal.get('hints')}")

    # 16. failures & recovery (ISSUE 13): a NaN-poisoned PCG solve
    # with the recovery ladder armed emits schema-valid
    # recovery_attempt / fault_injected / history_truncated events (the
    # validator enforces their vocabularies), the doctor renders the
    # "failures & recovery" section, and the repeated-recovery hint
    # fires — while the clean section-1 trace stays silent
    from amgx_tpu.utils import faultinject
    telemetry.reset()
    telemetry.disable()
    path_r = path + ".recovery"
    if os.path.exists(path_r):
        os.unlink(path_r)
    cfg_r = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=80, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_MAX, out:store_res_history=1, "
        "out:preconditioner(pre)=BLOCK_JACOBI, pre:max_iters=2, "
        "out:recovery_policy=AUTO, out:recovery_max_attempts=4, "
        f"out:telemetry=1, out:telemetry_path={path_r}")
    slv_r = amgx.create_solver(cfg_r)
    slv_r.setup(amgx.Matrix(A))
    # two recovered solves so the "engaged repeatedly" hint fires
    faultinject.configure("values_nan:iter=2:count=1")
    try:
        res_r1 = slv_r.solve(np.ones(A.shape[0]))
    finally:
        faultinject.reset()
    faultinject.configure("values_nan:iter=2:count=1")
    try:
        res_r2 = slv_r.solve(np.ones(A.shape[0]))
    finally:
        faultinject.reset()
    telemetry.disable()
    for i, rr in enumerate((res_r1, res_r2)):
        if int(rr.status) != 0 or not rr.recovery \
                or rr.recovery.get("outcome") != "recovered":
            fail(f"poisoned solve {i} did not recover: status "
                 f"{rr.status}, recovery {rr.recovery}")
    with open(path_r) as f:
        lines_r = f.readlines()
    try:
        telemetry.validate_jsonl(lines_r)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"recovery trace: {e}")
    recs_r = [json.loads(l) for l in lines_r if l.strip()]
    ev_names_r = {r["name"] for r in recs_r if r["kind"] == "event"}
    for needed in ("recovery_attempt", "fault_injected", "breakdown",
                   "history_truncated"):
        if needed not in ev_names_r:
            fail(f"recovery trace is missing the {needed!r} event")
    for r in recs_r:
        if r["kind"] in ("counter", "gauge", "hist") and \
                r["name"] not in telemetry.METRICS:
            fail(f"unregistered metric name {r['name']!r} in the "
                 "recovery trace (update telemetry.METRICS)")
    diag_r = doctor.diagnose([path_r])
    if not diag_r.get("failures"):
        fail("doctor diagnose has no failures section for the "
             "recovery trace")
    if diag_r["failures"].get("recovered", 0) < 2:
        fail(f"doctor undercounts recoveries: {diag_r['failures']}")
    rep_r = doctor.render(diag_r)
    if "failures & recovery" not in rep_r:
        fail("doctor report is missing the 'failures & recovery' "
             "section")
    if not any("recovery ladder engaged" in h
               for h in diag_r.get("hints", ())):
        fail(f"doctor did not hint on repeated recoveries: "
             f"{diag_r.get('hints')}")
    if not any("fault injection was ACTIVE" in h
               for h in diag_r.get("hints", ())):
        fail(f"doctor did not flag the active fault injection: "
             f"{diag_r.get('hints')}")
    # …and the clean section-1 trace stays silent: no failures
    # section, no recovery hint
    diag_clean = doctor.diagnose([path])
    if diag_clean.get("failures"):
        fail(f"doctor invented a failures section for the clean "
             f"trace: {diag_clean['failures']}")
    if any("recovery ladder" in h for h in diag_clean.get("hints", ())):
        fail(f"recovery hint fired on a clean trace: "
             f"{diag_clean.get('hints')}")

    # 17. communication-avoiding Krylov (ISSUE 16): a PCG_CA solve's
    # trace carries a schema-valid krylov_comm event (single fused
    # reduction per iteration) plus the collectives counter; the
    # validator rejects broken shapes BOTH WAYS; and dist_overlap
    # provenance works both ways too — modelled events say
    # measured=false, overlap.measured_event flips them to true and
    # they still validate
    import copy
    telemetry.reset()
    telemetry.disable()
    path_k = path + ".krylov"
    if os.path.exists(path_k):
        os.unlink(path_k)
    cfg_k = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG_CA, out:max_iters=120, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(pre)=BLOCK_JACOBI, pre:max_iters=2, "
        f"out:telemetry=1, out:telemetry_path={path_k}")
    slv_k = amgx.create_solver(cfg_k)
    slv_k.setup(amgx.Matrix(A))
    res_k = slv_k.solve(np.ones(A.shape[0]))
    telemetry.disable()
    if int(res_k.status) != 0:
        fail(f"PCG_CA solve did not converge: status {res_k.status}")
    with open(path_k) as f:
        lines_k = f.readlines()
    try:
        telemetry.validate_jsonl(lines_k)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"krylov_comm trace: {e}")
    recs_k = [json.loads(l) for l in lines_k if l.strip()]
    kc_k = [r for r in recs_k if r["kind"] == "event"
            and r["name"] == "krylov_comm"]
    if not kc_k:
        fail("PCG_CA trace has no krylov_comm event")
    a_k = kc_k[-1]["attrs"]
    if a_k["mode"] != "CA" or a_k["collectives_per_iter"] != 1 \
            or not a_k["fused"]:
        fail(f"krylov_comm event wrong for a CA solve (want mode=CA, "
             f"one fused collective/iter): {a_k}")
    if not any(r["kind"] == "counter"
               and r["name"] == "amgx_krylov_collectives_total"
               and r["labels"].get("op") == "fused"
               for r in recs_k):
        fail("PCG_CA trace never counted "
             "amgx_krylov_collectives_total{op=fused}")
    # … and the validator rejects broken krylov_comm shapes
    for mutate, what in (
            (lambda a: a.__setitem__("mode", "TURBO"), "unknown mode"),
            (lambda a: a.__setitem__("collectives_per_iter", -1),
             "negative collectives_per_iter"),
            (lambda a: a.__setitem__("per_iter", "3"),
             "non-dict per_iter profile")):
        bad_k = copy.deepcopy(kc_k[-1])
        mutate(bad_k["attrs"])
        try:
            telemetry.validate_record(bad_k)
            fail(f"validator accepted a krylov_comm event with {what}")
        except ValueError:
            pass
    # dist_overlap provenance both ways: the real distributed trace's
    # modelled events must say measured=false …
    if not all(a.get("measured") is False for a in ov_dd):
        fail(f"modelled dist_overlap events must carry measured=false: "
             f"{[a.get('measured') for a in ov_dd]}")
    ov_rec = next(r for r in recs_dd if r["kind"] == "event"
                  and r["name"] == "dist_overlap")
    # … dropping the flag fails validation …
    bad_ov = copy.deepcopy(ov_rec)
    bad_ov["attrs"].pop("measured", None)
    try:
        telemetry.validate_record(bad_ov)
        fail("validator accepted a dist_overlap event without the "
             "measured provenance bool")
    except ValueError:
        pass
    # … and a profiler-refined event flips to measured=true and still
    # validates (synthetic measure() result — the real-trace path is
    # covered by overlap.measure unit tests)
    meas_ov = telemetry.overlap.measured_event(
        ov_rec["attrs"], {"overlap_fraction": 0.8, "comm_s": 2e-7,
                          "compute_s": 1e-5, "n_comm_events": 4,
                          "n_devices": 8})
    if meas_ov.get("measured") is not True:
        fail(f"measured_event did not set measured=true: {meas_ov}")
    good_ov = copy.deepcopy(ov_rec)
    good_ov["attrs"] = meas_ov
    try:
        telemetry.validate_record(good_ov)
    except ValueError as e:
        fail(f"profiler-measured dist_overlap failed validation: {e}")

    # 18. device-time attribution (ISSUE 17): (a) the scope-coverage
    # lint — every SpMV pack dispatch site in ops/spmv.py labels a pack
    # the contract knows, every registered pack has a live dispatch
    # site, every dispatch rides a `with _tel_pack(...)` scope, and
    # every registered smoother's config name sanitises into the
    # contract; (b) the deviceprof correlator end-to-end on a synthetic
    # profiler capture: anatomy sums within 10% of total device time,
    # the emitted device_anatomy event schema-validates, every emitted
    # scope validates, and the doctor renders the section
    import ast
    import importlib
    import inspect

    from amgx_tpu.solvers.base import SolverFactory
    from amgx_tpu.telemetry import deviceprof, scopes

    # the package re-exports the spmv *function*; lint the module source
    _spmv_mod = importlib.import_module("amgx_tpu.ops.spmv")
    tree = ast.parse(inspect.getsource(_spmv_mod))
    dispatch_packs = set()
    bare_calls = []

    def _literals(node):
        return {c.value for c in ast.walk(node)
                if isinstance(c, ast.Constant)
                and isinstance(c.value, str)}

    with_calls = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.With):
            for item in node.items:
                with_calls.add(id(item.context_expr))
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id == "_tel_pack" and node.args:
            dispatch_packs |= _literals(node.args[0])
            if id(node) not in with_calls:
                bare_calls.append(ast.dump(node.args[0]))
    if bare_calls:
        fail(f"SpMV dispatch sites call _tel_pack without entering its "
             f"named scope (use `with _tel_pack(...):`): {bare_calls}")
    unscoped = sorted(dispatch_packs - set(scopes.SPMV_PACKS))
    if unscoped:
        fail(f"SpMV packs dispatched without a scope contract entry "
             f"(add to telemetry.scopes.SPMV_PACKS): {unscoped}")
    dead = sorted(set(scopes.SPMV_PACKS) - dispatch_packs)
    if dead:
        fail(f"scope contract lists SpMV packs no dispatch site emits "
             f"(stale SPMV_PACKS entries): {dead}")
    bad_smoothers = []
    for name, cls in sorted(SolverFactory.registered().items()):
        if getattr(cls, "is_smoother", False):
            try:
                if not scopes.validate(
                        scopes.scope_name("smoother", cls.config_name)):
                    raise ValueError(cls.config_name)
            except ValueError:
                bad_smoothers.append(name)
    if bad_smoothers:
        fail(f"registered smoothers whose config name does not "
             f"sanitise into the scope contract: {bad_smoothers}")

    # (b) correlator e2e on a synthetic capture: two overlapping
    # levels + coarse solve + nested smoother/spmv annotations + one
    # unscoped op, mirroring tests/conftest.py's shared fixture
    synth = {"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 1, "ts": 0, "dur": 100,
         "name": "fusion.1",
         "args": {"name": "amgx/cycle/level0/pre_smooth/"
                          "amgx/smoother/block_jacobi/"
                          "amgx/spmv/dia/slices/fusion.1"}},
        {"ph": "X", "pid": 0, "tid": 1, "ts": 100, "dur": 60,
         "name": "amgx/cycle/level0/restrict/fusion.2"},
        {"ph": "X", "pid": 0, "tid": 2, "ts": 150, "dur": 30,
         "name": "amgx/cycle/level1/pre_smooth/fusion.3"},
        {"ph": "X", "pid": 0, "tid": 1, "ts": 180, "dur": 30,
         "name": "amgx/cycle/coarse_solve/fusion.4"},
        {"ph": "X", "pid": 0, "tid": 1, "ts": 210, "dur": 10,
         "name": "copy.5"},
    ]}
    telemetry.reset()
    telemetry.disable()
    path_dp = path + ".deviceprof"
    if os.path.exists(path_dp):
        os.unlink(path_dp)
    telemetry.enable(ring_size=4096)
    anatomy = deviceprof.capture_anatomy(synth)
    deviceprof.emit(anatomy)
    telemetry.flush_jsonl(path_dp)
    telemetry.disable()
    if not anatomy["measured"]:
        fail("synthetic capture did not measure as scoped")
    level_sum = sum(lv["total_s"] for lv in anatomy["levels"].values()) \
        + anatomy["coarse_s"]
    tot = anatomy["total_device_s"]
    if tot <= 0 or abs(level_sum - tot) > 0.10 * tot:
        fail(f"device anatomy per-level sum {level_sum} strays more "
             f"than 10% from total device time {tot}")
    bad_scopes = [s for s in anatomy["scopes"] if not scopes.validate(s)]
    if bad_scopes:
        fail(f"device anatomy emitted non-contract scopes: {bad_scopes}")
    with open(path_dp) as f:
        lines_dp = f.readlines()
    try:
        telemetry.validate_jsonl(lines_dp)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"device_anatomy trace failed schema validation: {e}")
    recs_dp = [json.loads(l) for l in lines_dp if l.strip()]
    if not any(r["kind"] == "event" and r["name"] == "device_anatomy"
               for r in recs_dp):
        fail("deviceprof.emit wrote no device_anatomy event")
    if not any(r["kind"] == "counter"
               and r["name"] == "amgx_device_time_seconds_total"
               for r in recs_dp):
        fail("deviceprof.emit incremented no "
             "amgx_device_time_seconds_total counter")
    diag_dp = doctor.diagnose([path_dp])
    if not (diag_dp.get("device") or {}).get("measured"):
        fail("doctor diagnosis missed the device_anatomy event")
    if "Device anatomy" not in doctor.render(diag_dp):
        fail("doctor render has no Device anatomy section")
    # the stub path stays honest: no scoped ops → measured=false, and
    # the stub STILL schema-validates (httpd returns it inline on CPU)
    stub = deviceprof.measure_anatomy({"traceEvents": []})
    if stub["measured"] is not False:
        fail("empty capture did not degrade to a measured=false stub")
    try:
        telemetry.validate_record(
            {"kind": "event", "name": "device_anatomy", "seq": 1,
             "t": 0.0, "tid": 0, "sid": None, "attrs": stub})
    except ValueError as e:
        fail(f"measured=false anatomy stub failed validation: {e}")
    # (c) the recorded TPU capture (.xplane.pb): its device ops join to
    # their named-scope paths, the books balance, the event validates
    xplane = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "data",
        "fixture.xplane.pb")
    rec_a = deviceprof.measure_anatomy(xplane)
    if not rec_a["measured"] or not rec_a["levels"]:
        fail("the recorded TPU capture measured no scoped device time")
    if abs(rec_a["attributed_s"] + rec_a["unattributed_s"]
           - rec_a["total_device_s"]) > 2e-9:
        fail(f"recorded anatomy does not balance: {rec_a['attributed_s']}"
             f" + {rec_a['unattributed_s']} != "
             f"{rec_a['total_device_s']}")
    try:
        telemetry.validate_record(
            {"kind": "event", "name": "device_anatomy", "seq": 1,
             "t": 0.0, "tid": 0, "sid": None, "attrs": rec_a})
    except ValueError as e:
        fail(f"recorded-capture anatomy failed validation: {e}")
    # (d) retraces by function, counted with telemetry off too
    from amgx_tpu.utils.jaxcompat import trace_stats
    ts = trace_stats()
    if ts["traces"] <= 0 or not ts["by_fun"]:
        fail(f"trace_stats counted no traces after a solve: {ts}")

    # 19. HBM ledger (ISSUE 18): (a) a real small solve with the
    # `memledger=1` knob emits schema-valid hbm_snapshot events and the
    # registry↔census join balances — honesty invariant per device,
    # owners attribute the resident hierarchy; (b) an injected OOM
    # (fault point `oom`) yields exactly one schema-valid
    # oom_postmortem whose top owner is resident and whose suggestions
    # carry config knobs; (c) the doctor hint fires both ways: a
    # measured near-ceiling snapshot triggers it, the healthy
    # (unmeasured CPU) trace stays silent
    import copy

    from amgx_tpu.telemetry import memledger
    from amgx_tpu.utils import faultinject

    path_mem = path + ".memledger"
    path_oom = path + ".oom"
    path_nc = path + ".nearceiling"
    for p in (path_mem, path_oom, path_nc):
        if os.path.exists(p):
            os.unlink(p)
    telemetry.reset()
    faultinject.reset()
    telemetry.enable(ring_size=65536)
    cfg_mem = amgx.AMGConfig(
        "config_version=2, solver(s)=AMG, s:max_iters=60, "
        "s:tolerance=1e-6, s:monitor_residual=1, "
        "s:convergence=RELATIVE_INI, "
        "s:smoother(sm)=BLOCK_JACOBI, s:presweeps=1, s:postsweeps=1, "
        "s:max_levels=4, s:coarse_solver(cs)=DENSE_LU_SOLVER, "
        "memledger=1, memledger_sample_s=0")
    slv_mem = amgx.create_solver(cfg_mem)
    if not memledger.is_enabled():
        fail("memledger=1 config knob did not enable the ledger")
    slv_mem.setup(amgx.Matrix(A))
    res_mem = slv_mem.solve(np.ones(A.shape[0]))
    if int(res_mem.status) != 0:
        fail(f"memledger solve did not converge ({res_mem.status})")
    if memledger.entry_count() == 0:
        fail("setup registered nothing in the HBM ledger")
    snap_mem = memledger.snapshot()
    # registry↔census cross-check on the live solve: the invariant is
    # exact arithmetic per device, the resident hierarchy is owned,
    # and owned arrays are a subset of the census
    if not snap_mem["devices"]:
        fail("ledger snapshot saw no devices on a live solve")
    for dev, d in snap_mem["devices"].items():
        if d["accounted_bytes"] + d["unaccounted_bytes"] \
                != d["bytes_in_use"]:
            fail(f"honesty invariant violated on {dev}: "
                 f"{d['accounted_bytes']} + {d['unaccounted_bytes']} "
                 f"!= {d['bytes_in_use']}")
        if not snap_mem["measured"] \
                and d["bytes_in_use"] != d["census_bytes"]:
            fail(f"unmeasured stub must define bytes_in_use as the "
                 f"census total on {dev}")
        if sum(d["owners"].values()) != d["accounted_bytes"]:
            fail(f"owner bytes do not sum to accounted_bytes on {dev}")
    if not any(o.startswith("amgx/hierarchy/")
               for o in snap_mem["owners"]):
        fail("census attributed no amgx/hierarchy/* owner after setup "
             f"(owners: {sorted(snap_mem['owners'])})")
    bad_owner = [o for o in snap_mem["owners"]
                 if not memledger.validate(o)]
    if bad_owner:
        fail(f"snapshot owners violate the taxonomy: {bad_owner}")
    if snap_mem["n_owned_arrays"] > snap_mem["n_live_arrays"]:
        fail("census join claims more arrays than are live")
    telemetry.dump_jsonl(path_mem)      # the HEALTHY ledger trace
    with open(path_mem) as f:
        lines_mem = f.readlines()
    try:
        telemetry.validate_jsonl(lines_mem)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"memledger trace failed schema validation: {e}")
    recs_mem = [json.loads(l) for l in lines_mem if l.strip()]
    if not any(r["kind"] == "event" and r["name"] == "hbm_snapshot"
               for r in recs_mem):
        fail("memledger solve emitted no hbm_snapshot event")
    if not any(r["kind"] == "gauge" and r["name"] == "amgx_hbm_bytes"
               for r in recs_mem):
        fail("memledger solve set no amgx_hbm_bytes gauge")

    # (b) injected OOM → schema-valid post-mortem naming the resident
    faultinject.configure("oom:count=1")
    victim = amgx.create_solver(cfg_mem)
    try:
        victim.setup(amgx.Matrix(A))
    except Exception:
        pass
    else:
        fail("fault point oom:count=1 did not raise in setup")
    finally:
        faultinject.reset()
    telemetry.dump_jsonl(path_oom)
    with open(path_oom) as f:
        lines_oom = f.readlines()
    try:
        telemetry.validate_jsonl(lines_oom)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"oom trace failed schema validation: {e}")
    recs_oom = [json.loads(l) for l in lines_oom if l.strip()]
    pms = [r for r in recs_oom if r["kind"] == "event"
           and r["name"] == "oom_postmortem"]
    if len(pms) != 1:
        fail(f"expected exactly 1 oom_postmortem, got {len(pms)}")
    pm_a = pms[0]["attrs"]
    if pm_a["where"] != "setup" or pm_a["injected"] is not True:
        fail(f"post-mortem misattributed the OOM: where="
             f"{pm_a['where']!r} injected={pm_a['injected']!r}")
    if not pm_a["top_owners"]:
        fail("post-mortem names no resident owners")
    if not pm_a["suggestions"]:
        fail("post-mortem carries no eviction suggestions")
    diag_oom = doctor.diagnose([path_oom])
    if not (diag_oom.get("memory") or {}).get("oom_postmortems"):
        fail("doctor diagnosis missed the oom_postmortem event")
    rep_oom = doctor.render(diag_oom)
    if "Device memory (HBM ledger)" not in rep_oom:
        fail("doctor render has no Device memory section")
    if not any("device OOM in setup" in h
               for h in diag_oom.get("hints", [])):
        fail("doctor raised no OOM hint for an oom_postmortem trace")

    # (c) the near-ceiling hint BOTH WAYS: fires on a measured
    # <10%-headroom snapshot, silent on the healthy trace
    diag_mem = doctor.diagnose([path_mem])
    if any("near its ceiling" in h for h in diag_mem.get("hints", [])):
        fail("near-ceiling hint fired on a healthy trace")
    snap_nc = copy.deepcopy(snap_mem)
    snap_nc["measured"] = True
    for d in snap_nc["devices"].values():
        in_use = d["bytes_in_use"]
        d["bytes_limit"] = in_use + max(in_use // 20, 1)
        d["headroom_bytes"] = d["bytes_limit"] - in_use
        d["peak_bytes"] = in_use
    telemetry.reset()
    telemetry.enable(ring_size=4096)
    memledger.emit(snap_nc, phase="check")
    telemetry.dump_jsonl(path_nc)
    telemetry.disable()
    with open(path_nc) as f:
        try:
            telemetry.validate_jsonl(f.readlines())
        except (ValueError, json.JSONDecodeError) as e:
            fail(f"near-ceiling trace failed schema validation: {e}")
    diag_nc = doctor.diagnose([path_nc])
    if not any("near its ceiling" in h
               for h in diag_nc.get("hints", [])):
        fail("near-ceiling hint did not fire on a measured "
             "low-headroom snapshot")
    slv_mem.release_memledger()
    victim.release_memledger()
    memledger.disable()
    telemetry.reset()

    # 20. mesh flight recorder (ISSUE 20): (a) the section-15
    # distributed child trace, mirrored into a second rank identity
    # (the house single-process SPMD pattern), joins into a measured
    # 2-rank mesh whose emitted mesh_health / mesh_rendezvous records
    # pass the schema — including the compute + wait + unattributed
    # ≡ wall honesty invariant — and the doctor renders the "Mesh
    # health" section; (b) the straggler hint BOTH WAYS on synthetic
    # 3-rank traces (an injected-skew mesh fires it, the balanced
    # mesh stays silent)
    from amgx_tpu.telemetry import meshtrace

    path_mesh = path + ".mesh"
    path_me = path + ".mesh_emit"
    path_mskew = path + ".mesh_skew"
    path_mbal = path + ".mesh_bal"
    for p in (path_mesh, path_me, path_mskew, path_mbal):
        if os.path.exists(p):
            os.unlink(p)
    meta2 = json.loads(lines_dd[0])
    meta2["pid"] += 1
    meta2["session"] = "c0ffee000002"
    with open(path_mesh, "w") as f:
        f.writelines(lines_dd)
        f.write(json.dumps(meta2) + "\n")
        f.writelines(lines_dd[1:])
    mesh = meshtrace.analyze(path_mesh)
    if not mesh["measured"] or mesh["n_ranks"] != 2:
        fail(f"mirrored distributed trace did not join into a measured "
             f"2-rank mesh (measured={mesh['measured']} "
             f"n_ranks={mesh['n_ranks']})")
    if mesh["collectives"].get("halo", 0) <= 0:
        fail("mesh join reconstructed no halo rendezvous from the "
             "distributed child's dist_spmv spans")
    telemetry.enable(ring_size=16384)
    meshtrace.emit(mesh)
    telemetry.dump_jsonl(path_me)
    telemetry.disable()
    with open(path_me) as f:
        lines_me = f.readlines()
    try:
        telemetry.validate_jsonl(lines_me)
    except (ValueError, json.JSONDecodeError) as e:
        fail(f"emitted mesh records failed schema validation: {e}")
    recs_me = [json.loads(l) for l in lines_me if l.strip()]
    mh = [r for r in recs_me if r["kind"] == "event"
          and r["name"] == "mesh_health"]
    if len(mh) != 2:
        fail(f"expected 2 mesh_health events (one per rank), got "
             f"{len(mh)}")
    for r in mh:
        a = r["attrs"]
        if abs(a["compute_s"] + a["wait_s"] + a["unattributed_s"]
               - a["wall_s"]) > 1e-6 * max(1.0, abs(a["wall_s"])):
            fail(f"mesh_health honesty invariant violated: {a}")
    if not any(r["kind"] == "event" and r["name"] == "mesh_rendezvous"
               for r in recs_me):
        fail("meshtrace.emit wrote no mesh_rendezvous records")
    diag_mesh = doctor.diagnose([path_mesh])
    if not diag_mesh.get("mesh"):
        fail("doctor diagnose has no mesh analysis for a 2-rank trace")
    if "Mesh health" not in doctor.render(diag_mesh):
        fail("doctor report is missing the Mesh health section")

    # (b) the straggler hint, both ways, on synthetic 3-rank meshes —
    # each rank on its own perf epoch (the offsets the clock fit must
    # undo); rank 702 begins every hop `late_s` after its peers
    def _mesh_rank(pid, session, offset, late_s=0.0, span_dur=0.1):
        meta = {"kind": "meta", "name": "amgx-telemetry",
                "schema": telemetry.SCHEMA_VERSION, "pid": pid,
                "session": session, "host": "checkhost",
                "t_perf": 0.0 - offset, "t_unix": 0.0, "dropped": 0}
        out = [json.dumps(meta)]
        recs = [{"kind": "span_begin", "name": "solve",
                 "t": 0.0 - offset, "tid": 1, "sid": 1,
                 "parent": None, "attrs": {}}]
        for k in range(6):
            t0 = 0.2 + 0.25 * k + late_s
            recs.append({"kind": "span_begin", "name": "exchange_halo",
                         "t": t0 - offset, "tid": 1, "sid": 10 + k,
                         "parent": 1, "attrs": {"ring": 1}})
            recs.append({"kind": "span_end", "name": "exchange_halo",
                         "t": t0 + span_dur - offset, "tid": 1,
                         "sid": 10 + k, "dur": span_dur})
        recs.append({"kind": "span_end", "name": "solve",
                     "t": 2.0 - offset, "tid": 1, "sid": 1, "dur": 2.0})
        for i, rr in enumerate(recs):
            rr["seq"] = i + 1
            out.append(json.dumps(rr))
        return out

    def _mesh_fixture(dst, late_s):
        with open(dst, "w") as f:
            for pid, sess, off, late in (
                    (700, "beef00000000", 100.0, 0.0),
                    (701, "beef00000001", 900.0, 0.0),
                    (702, "beef00000002", 400.0, late_s)):
                sd = 0.02 if late else 0.1
                f.write("\n".join(
                    _mesh_rank(pid, sess, off, late, sd)) + "\n")

    _mesh_fixture(path_mskew, 0.05)
    diag_ms = doctor.diagnose([path_mskew])
    if not any("mesh straggler" in h for h in diag_ms.get("hints", ())):
        fail(f"straggler hint did not fire on the injected-skew mesh: "
             f"{diag_ms.get('hints')}")
    _mesh_fixture(path_mbal, 0.0)
    diag_mb = doctor.diagnose([path_mbal])
    if any("mesh straggler" in h for h in diag_mb.get("hints", ())):
        fail(f"straggler hint fired on a balanced mesh: "
             f"{diag_mb.get('hints')}")
    telemetry.reset()

    print(f"telemetry_check: OK — {n_rec} records validated "
          f"({res.iterations} iterations, "
          f"{len(names_by_kind.get('span_end', ()))} span names, "
          f"{n_ev} chrome-trace events, doctor OK, forensics OK, "
          f"setup-profile OK, coverage {cov:.0%}, device-setup OK, "
          f"serving-obs OK, mixed-precision OK, serving-lanes OK, "
          f"distributed OK, failures-recovery OK, krylov-comm OK, "
          f"device-anatomy OK, memledger OK, mesh OK)")
    if not keep:
        os.unlink(path)
        os.unlink(path_f)
        os.unlink(path_s)
        os.unlink(path_d)
        os.unlink(path_d2)
        os.unlink(path_o)
        os.unlink(path_32)
        os.unlink(path_m)
        os.unlink(path_l)
        os.unlink(path_li)
        os.unlink(path_lb)
        os.unlink(path_dd)
        os.unlink(path_db)
        os.unlink(path_dbal)
        os.unlink(path_r)
        os.unlink(path_k)
        os.unlink(path_dp)
        os.unlink(path_mem)
        os.unlink(path_oom)
        os.unlink(path_nc)
        os.unlink(path_mesh)
        os.unlink(path_me)
        os.unlink(path_mskew)
        os.unlink(path_mbal)


def dist_child(trace_path: str) -> int:
    """Section-15 child: one distributed classical solve on the forced
    8-device CPU mesh with agglomeration + shard-local device Galerkin
    active, streaming its trace to ``trace_path``."""
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu.distributed.matrix import make_mesh, shard_vector
    from amgx_tpu.io import poisson7pt

    mesh = make_mesh(8)
    A = poisson7pt(10, 10, 10)
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=CLASSICAL, amg:selector=PMIS, "
        "amg:interpolator=D1, amg:max_iters=1, amg:max_row_sum=0.9, "
        "amg:max_levels=6, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1, "
        "amg:presweeps=1, amg:postsweeps=1, amg:min_coarse_rows=8, "
        "amg:coarse_solver=DENSE_LU_SOLVER, determinism_flag=1, "
        "device_setup_min_rows=0, dist_agglomerate_min_rows=64, "
        f"out:telemetry=1, out:telemetry_path={trace_path}")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    res = slv.solve(shard_vector(m.device(), np.ones(A.shape[0])))
    return 0 if int(res.status) == 0 else 3


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--dist-child":
        sys.exit(dist_child(sys.argv[2]))
    main()
