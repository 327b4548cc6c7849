#!/usr/bin/env python
"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline (BASELINE.md config 3 analog, single chip): FGMRES + aggregation
AMG on a 3D 7-point Poisson, time-to-convergence (TRUE relative residual
1e-8).  Also measures raw SpMV throughput (BASELINE metric 2) and reports
achieved GFLOPS and effective HBM bandwidth in the extras.

TPU design used here: the GEO (structured pairwise) aggregation keeps the
whole hierarchy in DIA format — gather-free shifted-slice SpMV on every
level, reshape-based grid transfers (amg/pairwise.py).  The device solves
in fp32; the 1e-8 tolerance is reached honestly via mixed-precision
iterative refinement against the fp64 host matrix (the reference's dDFI
mixed mode, amgx_config.h:114-123).

Timing boundaries: ``upload_s`` times the fine-operator ACQUISITION —
a host→device transfer for uploaded systems (the AMGX_matrix_upload_all
analog) or the on-device generation (io/device_gen.py; the reference
generates its benchmark operator in-library too).  ``setup_s`` is the
AMGX_solver_setup analog; the SpMV measurement amortises a long
in-executable chain between two syncs with min-of-reps noise rejection.

Runs only on a TPU: without one it fails, unless ``--cpu`` asks for an
explicit CPU run (whose metric names carry a ``cpu_`` prefix).  Every
block that raises leaves its error in the JSON and makes the exit code
nonzero.  No process that holds the chip starts a child that needs it:
the cold/warm-start probe's children run BEFORE this process touches
JAX.
"""
import json
import os
import signal
import sys
import time


def _emit_error_json(kind: str, exc: BaseException,
                     retried: bool = False) -> int:
    """Structured failure diagnostic: ONE parseable JSON line on stdout
    (what the bench driver records as ``parsed``) plus the traceback on
    stderr, and a clean nonzero exit — not a raw ``_init_backend``
    backtrace and an empty record.
    ``retried`` records that the backend init was retried once (with
    backoff) before giving up, so bench_trend can distinguish a flaky
    worker from a dead one."""
    import traceback
    traceback.print_exc(file=sys.stderr)
    detail = f"{type(exc).__name__}: {exc}"
    print(json.dumps({
        "error_kind": kind,
        "detail": detail[:500],
        "metric": None,
        "value": None,
        "retried": bool(retried),
    }))
    return 1


def _is_device_init_error(exc: BaseException) -> bool:
    """Does this exception read as 'the accelerator backend failed to
    initialise' (vs a bench bug)?  Matches the jax backend-init failure
    surfaces: xla_bridge RuntimeError, JaxRuntimeError UNAVAILABLE."""
    text = f"{type(exc).__name__}: {exc}"
    needles = ("Unable to initialize backend", "UNAVAILABLE",
               "backend setup/compile error", "No visible device",
               "failed to connect", "DEADLINE_EXCEEDED")
    return any(n in text for n in needles)


#: BASELINE config 2: PCG + classical AMG (PMIS/D2, the reference's
#: interp_max_elements=4 truncation) — module-level because BOTH the
#: extra classical cases and the warm-start probe child benchmark the
#: same solver stack
CFG_CLA = (
    "config_version=2, solver(out)=PCG, out:max_iters=100, "
    "out:monitor_residual=1, out:tolerance=1e-8, "
    "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
    "amg:algorithm=CLASSICAL, amg:selector=PMIS, "
    "amg:interpolator=D2, amg:max_iters=1, "
    "amg:interp_max_elements=4, amg:max_row_sum=0.9, "
    "amg:max_levels=16, amg:smoother(sm)=JACOBI_L1, "
    "sm:max_iters=1, amg:presweeps=2, amg:postsweeps=2, "
    "amg:min_coarse_rows=32, amg:coarse_solver=DENSE_LU_SOLVER")

_SUM = None


def _sum_jit():
    global _SUM
    if _SUM is None:
        import jax
        import jax.numpy as jnp
        _SUM = jax.jit(jnp.sum)
    return _SUM


def _sync(arr):
    """Host-side sync on the array through a host fetch of a reduction
    (one scalar transfer; every timing window ends on it)."""
    float(_sum_jit()(arr))


def _precompile_sync(shape, dtype):
    """AOT-compile the sync reduce for ``shape`` so a cold compile cache
    doesn't charge its compile to the upload timing window."""
    import jax
    _sum_jit().lower(jax.ShapeDtypeStruct(shape, dtype)).compile()


def _dia_apply64(offs, vals, x):
    """Host f64 ``A @ x`` from row-aligned diagonal arrays — the true
    residual check of a device-GENERATED operator must not assemble a
    110M-nnz scipy CSR just to multiply once."""
    import numpy as np
    y = np.zeros_like(x)
    n = len(x)
    for o, row in zip(offs, vals):
        o = int(o)
        if o >= 0:
            y[:n - o] += row[:n - o] * x[o:]
        else:
            y[-o:] += row[-o:] * x[:n + o]
    return y


def _run_case(oracle, make_matrix, cfg, dtype, sync_shape=None,
              keep=None):
    """Acquire + setup + warm + timed solve of one system; the SAME
    protocol serves the headline size and the 256³ north-star block.

    Timing boundaries follow the reference C API: ``upload_s`` is the
    fine-operator acquisition — ``AMGX_matrix_upload_all`` for an
    uploaded host matrix, or
    the on-device generation (``AMGX_generate_distributed_poisson_7pt``
    analog, io/device_gen.py) when ``make_matrix`` generates on chip;
    ``AMGX_solver_setup`` is the AMG setup proper (timed as
    ``setup_s``); ``AMGX_solver_solve`` is timed device-side with b
    staged on device (AMGX_vector_upload is a separate call).

    ``oracle``: host scipy matrix for the true-residual check, or None
    to check against the Matrix's own host diagonal arrays (generated
    operators never assemble a host CSR)."""
    # per-case structured summary straight in the bench JSON (pack
    # choices, phase times, iteration count) — no AMGX_BENCH_PROFILE
    # gate.  Instruments are host-side (the compiled solve is
    # unchanged) but recording does take a lock per record, so
    # AMGX_BENCH_TELEMETRY=0 gives byte-exact telemetry-off parity
    # when measuring against a pre-telemetry baseline.
    if os.environ.get("AMGX_BENCH_TELEMETRY") == "0":
        return _run_case_inner(oracle, make_matrix, cfg, dtype,
                               sync_shape, keep)
    from amgx_tpu import telemetry

    with telemetry.capture() as tel:
        out = _run_case_inner(oracle, make_matrix, cfg, dtype, sync_shape,
                              keep)
    out["telemetry"] = _tel_case_summary(tel)
    # AMGX_BENCH_TELEMETRY_PATH: also append each case's raw trace as
    # one JSONL session — what `python -m amgx_tpu.telemetry.doctor`
    # and the Perfetto export ingest (multi-case files hold one meta
    # header per case, the multi-session layout the validator accepts)
    trace_path = os.environ.get("AMGX_BENCH_TELEMETRY_PATH")
    if trace_path:
        with open(trace_path, "a") as f:
            telemetry.dump_jsonl(f, tel.records)
    return out


def _tel_case_summary(tel):
    # phase totals from the histogram samples: those are emitted by the
    # TOP-LEVEL solver only, so nested smoother/coarse setups don't
    # inflate the counts (their spans still nest inside the trace)
    phases = {}
    for name, key in (("amgx_setup_seconds", "setup"),
                      ("amgx_resetup_seconds", "resetup"),
                      ("amgx_solve_seconds", "solve")):
        rs = tel.metric_records(name, kind="hist")
        if rs:
            phases[key] = {"count": len(rs),
                           "total_s": round(sum(r["value"] for r in rs),
                                            4)}
    iters = tel.gauge_last("amgx_solve_iterations")
    # cost-model view (telemetry/costmodel.py): the fine operator's
    # bytes/FLOPs per apply + padding waste, and the halo-exchange wire
    # totals when the case ran distributed — so BENCH logs carry the
    # hardware-terms numbers, not just wall seconds
    opc = tel.events("operator_cost")
    cost = None
    if opc:
        a = opc[-1]["attrs"]
        cost = {k: a.get(k) for k in
                ("pack", "bytes_per_apply", "flops_per_apply",
                 "padding_waste", "halo_bytes_per_apply")
                if a.get(k) is not None}
    halo_bytes = tel.counter_total("amgx_halo_bytes_total")
    halo = None
    if halo_bytes:
        halo = {
            "wire_bytes": int(halo_bytes),
            "entries": int(tel.counter_total("amgx_halo_entries_total")),
            "exchanges": int(tel.counter_total(
                "amgx_halo_exchange_total")),
        }
    # convergence-forensics block (AMGX_BENCH_FORENSICS=1 adds the
    # `forensics=1` knob to the case configs): per-level cycle-anatomy
    # factors + the weakest component, so a BENCH diff can show WHERE
    # an iteration-count regression lives, not just that it happened
    fore = None
    if tel.events("cycle_level") or tel.events("forensics_probe"):
        from amgx_tpu.telemetry import forensics as _fr
        fa = _fr.analyze(tel.records)
        if fa:
            fore = {
                "levels": {str(k): {c: (round(v, 4)
                                        if isinstance(v, float) else v)
                                    for c, v in d.items()}
                           for k, d in fa["levels"].items()},
                "weakest": fa["weakest"],
                "asymptotic_rate": (round(fa["asymptotic_rate"], 4)
                                    if isinstance(fa["asymptotic_rate"],
                                                  float) else None),
            }
    # setup-attribution block (AMGX_BENCH_SETUP_PROFILE=1): totals,
    # compile share and the top phases — the columns bench_trend.py and
    # the perf-gate triage read
    sprof = None
    if tel.events("setup_profile") or tel.events("setup_phase"):
        from amgx_tpu.telemetry import setup_profile as _sp
        sprof = _sp.summarize(_sp.analyze(tel.records))
    # device setup engine (amg/device_setup/): RAP path split +
    # plan-cache state + per-level fallback reasons — the numbers the
    # ISSUE-7 acceptance reads ("host-share of rap below 25%")
    dev_rap = tel.counter_totals("amgx_device_rap_total", label="path")
    dsetup = None
    if dev_rap:
        dsetup = {
            "rap_by_path": {str(k): int(v)
                            for k, v in sorted(dev_rap.items())},
            "fallbacks": [dict(e["attrs"]) for e in
                          tel.events("device_setup_fallback")],
        }
        caches = tel.events("device_setup_cache")
        if caches:
            dsetup["cache"] = dict(caches[-1]["attrs"])
    # warm-start layer: persistent-cache/AOT traffic of this case (plus
    # the cross-restart cumulative state when configured) — the columns
    # bench_trend.py's cache-efficacy annotation reads
    cc = None
    cc_hits = tel.counter_total("amgx_compile_cache_hits_total")
    cc_miss = tel.counter_total("amgx_compile_cache_misses_total")
    if cc_hits or cc_miss:
        cc = {"hits": int(cc_hits), "misses": int(cc_miss),
              "fallbacks": int(tel.counter_total(
                  "amgx_compile_cache_fallbacks_total"))}
        from amgx_tpu.telemetry import runstate
        cum = runstate.cumulative()
        if cum and cum.get("counters"):
            cc["cum"] = cum["counters"]
    return {
        "packs": {str(k): int(v) for k, v in sorted(
            tel.counter_totals("amgx_spmv_dispatch_total",
                               label="pack").items())},
        "phases": phases,
        "iterations": int(iters) if iters is not None else None,
        "jit_traces": int(tel.counter_total("amgx_jit_trace_total")),
        "jit_compiles": int(tel.counter_total("amgx_jit_compile_total")),
        **({"compile_cache": cc} if cc else {}),
        **({"operator_cost": cost} if cost else {}),
        **({"halo": halo} if halo else {}),
        **({"forensics": fore} if fore else {}),
        **({"setup_profile": sprof} if sprof else {}),
        **({"device_setup": dsetup} if dsetup else {}),
    }


def _run_case_inner(oracle, make_matrix, cfg, dtype, sync_shape=None,
                    keep=None):
    import jax.numpy as jnp
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu.core.matrix import pack_kind

    slv = amgx.create_solver(cfg)
    if sync_shape is not None:
        # AOT-compile the sync reduce so a cold compile cache doesn't
        # charge its compile to the acquisition window
        _precompile_sync(sync_shape, dtype)
    t0 = time.perf_counter()
    m = make_matrix()
    Ad = m.device()
    _sync(Ad.vals if Ad.vals is not None else Ad.diag)
    upload_t = time.perf_counter() - t0
    # the CHOSEN pack per case, straight in the log: a dispatch
    # regression (a case silently sliding off its kernel) then shows in
    # BENCH diffs, not only as a slower number
    print(f"[bench] fine-level pack: {pack_kind(Ad)}", file=sys.stderr)
    n = m.shape[0]
    t0 = time.perf_counter()
    slv.setup(m)
    t_setup_host = time.perf_counter() - t0
    # setup's device work is dispatched asynchronously; observe it
    # (diag always exists; lean windowed packs carry vals=None)
    hier = getattr(getattr(slv, "preconditioner", None), "hierarchy", None)
    if hier is not None and hier.levels:
        _sync(hier.levels[-1].Ad.diag)
    setup_t = time.perf_counter() - t0
    if os.environ.get("AMGX_BENCH_PROFILE"):
        print(f"[bench] setup host {t_setup_host:.2f}s "
              f"+ device-drain {setup_t - t_setup_host:.2f}s",
              file=sys.stderr)
    # self-attributing split: the host-side share (python + any
    # transfers, which block the host thread) vs the trailing
    # device-drain
    setup_host_s, setup_drain_s = t_setup_host, setup_t - t_setup_host
    b_dev = jnp.ones(n, dtype)         # staged on device, no transfer
    res = slv.solve(b_dev)             # warm-up/compile solve
    t0 = time.perf_counter()
    res = slv.solve(b_dev)
    solve_t = time.perf_counter() - t0
    x = np.asarray(res.x, dtype=np.float64)
    b = np.ones(n, dtype=np.float64)
    if oracle is not None:
        Ax = oracle @ x
    else:
        offs, vals = m.dia_cache()
        Ax = _dia_apply64(offs, vals.astype(np.float64, copy=False), x)
    relres = float(np.linalg.norm(b - Ax) / np.linalg.norm(b))
    if os.environ.get("AMGX_BENCH_PROFILE"):
        from amgx_tpu.utils.profiler import profiler_tree
        print(profiler_tree().report(), file=sys.stderr)
        profiler_tree().reset()
    if keep is not None:
        keep.append(slv)
    return {"upload_s": round(upload_t, 4), "setup_s": round(setup_t, 4),
            "setup_host_s": round(setup_host_s, 4),
            "setup_drain_s": round(setup_drain_s, 4),
            "solve_s": round(solve_t, 4),
            "relres": relres, "iterations": int(res.iterations),
            "status": int(res.status), "n": int(n),
            "pack": pack_kind(Ad)}


def _bench_device_anatomy(slv, n, dtype):
    """Profile ONE warm headline solve and attribute its device time to
    the ``amgx/*`` named-scope contract (ISSUE 17):
    telemetry.deviceprof joins the capture's XLA device slices back to
    the scope taxonomy, with the same solve's op-cost records
    feeding the measured-bandwidth column.  On CPU the trace carries no
    scoped device ops and the block honestly reports measured=false."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from amgx_tpu import telemetry
    b = jnp.ones(n, dtype)
    with tempfile.TemporaryDirectory() as td:
        with telemetry.capture() as cap:
            with jax.profiler.trace(td):
                res = slv.solve(b)
                _sync(res.x)
        return telemetry.deviceprof.capture_anatomy(td,
                                                    records=cap.records)


def _bench_memory(slv):
    """HBM-ledger snapshot of the kept headline solver (ISSUE 18):
    enable the ledger post-hoc (the timed solves above ran with it off
    — the zero-overhead contract), register the resident hierarchy,
    and report peak HBM + top owners.  perf_gate checks the block's
    SHAPE only and never ratchets it — ``memory_stats()`` availability
    varies by platform, and on CPU the block honestly reports
    measured=false with the census as the stand-in."""
    from amgx_tpu import telemetry
    from amgx_tpu.telemetry import recorder
    ml = telemetry.memledger
    was_ml = ml.is_enabled()
    was_rec = recorder.is_enabled()
    hier = None
    ml.enable(sample_s=0.0)
    try:
        hier = getattr(getattr(slv, "preconditioner", None),
                       "hierarchy", None) or getattr(slv, "hierarchy",
                                                     None)
        if hier is not None and hasattr(hier, "_register_memledger"):
            hier._register_memledger()
        snap = ml.snapshot()
        devs = snap["devices"].values()
        return {"measured": bool(snap["measured"]),
                "ledger_version": int(snap["ledger_version"]),
                "peak_hbm_bytes": int(max(
                    (d.get("peak_bytes", 0) for d in devs), default=0)),
                "bytes_in_use": int(sum(
                    d.get("bytes_in_use", 0) for d in devs)),
                "top_owners": [[k, int(v)]
                               for k, v in ml.top_owners(snap)]}
    finally:
        if hier is not None and hasattr(hier, "release_memledger"):
            hier.release_memledger()
        if not was_ml:
            ml.disable()
        if not was_rec:
            recorder.disable()


def _hier_cycle_bytes(slv):
    """(modelled bytes one V-cycle streams, per-level dtypes) of a kept
    solver's hierarchy — the cost-model numerator of the bench's
    mixed-precision effective-GB/s columns (telemetry/costmodel.py; no
    device work, shapes only)."""
    from amgx_tpu.telemetry import costmodel
    hier = getattr(getattr(slv, "preconditioner", None), "hierarchy",
                   None) or getattr(slv, "hierarchy", None)
    if hier is None or not hier.levels:
        return None, None
    costs = [c for _, c in hier.level_costs()]
    if not costs:
        return None, None
    hc = costmodel.hierarchy_cost(costs)
    return int(hc["total_bytes_per_cycle"]), \
        [c.get("dtype") for c in costs]


def _bench_mixed_precision(oracle, make_matrix, cfg_str, dtype,
                           sync_shape, f32_case, f32_bytes, f32_dts):
    """bf16-hierarchy variant of the headline case (ISSUE 10): same
    solver stack with ``amg:hierarchy_dtype=bfloat16``, reporting
    iteration counts, modelled bytes/cycle and achieved GB/s per
    variant, plus the EFFECTIVE speedup — f32-equivalent work rate
    (f32 bytes-per-cycle ÷ per-cycle wall), so halved bytes at equal
    achieved bandwidth reads as ~2×."""
    import amgx_tpu as amgx
    hold = []
    case_bf = _run_case(
        oracle, make_matrix,
        amgx.AMGConfig(cfg_str + ", amg:hierarchy_dtype=bfloat16"),
        dtype, sync_shape=sync_shape, keep=hold)
    bf_bytes, bf_dts = _hier_cycle_bytes(hold[0])

    def _variant(case, byts, dts):
        percyc = case["solve_s"] / max(case["iterations"], 1)
        v = {"solve_s": case["solve_s"], "setup_s": case["setup_s"],
             "iterations": case["iterations"], "relres": case["relres"],
             "status": case["status"],
             "per_cycle_s": round(percyc, 6),
             "bytes_per_cycle": byts,
             "level_dtypes": dts}
        if byts:
            v["achieved_gbs"] = round(byts / max(percyc, 1e-12) / 1e9,
                                      1)
        return v

    out = {"f32": _variant(f32_case, f32_bytes, f32_dts),
           "bf16": _variant(case_bf, bf_bytes, bf_dts)}
    pc32 = out["f32"]["per_cycle_s"]
    pcbf = out["bf16"]["per_cycle_s"]
    if pc32 and pcbf:
        # f32-equivalent achieved rate ratio: both variants do the same
        # numerical work per cycle — charge both at the f32 bytes
        out["effective_speedup"] = round(pc32 / pcbf, 3)
        if f32_bytes:
            out["effective_gbs_f32equiv"] = round(
                f32_bytes / pcbf / 1e9, 1)
    out["iters_ratio"] = round(
        case_bf["iterations"] / max(f32_case["iterations"], 1), 3)
    return out, case_bf


def _chain_time(Adf, x, reps=3, k=256):
    """min-of-reps per-apply seconds of a K-long SpMV chain (the same
    amortised-chain estimator ``measure`` uses, self-contained so the
    module-level bench blocks can call it)."""
    import jax
    import jax.numpy as jnp

    from amgx_tpu.ops.spmv import spmv
    from functools import partial

    @partial(jax.jit, static_argnums=(2,))
    def chain(A, v, K):
        def body(i, v):
            return spmv(A, v) * jnp.asarray(1e-3, v.dtype)
        return jnp.sum(jax.lax.fori_loop(0, K, body, v))

    float(chain(Adf, x, k))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(chain(Adf, x, k))
        best = min(best, time.perf_counter() - t0)
    return best / k


def _bench_gauntlet(dtype, scale=1.0):
    """The real-matrix gauntlet (ISSUE 15): every block case solved
    through its matched config with iterations + achieved GB/s +
    GFLOP/s recorded — loaded via the MatrixMarket write → block_dim
    re-blocking read round trip, so the measured operator took the full
    user upload path.  Returns one flat dict per case
    (``gauntlet_<name>``) so perf_gate tracks each case's setup_s /
    solve_s / iterations like any other bench case."""
    import tempfile

    import jax.numpy as jnp
    import numpy as np
    import scipy.sparse as sp

    import amgx_tpu as amgx
    from amgx_tpu.core.matrix import pack_kind
    from amgx_tpu.io.gauntlet import gauntlet_cases, \
        load_via_matrix_market
    from amgx_tpu.telemetry import costmodel
    from amgx_tpu.telemetry.peaks import device_peak

    peak = device_peak()
    out = {}
    with tempfile.TemporaryDirectory() as td:
        for case in gauntlet_cases(scale=scale):
            try:
                sysd, _ = load_via_matrix_market(case, td)
                m = amgx.Matrix(sysd.A, block_dim=case.block_dim)
                m.device_dtype = dtype
                oracle = sp.csr_matrix(sysd.A)
                slv = amgx.create_solver(amgx.AMGConfig(case.cfg))
                t0 = time.perf_counter()
                slv.setup(m)
                setup_s = time.perf_counter() - t0
                b = np.ones(m.shape[0])
                slv.solve(b)                    # warm/compile
                t0 = time.perf_counter()
                res = slv.solve(b)
                solve_s = time.perf_counter() - t0
                x = np.asarray(res.x, np.float64)
                rr = float(np.linalg.norm(b - oracle @ x)
                           / np.linalg.norm(b))
                Ad = m.device()
                xs = jnp.asarray(np.random.default_rng(3)
                                 .standard_normal(m.shape[0]), dtype)
                per = _chain_time(Ad, xs)
                cost = costmodel.spmv_cost(Ad, nnz=oracle.nnz)
                gbs = costmodel.achieved_gbs(
                    cost["bytes_per_apply"] or 0, per)
                out[f"gauntlet_{case.name}"] = {
                    "n": int(m.shape[0]), "nnz": int(oracle.nnz),
                    "block_dim": case.block_dim,
                    "setup_s": round(setup_s, 4),
                    "solve_s": round(solve_s, 4),
                    "iterations": int(res.iterations),
                    "relres": rr, "pack": pack_kind(Ad),
                    "spmv_gbs": round(gbs, 2),
                    "spmv_gflops": round(
                        2.0 * oracle.nnz / max(per, 1e-12) / 1e9, 2),
                    "roofline_frac": (round(costmodel.roofline_fraction(
                        gbs, peak.hbm_gbs), 4) if peak else None),
                }
            except Exception as e:
                import traceback
                traceback.print_exc()
                out[f"gauntlet_{case.name}"] = {"error": str(e)[:200]}
    return out


def _bench_block_kernels(dtype):
    """Block-native vs scalar-expansion SpMV A/B on the b=4 gauntlet
    class (ISSUE 15 acceptance): the SAME scattered block operator
    packed both ways, per-apply chain-timed; ``block_spmv_speedup`` is
    the equal-work wall ratio (≡ effective-GB/s ratio) perf_gate pins
    at ≥ 1.5×.  The expansion pack stays available behind the
    ``AMGX_BLOCK_NATIVE=0`` knob / ``block_native=False``."""
    import jax.numpy as jnp
    import numpy as np

    from amgx_tpu.core.matrix import pack_device, pack_kind
    from amgx_tpu.io.gauntlet import scattered_block_operator
    from amgx_tpu.telemetry import costmodel

    nb = 12288
    bsr = scattered_block_operator(nb, 4)    # shared with prim_bench
    nnz_sc = int(bsr.nnz)                    # scipy BSR counts scalars
    x = jnp.asarray(np.random.default_rng(15)
                    .standard_normal(nb * 4), dtype)
    out = {"n": nb * 4, "nnz_scalar": nnz_sc, "block_dim": 4}
    packs = {}
    for label, native in (("native", True), ("expansion", False)):
        Ad = pack_device(bsr, 4, dtype, dia_max_diags=0,
                         block_native=native)
        per = _chain_time(Ad, x, k=64)
        cost = costmodel.spmv_cost(Ad, nnz=nnz_sc)
        gbs = costmodel.achieved_gbs(cost["bytes_per_apply"] or 0, per)
        packs[label] = per
        out[label] = {
            "pack": pack_kind(Ad), "per_apply_s": round(per, 8),
            "bytes_per_apply": cost["bytes_per_apply"],
            "achieved_gbs": round(gbs, 2),
            "gflops": round(2.0 * nnz_sc / max(per, 1e-12) / 1e9, 2),
        }
    # equal-work ratio: both packs apply the same operator, so the
    # wall ratio IS the effective-bandwidth ratio
    out["block_spmv_speedup"] = round(
        packs["expansion"] / max(packs["native"], 1e-12), 3)
    return out


def _warm_start_child() -> int:
    """One cold/warm-start probe process (``bench.py
    --warm-start-child``): import → classical setup → first solve, all
    timed as ``ready_s`` (process start to first answer — the number a
    serving rollout cares about).  The parent points
    JAX_COMPILATION_CACHE_DIR / AMGX_TPU_AOT_STORE at an emptied directory
    and runs this twice: run 1 is the cold baseline, run 2 measures
    the populated-cache warm start.  Emits ONE JSON line."""
    t_start = time.perf_counter()
    import jax
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu import telemetry
    from amgx_tpu.io import poisson7pt

    on_tpu = jax.default_backend() not in ("cpu",)
    n_side = int(os.environ.get("AMGX_WARM_CHILD_N",
                                "64" if on_tpu else "12"))
    cfg = amgx.AMGConfig(CFG_CLA + ", setup_profile=1")
    m = amgx.Matrix(poisson7pt(n_side, n_side, n_side))
    if on_tpu:
        m.device_dtype = np.float32
    b = np.ones(m.shape[0])
    with telemetry.capture() as tel:
        slv = amgx.create_solver(cfg)
        slv.setup(m)
        res = slv.solve(b)
        ready_s = time.perf_counter() - t_start
        # same-process re-run: a SECOND solver instance re-pays python
        # jit dispatch but hits the in-process + persistent caches —
        # the "restart the solver object, not the process" number
        t0 = time.perf_counter()
        slv2 = amgx.create_solver(cfg)
        slv2.setup(m)
        slv2.solve(b)
        rerun_s = time.perf_counter() - t0
    from amgx_tpu.serve.aot import store_stats
    from amgx_tpu.telemetry import setup_profile as _sp
    from amgx_tpu.utils.jaxcompat import compile_cache_stats
    sprof = _sp.summarize(_sp.analyze(tel.records)) or {}
    print(json.dumps({
        "ready_s": round(ready_s, 4),
        "rerun_s": round(rerun_s, 4),
        "setup_s": round(slv.setup_time, 4),
        "solve_s": round(res.solve_time, 4),
        "iterations": int(res.iterations),
        "n": int(m.shape[0]),
        "compile_share": sprof.get("compile_share"),
        "compile_cache": compile_cache_stats(),
        "aot": store_stats(),
    }))
    return 0


def _bench_warm_start(cpu: bool = False):
    """Cold vs warm start of a fresh process against one cache
    directory (the ISSUE-8 acceptance numbers): run the probe child
    twice with the same compile-cache/AOT-store dirs and report
    ``cold_start_s`` vs ``warm_start_s`` (+ each run's setup compile
    share, which the warm run must collapse).

    Called before this process touches JAX (a chip belongs to one
    process at a time).  The dirs are a FIXED in-checkout path, emptied
    first so the first run is cold."""
    import shutil
    import subprocess
    probe = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache", "warm_probe")
    shutil.rmtree(probe, ignore_errors=True)
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(probe, "xla"),
               AMGX_TPU_AOT_STORE=os.path.join(probe, "aot"))
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    runs = {}
    for label in ("cold", "warm"):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--warm-start-child"],
            env=env, capture_output=True, text=True, timeout=1800)
        parsed = None
        for line in reversed(r.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                    break
                except ValueError:
                    continue
        if r.returncode != 0 or parsed is None:
            print(f"[bench] warm-start child ({label}) failed: "
                  f"rc={r.returncode}\n{r.stderr[-2000:]}",
                  file=sys.stderr)
            return {"error": f"{label} child rc={r.returncode}"}
        runs[label] = parsed
    cold, warm = runs["cold"], runs["warm"]
    out = {
        "cold_start_s": cold["ready_s"],
        "warm_start_s": warm["ready_s"],
        "speedup": (round(cold["ready_s"] / warm["ready_s"], 2)
                    if warm["ready_s"] else None),
        "cold_setup_s": cold["setup_s"],
        "warm_setup_s": warm["setup_s"],
        "rerun_s": warm["rerun_s"],
        "cold_compile_share": cold.get("compile_share"),
        "warm_compile_share": warm.get("compile_share"),
        "warm_compile_cache": warm.get("compile_cache"),
        "warm_aot": {k: warm["aot"][k]
                     for k in ("loads", "saves", "entries", "bytes")}
        if warm.get("aot") else None,
        "n": cold.get("n"),
    }
    return out


#: weak-scaling bench geometry: FIXED rows per device — the grid grows
#: with the part count ((nx, ny, nz·parts) z-slabs, the natural 1D
#: stencil partition), so per-part work is constant and efficiency is
#: T(1 part) / T(p parts)
_DIST_NX = _DIST_NY = 10
_DIST_NZ_PER_PART = 6
#: classical distributed stack of the weak-scaling block: per-rank
#: PMIS/D1 setup, shard-local device Galerkin (device_setup_min_rows=0
#: so every distributed level's RAP runs the engine's dist path) and
#: agglomeration below 64 rows/device — the knobs the PR-12 acceptance
#: watches
_DIST_CFG = (
    "config_version=2, solver(out)=PCG, out:max_iters=100, "
    "out:monitor_residual=1, out:tolerance=1e-8, "
    "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
    "amg:algorithm=CLASSICAL, amg:selector=PMIS, amg:interpolator=D1, "
    "amg:max_iters=1, amg:interp_max_elements=4, amg:max_row_sum=0.9, "
    "amg:max_levels=6, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1, "
    "amg:presweeps=1, amg:postsweeps=1, amg:min_coarse_rows=8, "
    "amg:coarse_solver=DENSE_LU_SOLVER, determinism_flag=1, "
    "device_setup_min_rows=0, dist_agglomerate_min_rows=64")


def _distributed_child() -> int:
    """One weak-scaling probe process (``bench.py --distributed-child``,
    run by the parent under an 8-device CPU mesh): fixed rows/device
    across 1/2/4/8 parts of the classical distributed stack, reporting
    per-part setup/solve/iterations, per-level sub-mesh sizes, the
    halo-vs-local byte ratio, and the 8-part weak-scaling efficiency.
    Emits ONE JSON line (the parent embeds it as ``distributed``)."""
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu import telemetry
    from amgx_tpu.distributed.matrix import (make_mesh, shard_vector,
                                             unshard_vector)
    from amgx_tpu.io import poisson7pt

    out = {"rows_per_part": _DIST_NX * _DIST_NY * _DIST_NZ_PER_PART,
           "parts": []}
    per_part = {}
    for parts in (1, 2, 4, 8):
        A = poisson7pt(_DIST_NX, _DIST_NY, _DIST_NZ_PER_PART * parts)
        n = A.shape[0]
        b = np.ones(n)
        m = amgx.Matrix(A)
        m.set_distribution(make_mesh(parts))
        slv = amgx.create_solver(amgx.AMGConfig(_DIST_CFG))
        t0 = time.perf_counter()
        with telemetry.capture() as cap:
            slv.setup(m)
        setup_s = time.perf_counter() - t0
        bd = shard_vector(m.device(), b)
        with telemetry.capture() as scap:   # warm/compile solve
            slv.solve(bd)
        t0 = time.perf_counter()
        res = slv.solve(bd)
        solve_s = time.perf_counter() - t0
        x = unshard_vector(m.device(), np.asarray(res.x))
        relres = float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))
        overlap = [e["attrs"] for e in cap.events("dist_overlap")]
        rap = cap.counter_totals("amgx_device_rap_total", label="path")
        kc = [e["attrs"] for e in scap.events("krylov_comm")]
        case = {
            "parts": parts, "n": int(n),
            "setup_s": round(setup_s, 4),
            "solve_s": round(solve_s, 4),
            "iterations": int(res.iterations),
            "relres": relres,
            # per-level sub-mesh sizes: (rows, active ranks) fine→coarse
            "level_submesh": [[int(d.get("rows", 0)),
                               int(d.get("submesh_parts", 0))]
                              for d in overlap],
            "halo_local_ratio": (overlap[0].get("halo_local_ratio")
                                 if overlap else None),
            "agglomerations": len(cap.events("dist_agglomerate")),
            "rap_by_path": {str(k): int(v)
                            for k, v in sorted(rap.items())},
            "collectives_per_iter": (int(kc[-1]["collectives_per_iter"])
                                     if kc else None),
        }
        out["parts"].append(case)
        per_part[parts] = case
    out["parts_max"] = max(per_part)
    if 1 in per_part and 8 in per_part:
        t1 = per_part[1]["solve_s"]
        t8 = per_part[8]["solve_s"]
        # weak-scaling efficiency: same per-device work, so perfect
        # scaling is equal wall time (ratio 1.0).  NOTE on the CPU
        # mesh the 8 "devices" share one host's cores, so the measured
        # efficiency is a lower bound the perf gate pins as a floor
        out["weak_eff_8"] = round(t1 / t8, 4) if t8 else None
        out["halo_frac_8"] = per_part[8]["halo_local_ratio"]
        out["submesh_8"] = per_part[8]["level_submesh"]
    # ISSUE 16 A/B: re-solve the full 8-part system with
    # krylov_comm=CA (single-reduction CG) against the CLASSIC run
    # above.  collectives_per_iter comes from the trace-time ledger
    # behind amgx_krylov_collectives_total, so the "halved" acceptance
    # is counted per iteration, not modelled.
    try:
        ca = amgx.create_solver(
            amgx.AMGConfig(_DIST_CFG + ", out:krylov_comm=CA"))
        ca.setup(m)
        with telemetry.capture() as ccap:
            ca_res = ca.solve(bd)
        x_ca = unshard_vector(m.device(), np.asarray(ca_res.x))
        kc_ca = [e["attrs"] for e in ccap.events("krylov_comm")]
        cpi_classic = per_part[8].get("collectives_per_iter")
        cpi_ca = (int(kc_ca[-1]["collectives_per_iter"])
                  if kc_ca else None)
        out["krylov_ab_8"] = {
            "coll_per_iter_classic": cpi_classic,
            "coll_per_iter_ca": cpi_ca,
            "coll_ratio": (round(cpi_classic / cpi_ca, 3)
                           if cpi_classic and cpi_ca else None),
            "ca_iterations": int(ca_res.iterations),
            "ca_relres": float(np.linalg.norm(b - A @ x_ca)
                               / np.linalg.norm(b)),
        }
    except Exception as e:   # A/B must not sink the weak-scaling block
        out["krylov_ab_8"] = {"error": f"{type(e).__name__}: {e}"}
    # measured (not modelled) overlap: profile one 8-part solve and let
    # telemetry.overlap classify the trace's comm-vs-compute spans.  On
    # the forced CPU mesh XLA rarely names its fused collectives, so
    # None here means "no comm ops in the trace" — honest, not an error.
    try:
        import tempfile

        import jax
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                slv.solve(bd)
            trace = telemetry.overlap.find_trace_file(td)
            out["measured_overlap_8"] = (telemetry.overlap.measure(trace)
                                         if trace else None)
    except Exception as e:
        out["measured_overlap_8"] = {"error": f"{type(e).__name__}: {e}"}
    # ISSUE 20: the mesh flight recorder over the warm 8-part solve.
    # One process IS the whole virtual mesh (SPMD on the forced CPU
    # device count), so per-rank traces are simulated by re-appending
    # the one real session under 8 distinct (pid, session) identities
    # — every rank shares the timeline, so expected wait is ~0 and the
    # block smokes the join/attribution path honestly ("virtual": the
    # numbers are not 8 independent processes).
    try:
        from amgx_tpu.telemetry.export import _json_line, _meta_record
        from amgx_tpu.telemetry.meshtrace import analyze
        lines = []
        for rk in range(8):
            meta = _meta_record()
            meta["session"] = f"benchmesh{rk:03x}"
            lines.append(_json_line(meta))
            lines.extend(_json_line(r) for r in scap.records)
        mesh = analyze(lines)
        ranks = mesh.get("ranks") or {}
        wait_share = {
            str(r): (round(d["wait_s"] / d["wall_s"], 4)
                     if d["wall_s"] else 0.0)
            for r, d in sorted(ranks.items())}
        stragglers = sorted(((d["straggler_score"], r)
                             for r, d in ranks.items()), reverse=True)
        out["mesh"] = {
            "virtual": True,
            "measured": bool(mesh["measured"]),
            "n_ranks": int(mesh["n_ranks"]),
            "collectives": mesh["collectives"],
            "total_wait_s": mesh["total_wait_s"],
            "wait_share": wait_share,
            "max_wait_share": (max(wait_share.values())
                               if wait_share else None),
            "straggler": [[int(r), round(s, 4)]
                          for s, r in stragglers[:3]],
        }
    except Exception as e:   # the recorder must not sink the block
        out["mesh"] = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps(out))
    return 0


def _bench_distributed():
    """Weak-scaling distributed block: run the probe child on a forced
    8-device CPU mesh (``xla_force_host_platform_device_count``) — the
    same virtual-mesh harness the distributed test tier uses — so every
    bench round measures the pod-scale path even on single-chip rigs.
    Skipped with AMGX_BENCH_DISTRIBUTED=0."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--distributed-child"],
        env=env, capture_output=True, text=True, timeout=1800)
    parsed = None
    for line in reversed(r.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except ValueError:
                continue
    if r.returncode != 0 or parsed is None:
        print(f"[bench] distributed child failed: rc={r.returncode}\n"
              f"{r.stderr[-2000:]}", file=sys.stderr)
        return {"error": f"child rc={r.returncode}"}
    return parsed


def _bench_chaos(make_matrix, cfg_str, dtype, scope="out"):
    """AMGX_BENCH_CHAOS=1: inject ONE NaN-poison fault into the
    headline solver stack with the recovery ladder armed, and report
    the recovered-solve overhead vs the clean solve.

    The recovered wall time includes everything a real chaos event
    costs: the early in-loop detection, the injection-armed retrace of
    the solve body, and the ladder's restart solve — so ``overhead_x``
    is the honest price of surviving one poisoned solve, not just the
    extra iterations.  A final clean solve proves the disarmed path
    retraces back to the fast body."""
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu.errors import SolveStatus
    from amgx_tpu.utils import faultinject

    m = make_matrix()
    n = m.shape[0]
    b = np.ones(n, dtype=np.float64)
    cfg = amgx.AMGConfig(cfg_str + f", {scope}:recovery_policy=AUTO")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    slv.solve(b)                       # warm: compile the clean body
    t0 = time.perf_counter()
    r_clean = slv.solve(b)
    clean_s = time.perf_counter() - t0
    faultinject.configure("values_nan:iter=3:count=1")
    try:
        t0 = time.perf_counter()
        r_chaos = slv.solve(b)
        recovered_s = time.perf_counter() - t0
        injected = faultinject.stats()
    finally:
        faultinject.reset()
    r_after = slv.solve(b)             # disarmed: clean retrace works
    return {
        "clean_solve_s": round(clean_s, 6),
        "recovered_solve_s": round(recovered_s, 6),
        "overhead_x": (round(recovered_s / clean_s, 3)
                       if clean_s > 0 else None),
        "recovered": bool(r_chaos.status == SolveStatus.SUCCESS),
        "recovery": r_chaos.recovery,
        "clean_iterations": int(r_clean.iterations),
        "recovered_iterations": int(r_chaos.iterations),
        "after_status": int(r_after.status),
        "injected": injected,
    }


def _bench_serving(n_side: int = 12, n_requests: int = 32):
    """Serving-mode benchmark: drive the request-level layer
    (amgx_tpu/serve/) with concurrent same-pattern traffic and report
    latency percentiles + cache/batch behaviour — the SLO-shaped
    numbers (p50/p95/p99, throughput) the solve-time headline cannot
    show.  Small operator on purpose: this measures the serving
    machinery (admission, batching, session reuse), not SpMV."""
    import numpy as np

    import amgx_tpu as amgx
    from amgx_tpu.io import poisson7pt
    from amgx_tpu.serve import SolveService

    A = poisson7pt(n_side, n_side, n_side)
    cfg_str = (
        "config_version=2, solver(out)=PCG, out:max_iters=200, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER, "
        "serve_batch_window_ms=2, serve_workers=2, serve_max_batch=8, "
        # live observability (ISSUE 9): an SLO objective so attainment
        # and burn rate are meaningful, and solve-path profiling every
        # 4th batch for the achieved-vs-roofline numbers
        "slo_latency_ms=2000, slo_target=0.99, serve_profile_every=4")
    cfg = amgx.AMGConfig(cfg_str)
    m = amgx.Matrix(A)
    rng = np.random.default_rng(5)
    n = A.shape[0]
    svc = SolveService(cfg)
    try:
        # warm: first request pays setup + the k=1 compile; batch sizes
        # are bucketed to powers of two (serve/batch.py), so compiling
        # each bucket width ONCE leaves the timed wave compile-free —
        # the steady state a long-running service sits in
        svc.solve(m, rng.standard_normal(n), timeout=300)
        sess, _ = svc.cache.get_or_create(svc.cfg, m)
        for w in (2, 4, 8):
            sess.solve_batch(rng.standard_normal((w, n)))
        svc.reset_latency_stats()
        t0 = time.perf_counter()
        pend = [svc.submit(m, rng.standard_normal(n))
                for _ in range(n_requests)]
        ok = sum(1 for p in pend
                 if p.wait(300) is not None and p.rc == 0)
        wall = time.perf_counter() - t0
        lat = svc.latency_percentiles()
        st = svc.stats()
        # open-loop SLO probe (serve/loadgen.py): Poisson arrivals at a
        # fixed offered rate AFTER the closed wave's stats are captured
        # (run_load resets the latency window) — rejection rate under
        # un-throttled arrivals is the number the closed wave cannot show
        try:
            from amgx_tpu.serve.loadgen import run_load
            open_loop = run_load(svc, [m], rps=25.0, duration_s=1.5,
                                 seed=7)
        except Exception as e:
            print(f"[bench] open-loop probe failed: {e}",
                  file=sys.stderr)
            open_loop = {"error": str(e)[:200]}
        # re-snapshot AFTER the open-loop probe: run_load reset the SLO
        # window, so this SLO/phase/profile picture is the open-loop
        # steady state, not the closed warm-up wave's.  `st` (the
        # closed-wave snapshot above) keeps feeding cache/setups/
        # rejected so those fields stay comparable with pre-probe
        # rounds and rejections are not double-reported next to
        # open_loop["rejected"]
        st_open = svc.stats()
        # multi-device scale-out probe (serve/router.py): single-lane
        # vs min(4, ndev)-lane aggregate throughput under ~10× overload
        # — the perf_gate `scaling` metric's source (skipped on
        # single-device hosts and under AMGX_BENCH_SCALING=0)
        scaling = None
        if os.environ.get("AMGX_BENCH_SCALING", "1") != "0":
            try:
                overload_rps = min(max(10.0 * n_requests / wall, 50.0),
                                   400.0)
                scaling = _bench_scaling(cfg_str, rps=overload_rps)
            except Exception as e:
                print(f"[bench] scaling probe failed: {e}",
                      file=sys.stderr)
                scaling = {"error": str(e)[:200]}
        return {
            "n": int(n),
            "requests": int(n_requests),
            "completed": int(ok),
            "wall_s": round(wall, 4),
            "throughput_rps": round(n_requests / wall, 1),
            "p50_ms": (round(lat["p50"] * 1e3, 2)
                       if lat["p50"] is not None else None),
            "p95_ms": (round(lat["p95"] * 1e3, 2)
                       if lat["p95"] is not None else None),
            "p99_ms": (round(lat["p99"] * 1e3, 2)
                       if lat["p99"] is not None else None),
            "cache": {k: st["cache"][k] for k in
                      ("sessions", "hits", "misses", "evictions")},
            "setups": {k: st["cache"]["by_session"][0][k]
                       for k in ("full_setups", "resetups", "value_hits")}
            if st["cache"]["by_session"] else {},
            "rejected": int(st["rejected"]),
            "open_loop": open_loop,
            # SLO attainment + error-budget burn rate over the probe
            # window, and the queue-wait vs solve phase split — the
            # live-observability numbers (telemetry/slo.py)
            "slo": {k: st_open["slo"].get(k)
                    for k in ("attainment", "burn_rate",
                              "rejection_rate", "overloaded",
                              "by_outcome")},
            "phase_split": st_open.get("phase_split"),
            # sampled solve-path profiling (serve_profile_every):
            # per-pattern achieved-vs-roofline from fenced batches
            "profile": st_open.get("profile"),
            # multi-lane scale-out: lanes / agg_rps / speedup / steal%
            "scaling": scaling,
        }
    finally:
        svc.shutdown()


def _bench_scaling(cfg_str: str, rps: float, duration_s: float = 2.0):
    """Serving scale-out probe: the SAME open-loop overload wave (10×
    the calibrated single-lane capacity, four small operators) against
    a single-lane service and a min(4, ndev)-lane one — aggregate
    achieved throughput should approach linear in lane count
    (perf_gate's `scaling` metric pins 4-lane ≥ 3× single-lane).
    Affinity routing partitions the uniform pattern mix one-per-lane,
    so the wave serves from four resident hierarchies in parallel."""
    import scipy.sparse as sp

    import amgx_tpu as amgx
    import jax
    from amgx_tpu.io import poisson5pt, poisson7pt
    from amgx_tpu.serve import SolveService
    from amgx_tpu.serve.loadgen import run_load

    ndev = len(jax.devices())
    lanes = min(4, ndev)
    if lanes < 2:
        return {"skipped": f"needs >=2 visible devices (have {ndev})"}
    patterns = [amgx.Matrix(poisson7pt(8, 8, 8)),
                amgx.Matrix(poisson7pt(9, 9, 9)),
                amgx.Matrix(sp.csr_matrix(poisson5pt(18, 18))),
                amgx.Matrix(sp.csr_matrix(poisson5pt(22, 22)))]
    # uniform pattern mix for the SCALING metric: affinity partitions
    # the four patterns across the four lanes (cold placement spreads
    # homes), so aggregate throughput measures the lane fabric, not
    # mid-wave replication setups.  The skewed/replication behaviour
    # is covered by tests/test_serve_scale.py and the loadgen --skew
    # knob; its steal/replication counters still report here
    out = {"lanes": lanes, "skew": 0.0, "patterns": len(patterns)}

    def _measure(svc, at_rps):
        res = run_load(svc, patterns, rps=at_rps,
                       duration_s=duration_s, skew=0.0,
                       multi_rhs_frac=0.25, seed=11)
        return {"achieved_rps": res["achieved_rps"],
                "rejection_rate": res["rejection_rate"],
                "p99_ms": res["p99_ms"],
                "attainment": res["attainment"],
                "gen_slip_s": res["max_slip_s"]}

    svc1 = SolveService(amgx.AMGConfig(cfg_str + ", serve_lanes=1"))
    try:
        svc1.warmup(patterns)
        # calibration: a below-capacity wave measures nothing (both
        # configs would serve everything and "speedup" reads 1.0) —
        # probe the single lane's capacity first, then offer 10× that
        # to BOTH configs so each measures what it can actually serve
        cal = _measure(svc1, at_rps=rps)
        cap1 = cal["achieved_rps"] or rps
        overload_rps = max(10.0 * cap1, rps)
        out["calibration_rps"] = cap1
        out["offered_rps"] = round(overload_rps, 1)
        out["single"] = _measure(svc1, at_rps=overload_rps)
    finally:
        svc1.shutdown()
    svcN = SolveService(amgx.AMGConfig(
        cfg_str + f", serve_lanes={lanes}"))
    try:
        # home-lane warmup only: cold placement spreads the four
        # patterns one-per-lane, so the wave serves from four resident
        # hierarchies in parallel (warmup(all_lanes=True) is the
        # pre-replication mode for hot-key fleets — too compile-heavy
        # for a bench probe without a warmed AOT store)
        svcN.warmup(patterns)
        entry = _measure(svcN, at_rps=overload_rps)
        st = svcN.stats()
        rt = st["router"]
        routed = sum(rt["decisions"].values()) or 1
        entry.update(
            steals=rt["steals"],
            replications=rt["replications"],
            steal_frac=round(rt["steals"] / routed, 4),
            sessions_by_lane=rt["sessions_by_lane"],
            lanes_overloaded=sum(1 for l in st["lanes"]
                                 if l["overloaded"]))
        out["multi"] = entry
    finally:
        svcN.shutdown()
    s1 = out["single"]["achieved_rps"] or 0
    sL = out["multi"]["achieved_rps"] or 0
    out["agg_rps"] = sL
    out["speedup"] = round(sL / s1, 3) if s1 else None
    return out


def _errors_in(obj, path="") -> list:
    """Paths of every block in the result that recorded an error (its
    message under ``"error"``; an ``"error"`` COUNT, such as the
    serving SLO's ``by_outcome`` tally, is a measurement, not a
    failure)."""
    found = []
    if isinstance(obj, dict):
        if isinstance(obj.get("error"), str):
            found.append(path or "/")
        for k, v in obj.items():
            if k != "error":
                found += _errors_in(v, f"{path}/{k}")
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            found += _errors_in(v, f"{path}/{i}")
    return found


def main():
    argv = [a for a in sys.argv[1:] if a != "--cpu"]
    want_cpu = len(argv) != len(sys.argv) - 1
    # a SIGTERM (a time limit) unwinds as SystemExit, so subprocess.run
    # kills the warm-start child it is waiting on instead of leaving it
    # to hold the chip
    signal.signal(signal.SIGTERM, lambda sig, _f: sys.exit(128 + sig))

    # zero cold-start probe (ISSUE 8): cold vs warm fresh-process start
    # against one cache dir.  It runs FIRST, while this process has not
    # touched JAX: each of its two child processes holds the chip in
    # turn, and a chip belongs to one process at a time.
    # AMGX_BENCH_WARM_START=0 skips it.
    warm_start = None
    if os.environ.get("AMGX_BENCH_WARM_START", "1") != "0":
        try:
            warm_start = _bench_warm_start(want_cpu)
        except Exception as e:
            import traceback
            print(f"[bench] warm-start benchmark failed: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            warm_start = {"error": str(e)[:200]}

    import jax
    import jax.numpy as jnp
    import numpy as np

    # backend/device init is the one failure mode that must produce a
    # STRUCTURED diagnostic: a flaky TPU worker otherwise
    # leaves an unparseable traceback and an empty bench record.  A
    # transient worker hiccup gets ONE retry after a short backoff
    # (the shared utils/retry.py driver; only failures that READ as
    # device-init burn the attempt) before the round is declared
    # unusable; either way the JSON carries ``retried`` so flaky and
    # dead rounds stay distinguishable
    from amgx_tpu.utils.retry import retry_call
    retried = False

    def _init_backend():
        b = jax.default_backend()
        jax.devices()
        return b

    def _note_retry(exc, _attempt):
        nonlocal retried
        retried = True
        print(f"[bench] device init failed "
              f"({type(exc).__name__}); retrying in 10s",
              file=sys.stderr)

    try:
        backend = retry_call(_init_backend, max_attempts=2,
                             base_delay_s=10.0,
                             retryable=_is_device_init_error,
                             on_retry=_note_retry, label="bench_init")
    except Exception as e:
        return _emit_error_json("device_unavailable", e,
                                retried=retried)
    on_tpu = backend == "tpu"
    if not on_tpu and not want_cpu:
        # never a CPU case under the chip's metric names
        return _emit_error_json("device_unavailable", RuntimeError(
            f"no TPU backend (JAX chose {backend!r}); pass --cpu for an "
            "explicit CPU run"))

    import amgx_tpu as amgx
    from amgx_tpu.io import poisson7pt, poisson7pt_device
    from amgx_tpu.io.device_gen import precompile_poisson7pt
    from amgx_tpu.ops.spmv import spmv

    n_side = 128 if on_tpu else 48
    if argv:
        n_side = int(argv[0])

    # AMGX_BENCH_FORENSICS=1: add cycle-anatomy instrumentation to the
    # solve cases (3 extra residual SpMVs per level per cycle — NOT the
    # telemetry-off parity mode; use for convergence investigations)
    fore_knob = ", forensics=1" \
        if os.environ.get("AMGX_BENCH_FORENSICS") == "1" else ""
    # AMGX_BENCH_SETUP_PROFILE=1: setup attribution
    # (telemetry/setup_profile.py) — per-phase compile/transfer/memory
    # splits embedded in every case's telemetry block, so BENCH rounds
    # carry WHERE setup time went, not just how much there was
    if os.environ.get("AMGX_BENCH_SETUP_PROFILE") == "1":
        fore_knob += ", setup_profile=1"

    dtype = np.dtype(np.float32 if on_tpu else np.float64)
    # generated ON DEVICE (io/device_gen.py) — the reference's built-in
    # generator (AMGX_generate_distributed_poisson_7pt) assembles on the
    # GPU the same way; host keeps the analytic diagonals only
    m = poisson7pt_device(n_side, n_side, n_side, device_dtype=dtype)
    n = m.shape[0]
    # headline-size CSR serves the per-format repacks and the residual
    # oracle — but only at sizes where assembling it is sane; above the
    # repack gate the dia-array oracle serves instead (never a 256³ CSR)
    A = m.host if n <= 3_000_000 else None
    nnz = m.nnz

    # ---------------- SpMV throughput (amortised chain) ----------------
    Ad = m.device()
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n), dtype)

    from functools import partial

    @partial(jax.jit, static_argnums=(2,))
    def spmv_chain(A, v, K):
        # the matrix rides as a jit ARGUMENT (a closure would bake ~0.5 GB
        # of constants into the executable at 256^3 and kill the compile)
        def body(i, v):
            return spmv(A, v) * jnp.asarray(1e-3, v.dtype)
        v = jax.lax.fori_loop(0, K, body, v)
        return jnp.sum(v)

    def timed(K, Adf, reps=3, xv=None):
        """min-of-reps wall time of one K-iteration chain: host-fetch
        latency noise is one-sided, so the minimum is the faithful
        estimator."""
        xv = x if xv is None else xv
        float(spmv_chain(Adf, xv, K))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(spmv_chain(Adf, xv, K))  # host fetch = true sync
            best = min(best, time.perf_counter() - t0)
        return best

    def measure(Adf, target_s=1.0, kmax=60000, kcal=512, nnz=None,
                nr=None, xv=None):
        """Slope measurement with an auto-calibrated span: the chain is
        lengthened until the device-side signal (~target_s) dominates the
        host sync noise — a fixed short span reads impossible rates."""
        nnz = nnz if nnz is not None else m.nnz
        nr = nr if nr is not None else n
        xv = xv if xv is not None else x
        per = max((timed(kcal, Adf, xv=xv) - timed(0, Adf, xv=xv)) / kcal,
                  1e-8)
        # cap any single chain at ~4 s of device time
        k2 = int(min(kmax, max(kcal, min(target_s, 4.0) / per)))
        k1 = k2 // 8
        d = timed(k2, Adf, xv=xv) - timed(k1, Adf, xv=xv)
        span = k2 - k1
        if d <= 0:          # noise still won: widen to the full chain
            d, span = timed(k2, Adf, xv=xv) - timed(0, Adf, xv=xv), k2
        t = d / span if d > 0 else 1e-9
        itemsize = dtype.itemsize
        if Adf.fmt == "dia3":
            # Galerkin composition: each factor's diagonal rows stream
            # once, plus the two intermediates and x/y
            nd3 = (len(Adf.P.dia_offsets) + len(Adf.A.dia_offsets)
                   + len(Adf.R.dia_offsets) + 6)
            bytes_moved = nd3 * Adf.n_rows * itemsize
        elif Adf.fmt == "dia":
            # block-DIA planes count b² value slots per offset row
            bb = Adf.block_dim
            bytes_moved = (Adf.ell_width * bb * bb + 2 * bb) \
                * Adf.n_rows * itemsize
        elif Adf.fmt == "ell" and Adf.sh_vals is not None:
            # tile-DIA shift pack: class-value rows + per-class x windows
            # + y (no per-entry column data at all)
            T, n_tiles, Dpad, _pad, _L = Adf.sh_dims
            bytes_moved = (n_tiles * Dpad * (T + (T // 128 + 1) * 128)
                           + nr) * itemsize
        elif Adf.fmt == "ell" and Adf.bn_codes is None:
            # values + int32 column indices
            bytes_moved = (Adf.ell_width + 2) * nr * itemsize + \
                Adf.ell_width * nr * 4
        elif Adf.bn_codes is not None:
            # binned sliced-ELL kernel: codes+vals planes stream once,
            # one (Sb, 128) x segment per chunk (× b sub-lanes for
            # block-native planes), y once
            from amgx_tpu.ops.pallas_csr import bn_block_dim
            bb = bn_block_dim(Adf.bn_dims)
            L = int(Adf.bn_codes.size)
            C = int(Adf.bn_dims[0])
            Sb = int(Adf.bn_dims[4])
            bytes_moved = L * (4 + bb * bb * itemsize) + \
                C * Sb * 128 * bb * itemsize + nr * itemsize
        else:  # CSR: nnz vals + int32 cols/row_ids + x/y vectors
            bytes_moved = nnz * (itemsize + 8) + 2 * nr * itemsize
        return t, 2.0 * nnz / t / 1e9, bytes_moved / t / 1e9

    spmv_t, spmv_gflops, spmv_gbs = measure(Ad)
    # the chip's published peaks (telemetry/peaks.py); None on the CPU
    from amgx_tpu.telemetry.peaks import device_peak
    peak = device_peak()
    # per-format throughput (BASELINE.md metric 2 wants CSR GFLOPS/chip):
    # repack the same operator as ELL (gather) and CSR (segment-sum)
    from amgx_tpu.core.matrix import pack_device
    fmt_stats = {Ad.fmt: round(spmv_gflops, 2)}
    for fmt_name, kw in (("ell", dict(dia_max_diags=0)),
                         ("ell_onehot", dict(dia_max_diags=0,
                                             use_shift=False)),
                         ("csr", dict(dia_max_diags=0, ell_max_width=0))):
        if n > 3_000_000:
            break      # gather formats at 256³ exceed sane bench time
        Af = pack_device(m.host, 1, dtype, **kw)
        try:
            kb = dict(kmax=30000, kcal=64) if fmt_name == "ell" \
                else dict(kmax=2000, kcal=8)
            _, gf, gbs = measure(Af, target_s=1.5 if fmt_name == "ell"
                                 else 0.5, **kb)
            fmt_stats[fmt_name] = round(gf, 2)
            if fmt_name == "ell":
                fmt_stats["ell_eff_gbs"] = round(gbs, 1)
        except Exception as e:      # a crashed format measurement must
            # not take down the headline run (its error still fails it)
            fmt_stats[fmt_name] = {"error": str(e)[:200]}
            print(f"[bench] {fmt_name} measurement failed: {e}",
                  file=sys.stderr)

    # gather-cliff rescue (solvers/base._maybe_reorder): a randomly
    # permuted Poisson misses both the DIA and window gates; RCM at
    # setup restores the windowed kernel.  Measured on a 64³ operator
    # (the permutation+RCM host cost at 128³ has no bearing on the
    # steady-state SpMV rate being reported).
    if on_tpu:
        try:
            import scipy.sparse as sp
            from scipy.sparse.csgraph import reverse_cuthill_mckee
            Ar = sp.csr_matrix(poisson7pt(64, 64, 64))
            rng = np.random.default_rng(1)
            pr = rng.permutation(Ar.shape[0])
            Ar = Ar[pr][:, pr].tocsr()
            rcm = np.asarray(reverse_cuthill_mckee(
                Ar, symmetric_mode=False))
            Arr = Ar[rcm][:, rcm].tocsr()
            Adr = pack_device(Arr, 1, dtype, dia_max_diags=0)
            assert Adr.win_codes is not None, "RCM rescue did not fit"
            xr = jnp.asarray(rng.standard_normal(Arr.shape[0]), dtype)
            _, gf, _ = measure(Adr, target_s=0.5, kmax=4000, kcal=16,
                               nnz=Arr.nnz, nr=Arr.shape[0], xv=xr)
            fmt_stats["ell_rcm_rescued"] = round(gf, 2)
        except Exception as e:
            fmt_stats["ell_rcm_rescued"] = {"error": str(e)[:200]}
            print(f"[bench] rcm rescue measurement failed: {e}",
                  file=sys.stderr)

    # general-sparsity binned kernel (ops/pallas_csr.py): a ~1%
    # scattered random matrix and an uploaded MatrixMarket system —
    # neither fits the DIA/shift/window gates, so these track the
    # binned path's GFLOPS class per round
    if on_tpu:
        from amgx_tpu.core.matrix import pack_kind

        def bench_scattered(label, Ax, seed):
            import scipy.sparse as sp
            Ax = sp.csr_matrix(Ax)
            Adx = pack_device(Ax, 1, dtype, dia_max_diags=0)
            print(f"[bench] {label} pack: {pack_kind(Adx)}",
                  file=sys.stderr)
            xv = jnp.asarray(np.random.default_rng(seed)
                             .standard_normal(Ax.shape[1]), dtype)
            _, gf, gbs = measure(Adx, target_s=0.5, kmax=4000, kcal=16,
                                 nnz=Ax.nnz, nr=Ax.shape[0], xv=xv)
            fmt_stats[label] = round(gf, 2)
            fmt_stats[label + "_pack"] = pack_kind(Adx)
            return gbs

        try:
            import scipy.sparse as sp
            ns = 16384
            As = sp.random(ns, ns, density=0.01, random_state=8,
                           format="csr", dtype=np.float64)
            gbs = bench_scattered("binned_scattered_1pct", As, 9)
            fmt_stats["binned_scattered_eff_gbs"] = round(gbs, 1)
        except Exception as e:
            fmt_stats["binned_scattered_1pct"] = {"error": str(e)[:200]}
            print(f"[bench] scattered binned measurement failed: {e}",
                  file=sys.stderr)
        try:
            # uploaded-MatrixMarket path: write + read through the real
            # reader (io/matrix_market.py, the AMGX_read_system analog)
            # so the measured operator took the full upload route
            import tempfile

            import scipy.sparse as sp
            from amgx_tpu.io.matrix_market import (read_matrix_market,
                                                   write_matrix_market)
            nm = 8192
            rngm = np.random.default_rng(12)
            Am = (sp.random(nm, nm, density=0.004, random_state=12,
                            format="csr", dtype=np.float64)
                  + sp.diags(rngm.uniform(4.0, 5.0, nm))).tocsr()
            with tempfile.NamedTemporaryFile("w", suffix=".mtx",
                                             delete=False) as fh:
                path_mm = fh.name
            write_matrix_market(path_mm, Am)
            sysd = read_matrix_market(path_mm)
            bench_scattered("binned_mm_uploaded", sysd.A, 13)
            os.unlink(path_mm)
        except Exception as e:
            fmt_stats["binned_mm_uploaded"] = {"error": str(e)[:200]}
            print(f"[bench] matrixmarket binned measurement failed: {e}",
                  file=sys.stderr)

    # ---------------- FGMRES + aggregation AMG ----------------
    # restart 6: AMG+CG-cycle preconditioning converges identically with a
    # short Krylov memory, and FGMRES orthogonalisation traffic scales
    # with the basis size (measured best total time at 128³ and 256³);
    # 2+2 sweeps trades slightly costlier cycles for fewer iterations
    cfg_str = (
        "config_version=2, solver(out)=FGMRES, out:max_iters=100, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:gmres_n_restart=6, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=GEO, amg:max_iters=1, amg:max_levels=20, "
        "amg:cycle=CG, amg:cycle_iters=2, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:presweeps=2, amg:postsweeps=2, amg:min_coarse_rows=32, "
        "amg:coarse_solver=DENSE_LU_SOLVER" + fore_knob)
    cfg = amgx.AMGConfig(cfg_str)
    precompile_poisson7pt(n_side, n_side, n_side, dtype)
    hold_f32 = []
    case = _run_case(
        A, lambda: poisson7pt_device(n_side, n_side, n_side,
                                     device_dtype=dtype),
        cfg, dtype, sync_shape=(7, n), keep=hold_f32)

    # mixed-precision A/B (ISSUE 10): the SAME headline stack with a
    # bf16-stored hierarchy under the f32 Krylov — iteration counts,
    # bytes/cycle and the effective (f32-equivalent) speedup; a failure
    # here must not take down the headline JSON line
    mixed = None
    case_bf16 = None
    try:
        f32_bytes, f32_dts = _hier_cycle_bytes(hold_f32[0]) \
            if hold_f32 else (None, None)
        mixed, case_bf16 = _bench_mixed_precision(
            A, lambda: poisson7pt_device(n_side, n_side, n_side,
                                         device_dtype=dtype),
            cfg_str, dtype, (7, n), case, f32_bytes, f32_dts)
    except Exception as e:
        import traceback
        print(f"[bench] mixed-precision benchmark failed: {e}",
              file=sys.stderr)
        traceback.print_exc()
        mixed = {"error": str(e)[:200]}

    # north-star scale (BASELINE config 3: 256³ FGMRES + aggregation AMG):
    # measured in the same run when the headline ran at the default size
    big = {}
    extra_cases = {}
    if on_tpu and n_side == 128 and not argv:
        # a failing extra case leaves its error in the JSON (and makes
        # the exit code nonzero) without taking down the headline line
        def guarded(label, fn):
            try:
                return fn()
            except Exception as e:
                import traceback
                print(f"[bench] {label} failed: {e}", file=sys.stderr)
                traceback.print_exc()     # distinguish real regressions
                return {"error": str(e)[:200]}

        def case_256():
            # generated on device; the true-residual check runs off the
            # host analytic diagonals — no 110M-nnz CSR is ever built
            precompile_poisson7pt(256, 256, 256, dtype)
            return _run_case(
                None, lambda: poisson7pt_device(256, 256, 256,
                                                device_dtype=dtype),
                cfg, dtype, sync_shape=(7, 256 ** 3))

        big = guarded("poisson256", case_256)

        # one classical config string shared by every classical case so
        # they always benchmark the same solver stack (module-level
        # CFG_CLA; coarse operators ride the windowed-ELL kernel)
        cfg_cla_str = CFG_CLA + fore_knob

        def case_cla():
            # UPLOADED host matrix on purpose: this case keeps the
            # AMGX_matrix_upload_all path timed (generated cases above
            # exercise the on-device generator)
            A3 = poisson7pt(64, 64, 64)
            m3 = amgx.Matrix(A3)
            m3.device_dtype = np.float32
            cla = amgx.AMGConfig(cfg_cla_str)
            holder = []
            out3 = _run_case(A3, lambda: m3, cla, dtype,
                             sync_shape=(7, A3.shape[0]), keep=holder)
            # classical-coarse representative SpMV:
            # the level-1 operator in its actual solve representation
            # (dia3 Galerkin composition / embedded DIA), measured on
            # its true nnz
            try:
                hier3 = holder[0].preconditioner.hierarchy
                if len(hier3.levels) > 1:
                    lvl1 = hier3.levels[1]
                    Ad1 = lvl1.Ad
                    if Ad1.fmt in ("dia3", "dia"):
                        n1 = Ad1.n_rows
                        x1 = jnp.asarray(np.random.default_rng(4)
                                         .standard_normal(n1), dtype)
                        _, gf, gbs = measure(
                            Ad1, target_s=0.5, kmax=8000, kcal=32,
                            nnz=lvl1.A.nnz, nr=n1, xv=x1)
                        fmt_stats["classical_coarse_" + Ad1.fmt] = \
                            round(gf, 2)
                        fmt_stats["classical_coarse_eff_gbs"] = \
                            round(gbs, 1)
            except Exception as e:
                print(f"[bench] classical coarse spmv failed: {e}",
                      file=sys.stderr)
                out3["coarse_spmv"] = {"error": str(e)[:200]}
            return out3

        extra_cases["pcg_classical64"] = guarded("pcg_classical64",
                                                 case_cla)

        # classical at the headline scale: fine-level strength+PMIS+D2
        # on device
        # (amg/classical/device_fine.py); coarse levels host
        def case_cla128():
            A5 = poisson7pt(128, 128, 128)
            m5 = amgx.Matrix(A5)
            m5.device_dtype = np.float32
            cla = amgx.AMGConfig(cfg_cla_str)
            return _run_case(A5, lambda: m5, cla, dtype,
                             sync_shape=(7, A5.shape[0]))

        extra_cases["pcg_classical128"] = guarded("pcg_classical128",
                                                  case_cla128)

        # BASELINE config 4 analog: block 4×4 system, BiCGStab + DILU
        def case_blk():
            import scipy.sparse as sp
            A4 = sp.kron(poisson7pt(16, 16, 16), sp.identity(4)).tocsr()
            m4 = amgx.Matrix(A4, block_dim=4)
            m4.device_dtype = np.float32
            blk = amgx.AMGConfig(
                "config_version=2, solver(out)=PBICGSTAB, "
                "out:max_iters=200, out:monitor_residual=1, "
                "out:tolerance=1e-8, out:convergence=RELATIVE_INI, "
                "out:preconditioner(pre)=MULTICOLOR_DILU, pre:max_iters=1")
            return _run_case(A4, lambda: m4, blk, dtype)

        extra_cases["bicgstab_dilu_4x4"] = guarded("bicgstab_dilu_4x4",
                                                   case_blk)

        # BASELINE config 5 (stretch): LOBPCG smallest eigenpairs +
        # PAGERANK on a synthetic scale-free web graph — tracks
        # eigensolver perf round over round
        def case_eig():
            from amgx_tpu.eigen import EigenSolverFactory
            out = {}
            # fused whole-loop LOBPCG: one executable, one host sync
            A6 = poisson7pt(32, 32, 32)
            m6 = amgx.Matrix(A6)
            m6.device_dtype = np.float32
            cfg6 = amgx.AMGConfig(
                "config_version=2, eig_solver(e)=LOBPCG, "
                "e:eig_max_iters=300, e:eig_tolerance=1e-4, "
                "e:eig_wanted_count=2, e:eig_which=smallest")
            es = EigenSolverFactory.allocate(cfg6)
            es.setup(m6)
            res = es.solve()            # warm/compile
            t0 = time.perf_counter()
            res = es.solve()
            out["lobpcg_32cubed_s"] = round(time.perf_counter() - t0, 4)
            out["lobpcg_iterations"] = int(res.iterations)
            out["lobpcg_lambda_min"] = float(
                np.min(np.asarray(res.eigenvalues).real))
            # PageRank: preferential-attachment-ish random digraph
            import scipy.sparse as sp
            rng = np.random.default_rng(11)
            nw = 200_000
            deg = 8
            dst = (rng.pareto(1.2, size=nw * deg) * 10).astype(np.int64)
            dst = dst % nw
            src = np.repeat(np.arange(nw), deg)
            W = sp.csr_matrix((np.ones(len(src)), (src, dst)),
                              shape=(nw, nw))
            mw = amgx.Matrix(sp.csr_matrix(W))
            mw.device_dtype = np.float32
            cfg7 = amgx.AMGConfig(
                "config_version=2, eig_solver(e)=PAGERANK, "
                "e:eig_max_iters=200, e:eig_tolerance=1e-7")
            ep = EigenSolverFactory.allocate(cfg7)
            ep.setup(mw)
            res2 = ep.solve()
            t0 = time.perf_counter()
            res2 = ep.solve()
            out["pagerank_200k_s"] = round(time.perf_counter() - t0, 4)
            out["pagerank_iterations"] = int(res2.iterations)
            return out

        extra_cases["eigen"] = guarded("eigen", case_eig)

        # classical device resetup (value-only refresh runs the whole
        # Galerkin chain on device, no host SpGEMM): timed
        # WARM — the plan indices live on device after the first refresh
        def case_resetup():
            A7 = poisson7pt(48, 48, 48)
            m7 = amgx.Matrix(A7)
            m7.device_dtype = np.float32
            cfg7 = amgx.AMGConfig(
                cfg_cla_str + ", amg:structure_reuse_levels=-1")
            slv7 = amgx.create_solver(cfg7)
            slv7.setup(m7)
            A7b = A7 * 2.0
            m7b = amgx.Matrix(A7b)
            m7b.device_dtype = np.float32
            slv7.resetup(m7b)          # first refresh ships the plans
            A7c = A7 * 3.0
            m7c = amgx.Matrix(A7c)
            m7c.device_dtype = np.float32
            t0 = time.perf_counter()
            slv7.resetup(m7c)
            t_re = time.perf_counter() - t0
            res = slv7.solve(jnp.ones(A7.shape[0], dtype))
            x7 = np.asarray(res.x, np.float64)
            b7 = np.ones(A7.shape[0])
            rr = float(np.linalg.norm(b7 - A7c @ x7) /
                       np.linalg.norm(b7))
            return {"resetup_warm_s": round(t_re, 4),
                    "iterations": int(res.iterations), "relres": rr,
                    "n": int(A7.shape[0])}

        extra_cases["classical_device_resetup48"] = guarded(
            "classical_device_resetup48", case_resetup)

        # real-matrix gauntlet (ISSUE 15): block b=2-5
        # elasticity/CFD/anisotropic/jump cases, each solved via its
        # matched config through the MatrixMarket round trip — per-case
        # iterations + achieved GB/s tracked by perf_gate
        if os.environ.get("AMGX_BENCH_GAUNTLET", "1") != "0":
            g = guarded("gauntlet", lambda: _bench_gauntlet(dtype))
            if isinstance(g, dict) and "error" not in g:
                extra_cases.update(g)
            else:
                extra_cases["gauntlet"] = g

        # block-native vs scalar-expansion SpMV A/B (ISSUE 15
        # acceptance: b=4 ≥ 1.5× effective GB/s; perf_gate pins the
        # floor as a "scaling"-kind contract)
        extra_cases["block_kernels"] = guarded(
            "block_kernels", lambda: _bench_block_kernels(dtype))

        # bf16-hierarchy headline case at 128³ (ISSUE 10 acceptance):
        # the perf-gate case — solve/setup/iterations like every other
        # case plus the effective-speedup FLOOR metric
        if case_bf16 is not None and isinstance(mixed, dict) \
                and "error" not in mixed:
            extra_cases["poisson128_bf16"] = {
                "setup_s": case_bf16["setup_s"],
                "solve_s": case_bf16["solve_s"],
                "iterations": case_bf16["iterations"],
                "relres": case_bf16["relres"],
                "pack": case_bf16.get("pack"),
                "bf16_effective_speedup": mixed.get(
                    "effective_speedup"),
                "achieved_gbs": mixed["bf16"].get("achieved_gbs"),
            }

    # serving mode (amgx_tpu/serve/): request-level latency percentiles
    # + cache/batch stats, mirroring the PR 3 telemetry embedding — a
    # transient failure must not take down the headline JSON line
    try:
        serving = _bench_serving()
    except Exception as e:
        import traceback
        print(f"[bench] serving benchmark failed: {e}", file=sys.stderr)
        traceback.print_exc()
        serving = {"error": str(e)[:200]}

    # chaos block (ISSUE 13, AMGX_BENCH_CHAOS=1): one NaN-poison fault
    # into the headline stack with the recovery ladder armed —
    # recovered-solve overhead vs clean solve (bench_trend's `recov`
    # column); a failure here must not take down the headline JSON line
    chaos = None
    if os.environ.get("AMGX_BENCH_CHAOS") == "1":
        try:
            chaos = _bench_chaos(
                lambda: poisson7pt_device(n_side, n_side, n_side,
                                          device_dtype=dtype),
                cfg_str, dtype)
        except Exception as e:
            import traceback
            print(f"[bench] chaos benchmark failed: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            chaos = {"error": str(e)[:200]}

    # pod-scale distributed weak-scaling block (ISSUE 12): 1/2/4/8-part
    # classical solves at fixed rows/device on a forced 8-device CPU
    # mesh, with agglomeration + shard-local device Galerkin active —
    # the weak_eff_8 floor is perf-gate-enforced
    distributed = None
    if os.environ.get("AMGX_BENCH_DISTRIBUTED", "1") != "0":
        try:
            distributed = _bench_distributed()
        except Exception as e:
            import traceback
            print(f"[bench] distributed benchmark failed: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            distributed = {"error": str(e)[:200]}

    # device-time anatomy (ISSUE 17): one profiler-traced warm headline
    # solve, attributed to the amgx/* scope contract.  Best-effort —
    # perf_gate checks the block's SHAPE only and never ratchets it,
    # bench_trend prints the top-2 scopes — and honest on CPU, where
    # the trace carries no named-scope metadata (measured=false stub).
    # AMGX_BENCH_DEVICEPROF=0 skips the extra profiled solve.
    device_anatomy = None
    if os.environ.get("AMGX_BENCH_DEVICEPROF", "1") != "0" and hold_f32:
        try:
            device_anatomy = _bench_device_anatomy(hold_f32[0], n, dtype)
        except Exception as e:
            import traceback
            print(f"[bench] device-anatomy capture failed: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            device_anatomy = {"error": str(e)[:200]}

    # HBM-ledger snapshot (ISSUE 18): peak HBM + top owners for the
    # kept headline solver.  Best-effort and shape-only for perf_gate;
    # bench_trend prints the peakHBM column.  AMGX_BENCH_MEMLEDGER=0
    # skips.
    memory = None
    if os.environ.get("AMGX_BENCH_MEMLEDGER", "1") != "0" and hold_f32:
        try:
            memory = _bench_memory(hold_f32[0])
        except Exception as e:
            import traceback
            print(f"[bench] memory-ledger snapshot failed: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            memory = {"error": str(e)[:200]}

    metric_name = f"poisson{n_side}_fgmres_agg_amg_solve_s"
    if not on_tpu:
        metric_name = "cpu_" + metric_name
    out = {
        "metric": metric_name,
        "value": case["solve_s"],
        "unit": "s",
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "extras": {
            "backend": backend,
            "n": n,
            "nnz": int(nnz),
            "iterations": case["iterations"],
            "relres": case["relres"],
            "status": case["status"],
            "setup_s": case["setup_s"],
            "upload_s": case["upload_s"],
            "spmv_gflops": round(spmv_gflops, 3),
            "spmv_gbs": round(spmv_gbs, 1),
            # None (not measured) off the chip
            "spmv_frac_hbm_roofline": (
                round(spmv_gbs / peak.hbm_gbs, 3) if peak else None),
            "hbm_roofline_gbs": peak.hbm_gbs if peak else None,
            "spmv_s": round(spmv_t, 8),
            "spmv_gflops_by_format": fmt_stats,
            "matrix_fmt": Ad.fmt,
            "headline_pack": case.get("pack"),
            "telemetry": case.get("telemetry"),
            "serving": serving,
            **({"warm_start": warm_start} if warm_start else {}),
            **({"mixed_precision": mixed} if mixed else {}),
            **({"chaos": chaos} if chaos else {}),
            "device_dtype": str(dtype),
            **({"poisson256": big} if big else {}),
            **({"distributed": distributed} if distributed else {}),
            **({"device_anatomy": device_anatomy}
               if device_anatomy else {}),
            **({"memory": memory} if memory else {}),
            **extra_cases,
        },
        # the backend init needed its one-retry backoff this round —
        # usable, but the worker was flaky (bench_trend annotates it)
        **({"retried": True} if retried else {}),
    }
    failed = _errors_in(out)
    if failed:
        out["failed_blocks"] = failed
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    try:
        if len(sys.argv) > 1 and sys.argv[1] == "--warm-start-child":
            sys.exit(_warm_start_child())
        if len(sys.argv) > 1 and sys.argv[1] == "--distributed-child":
            sys.exit(_distributed_child())
        sys.exit(main())
    except Exception as e:
        # device loss mid-run still gets the structured diagnostic; a
        # genuine bench bug stays loud
        if _is_device_init_error(e):
            sys.exit(_emit_error_json("device_unavailable", e))
        raise
