"""The program's instrumentation as a profiler capture reads it:
device operations joined to their ``jax.named_scope`` paths in an
``.xplane.pb`` (telemetry/proftrace.py), the anatomy built on them
(telemetry/deviceprof.py), the ``amgx/<marker>`` host spans
(utils/profiler.py), the per-function trace counts
(utils/jaxcompat.py), and the scopes the solve's HLO carries."""
import re
from pathlib import Path

import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import telemetry
from amgx_tpu.telemetry import deviceprof, proftrace, scopes
from amgx_tpu.utils import jaxcompat, profiler

pytestmark = pytest.mark.deviceprof

#: one FGMRES + aggregation AMG solve of the 32^3 7-point Poisson,
#: recorded on a TPU v5e (the benchmark's trace-reader fixture)
FIXTURE = (Path(__file__).resolve().parents[1] / "benchmark" / "tests"
           / "data" / "fixture.xplane.pb")

CFG = ("config_version=2, solver(out)=FGMRES, out:max_iters=60, "
       "out:monitor_residual=1, out:tolerance=1e-10, "
       "out:convergence=RELATIVE_INI, out:gmres_n_restart=10, "
       "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
       "amg:selector=SIZE_2, amg:max_iters=1, amg:smoother(sm)=BLOCK_JACOBI, "
       "sm:max_iters=1, amg:min_coarse_rows=16, "
       "amg:coarse_solver=DENSE_LU_SOLVER")


#: HLO opcodes that move or name values and compute nothing
PLUMBING = {"parameter", "get-tuple-element", "tuple", "constant",
            "bitcast", "copy"}


@pytest.fixture(scope="module")
def recorded():
    return proftrace.read_xspace(str(FIXTURE))


# ------------------------------------------------------ the XSpace reader
def test_reader_joins_every_recorded_op_to_its_metadata():
    from jax.profiler import ProfileData
    data = FIXTURE.read_bytes()
    meta = proftrace.xspace_metadata(data)
    assert list(meta) == ["/device:TPU:0"]
    names = [ev.name for p in ProfileData.from_serialized_xspace(data).planes
             if p.name == "/device:TPU:0" for line in p.lines
             if line.name == proftrace.OPS_LINE for ev in line.events]
    assert len(names) == 16974
    dev = meta["/device:TPU:0"]
    assert all(n in dev for n in names)
    scoped = [n for n in names if "amgx/" in dev[n].get("tf_op", "")]
    assert len(scoped) == 14882
    # the Python source of an op, scoped or not
    assert any(v.get("source", "").endswith("amgx_tpu/solvers/base.py:1921")
               for v in dev.values())


def test_leaf_ops_scoped_share(recorded):
    ops, spans = recorded
    assert list(ops) == [0]
    leaf = [o for o in ops[0] if not o.container]
    assert any(o.container for o in ops[0])       # while bodies nest
    busy = sum(o.end - o.start for o in leaf)
    scoped = sum(o.end - o.start for o in leaf if "amgx/" in o.scope)
    assert 0.73 <= scoped / busy <= 0.74
    assert spans == []          # recorded before markers reached traces


def test_find_xplane_file_rejects_other_files(tmp_path):
    other = tmp_path / "t.trace.json"
    other.write_text("{}")
    assert proftrace.find_xplane_file(str(other)) is None
    assert proftrace.find_xplane_file(str(tmp_path / "missing")) is None
    with pytest.raises(FileNotFoundError):
        proftrace.read_xspace(str(tmp_path))


# -------------------------------------------------- anatomy of the chip run
def test_anatomy_of_recorded_tpu_trace(recorded):
    a = deviceprof.measure_anatomy(str(FIXTURE))
    assert a["measured"] is True
    assert a["n_devices"] == 1
    assert a["attributed_s"] + a["unattributed_s"] == pytest.approx(
        a["total_device_s"], abs=2e-9)
    ops, _ = recorded
    busy = proftrace.union_len([(o.start, o.end) for o in ops[0]
                                if not o.container])
    assert a["total_device_s"] == pytest.approx(busy, abs=1e-9)
    assert a["attributed_s"] / a["total_device_s"] == pytest.approx(
        0.7345, abs=5e-4)
    assert set(a["levels"]) == {"0", "1", "2", "3"}
    assert a["coarse_s"] > 0
    shares = a["cycle_shares"]
    assert 0 < shares["smooth"] < shares["coarse"] < 1
    # executions of each pack are counted in the trace
    assert all(e["executions"] > 0 for e in a["spmv"].values())
    # the largest unscoped ops, by their Python source
    assert a["unscoped_top"][0][0].endswith("solvers/base.py:1921")
    telemetry.validate_record(
        {"kind": "event", "name": "device_anatomy", "seq": 1, "t": 0.0,
         "tid": 0, "sid": None, "attrs": a})


def test_cycle_shares_by_innermost_cycle_scope(chrome_trace):
    """Synthetic ground truth (tests/conftest.py): 430 µs of ops; level
    1 and the coarse solve 90 µs, the smoothing scopes 210 µs."""
    a = deviceprof.measure_anatomy(chrome_trace)
    assert a["cycle_shares"]["coarse"] == pytest.approx(90 / 430, abs=1e-6)
    assert a["cycle_shares"]["smooth"] == pytest.approx(210 / 430,
                                                        abs=1e-6)
    assert a["unscoped_top"] == [["copy.9", pytest.approx(40e-6)]]
    stub = deviceprof.measure_anatomy({"traceEvents": []})
    assert stub["cycle_shares"] == {"coarse": None, "smooth": None}


def test_idle_gaps_labelled_by_program_span():
    S = deviceprof._Slice
    ops = [S(0, 0, 10, "a", "", [], ""), S(0, 15, 20, "b", "", [], ""),
           S(0, 50, 60, "c", "", [], ""), S(0, 70, 80, "d", "", [], "")]
    spans = [("amgx/solve:FGMRES", 0, 100), ("amgx/setup_smoothers", 30, 55)]
    got = deviceprof._idle_by_span(ops, spans)
    assert got == {"solve:FGMRES": 15, "setup_smoothers": 30}
    assert deviceprof._idle_by_span(ops, []) == {"none": 45}


# ------------------------------------------------- program host spans
def test_markers_enter_trace_annotations(monkeypatch):
    import jax
    entered = []

    class Ann:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    with profiler.cpu_profiler("outer"):
        with profiler.cpu_profiler("inner"):
            pass
    assert entered == ["amgx/outer", "amgx/inner"]
    assert not hasattr(profiler, "enable_jax_trace_annotations")


def test_cpu_profiled_solve_records_program_host_spans(tmp_path):
    import jax
    from amgx_tpu.io import poisson7pt
    A = poisson7pt(6, 6, 6)
    slv = amgx.create_solver(amgx.AMGConfig(CFG))
    slv.setup(amgx.Matrix(A))
    b = np.ones(A.shape[0])
    np.asarray(slv.solve(b).x)
    with jax.profiler.trace(str(tmp_path)):
        np.asarray(slv.solve(b).x)
    ops, spans = proftrace.read_xspace(str(tmp_path))
    names = {s.name for s in spans}
    assert "amgx/solve:FGMRES" in names
    assert all(s.end >= s.start for s in spans)
    # the CPU has no device plane: the anatomy is an honest stub
    assert ops == {}
    assert deviceprof.measure_anatomy(str(tmp_path))["measured"] is False


# ------------------------------------------------ retraces by function
def test_trace_stats_count_by_function():
    import jax
    import jax.numpy as jnp

    def traced_twice_by_shape(x):
        return jnp.sin(x) * 2

    f = jax.jit(traced_twice_by_shape)
    before = jaxcompat.trace_stats()
    f(jnp.ones(3))
    f(jnp.ones(3))                      # cached: no trace
    f(jnp.ones(5))
    after = jaxcompat.trace_stats()
    got = after["by_fun"]["traced_twice_by_shape"]
    assert got["traces"] == 2
    assert got["trace_s"] > 0
    assert after["traces"] - before["traces"] >= 2
    assert after["trace_s"] > before["trace_s"]


def test_jit_trace_counter_labels_the_function():
    import jax
    import jax.numpy as jnp

    def labelled_trace_probe(x):
        return x + 1

    with telemetry.capture() as cap:
        jax.jit(labelled_trace_probe)(jnp.ones(2))
    by_fun = cap.counter_totals("amgx_jit_trace_total", label="fun")
    assert by_fun, "no amgx_jit_trace_total sample"
    assert set(by_fun) <= set(jaxcompat.trace_stats()["by_fun"]) | {"other"}
    assert sum(by_fun.values()) >= 1


# --------------------------------------------- scopes of the solve's HLO
def test_refine_stage_in_contract():
    assert scopes.SCOPE_VERSION == 2
    assert "refine" in scopes.KRYLOV_STAGES
    assert scopes.canonicalize("amgx/krylov/refine/while/body/dot") == \
        "amgx/krylov/refine"


def test_refined_solve_leaves_no_vector_op_unscoped():
    """Every instruction of the compiled refined FGMRES + AMG solve that
    computes a vector of the operator's length or more carries an
    ``amgx/`` scope; what stays unscoped is scalar loop control, the
    arguments and the loop state's plumbing."""
    import jax.numpy as jnp
    from amgx_tpu.io import poisson7pt
    A = poisson7pt(8, 8, 8)
    n = A.shape[0]
    m = amgx.Matrix(A)
    m.device_dtype = np.float32
    slv = amgx.create_solver(amgx.AMGConfig(CFG))
    slv.setup(m)
    res = slv.solve(np.ones(n), zero_initial_guess=True)
    assert int(res.iterations) > 0
    assert slv._refined_fn is not None, "the f64 refinement did not run"
    b_hi = jnp.ones(n, jnp.float32)
    compiled = jaxcompat.compile_uncached(
        slv._refined_fn[1],
        (slv._bindings.collect(), b_hi, None, None, None,
         jnp.asarray(1e-10, jnp.float64), jnp.asarray(60, jnp.int32)))
    unscoped, fused = [], False
    for line in compiled.as_text().splitlines():
        if line and not line.startswith(" "):
            fused = "fused" in line.split("(")[0]
            continue
        mm = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                      r"([\w-]+)\(", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if fused or not mm or not op or mm.group(2) in PLUMBING:
            continue
        elems = int(np.prod([int(d) for d in mm.group(1).split(",") if d]))
        if elems >= n and "amgx/" not in op.group(1):
            unscoped.append(op.group(1))
    assert unscoped == []
