"""Fault-injection harness chaos tests (utils/faultinject.py).

The acceptance contract (ISSUE 13): every injection point fires where
it is wired, every :class:`~amgx_tpu.errors.FailureKind` is reachable
and correctly classified (or fails cleanly with the correct RC), a
NaN-poisoned PCG solve terminates within a few iterations of the
injection instead of burning ``max_iters`` — and with the knobs off
the solve path is bit-identical with zero extra retraces.
"""
import numpy as np
import pytest
import scipy.sparse as sp

import amgx_tpu as amgx
from amgx_tpu import telemetry
from amgx_tpu.errors import RC, AMGXError, FailureKind, SolveStatus
from amgx_tpu.io import poisson5pt, poisson7pt
from amgx_tpu.solvers import SolverFactory
from amgx_tpu.utils import faultinject
from amgx_tpu.utils.jaxcompat import trace_stats
from amgx_tpu.utils.thread_manager import ThreadManager

pytestmark = pytest.mark.chaos

PCG_CFG = (
    "config_version=2, solver(s)=PCG, s:preconditioner(p)=BLOCK_JACOBI, "
    "p:max_iters=3, s:max_iters=200, s:monitor_residual=1, "
    "s:tolerance=1e-8, s:convergence=RELATIVE_INI, "
    "s:store_res_history=1")


@pytest.fixture(autouse=True)
def _disarm():
    """Every chaos test leaves the process-global plan disarmed."""
    faultinject.reset()
    yield
    faultinject.reset()


def _pcg(extra=""):
    s = SolverFactory.create("PCG", amgx.AMGConfig(PCG_CFG + extra), "s")
    A = sp.csr_matrix(poisson5pt(16, 16))
    s.setup(amgx.Matrix(A))
    return s, A


# ---------------------------------------------------------------------------
# spec parsing + trigger semantics
# ---------------------------------------------------------------------------
def test_spec_parsing_and_triggers():
    faultinject.configure("values_nan:iter=3:count=2, worker_death")
    assert faultinject.armed("values_nan")
    assert faultinject.param("values_nan", "iter") == 3
    assert faultinject.trace_mode() == ("values_nan", 3)
    assert faultinject.should_fire("values_nan")
    assert faultinject.should_fire("values_nan")
    assert not faultinject.should_fire("values_nan")   # count exhausted
    assert faultinject.trace_mode() is None
    assert faultinject.should_fire("worker_death")     # count-less: always
    assert faultinject.should_fire("worker_death")
    st = faultinject.stats()
    assert st["values_nan"]["fired"] == 2
    assert st["worker_death"]["remaining"] is None


def test_config_string_safe_spec_form():
    """The ``fault_inject`` KNOB must survive the flat config-string
    grammar (one '=' per entry, ',' splits entries): params pair by
    ':' alternation and points separate on whitespace."""
    faultinject.configure("values_nan:iter:3:count:2 worker_death:count:1")
    assert faultinject.trace_mode() == ("values_nan", 3)
    assert faultinject.armed("worker_death")
    faultinject.reset()
    # end to end through AMGConfig — the whole point of the form
    cfg = amgx.AMGConfig(
        "config_version=2, solver(s)=CG, "
        "s:fault_inject=setup_error:count:1")
    s = SolverFactory.create("CG", cfg, "s")
    A = sp.csr_matrix(poisson5pt(8, 8))
    with pytest.raises(AMGXError):
        s.setup(amgx.Matrix(A))
    s.setup(amgx.Matrix(A))               # one-shot: second succeeds


def test_unknown_point_rejected():
    with pytest.raises(ValueError, match="unknown fault-injection"):
        faultinject.configure("definitely_not_a_point:count=1")
    # the config-knob path surfaces the same validation at solver
    # construction (a typo'd chaos spec must fail loud, never arm)
    with pytest.raises(ValueError, match="unknown fault-injection"):
        SolverFactory.create(
            "CG", amgx.AMGConfig(
                "config_version=2, solver(s)=CG, "
                "s:fault_inject=bogus_point"), "s")


def test_disarmed_is_inert():
    assert not faultinject.active()
    assert not faultinject.should_fire("setup_error")
    faultinject.maybe_raise("setup_error")   # no-op, must not raise
    assert faultinject.stats() == {}


def test_probability_trigger_deterministic_seed():
    faultinject.configure("upload_error:prob=1.0:seed=7:count=3")
    assert faultinject.should_fire("upload_error")
    faultinject.configure("upload_error:prob=0.0:seed=7")
    assert not faultinject.should_fire("upload_error")


# ---------------------------------------------------------------------------
# seam points: setup / upload / oom — clean terminal failure, correct RC
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("point,rc", [
    ("setup_error", RC.CORE),
    ("upload_error", RC.CUDA_FAILURE),
    ("oom", RC.NO_MEMORY),
])
def test_seam_points_raise_with_correct_rc(point, rc):
    A = sp.csr_matrix(poisson5pt(8, 8))
    s = SolverFactory.create("CG", amgx.AMGConfig(
        "config_version=2, solver(s)=CG, s:max_iters=50, "
        "s:monitor_residual=1, s:tolerance=1e-8, "
        "s:convergence=RELATIVE_INI"), "s")
    faultinject.configure(f"{point}:count=1")
    with pytest.raises(AMGXError) as ei:
        s.setup(amgx.Matrix(A))
    assert ei.value.rc == rc
    # count consumed: the next setup succeeds — the fault was one-shot
    s.setup(amgx.Matrix(A))
    assert s.solve(np.ones(A.shape[0])).status == SolveStatus.SUCCESS


# ---------------------------------------------------------------------------
# traced points: NaN poison + krylov zero — detection inside the loop
# ---------------------------------------------------------------------------
def test_nan_poisoned_pcg_stops_early_with_kind():
    """The headline acceptance: a NaN-poisoned PCG terminates within 5
    iterations of the injection instead of running to max_iters, and
    the terminal result carries kind + first-bad iteration."""
    s, A = _pcg()
    b = np.ones(A.shape[0])
    clean = s.solve(b)
    assert clean.status == SolveStatus.SUCCESS
    assert clean.iterations > 8          # the guard has room to matter
    inject_at = 2
    telemetry.enable(8192)
    try:
        telemetry.reset()
        faultinject.configure(f"values_nan:iter={inject_at}:count=1")
        res = s.solve(b)
        reg = telemetry.registry()
        fired = reg.get_counter("amgx_fault_injected_total",
                                point="values_nan")
        ev = [r for r in telemetry.records()
              if r["kind"] == "event" and r["name"] == "fault_injected"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert res.status in (SolveStatus.DIVERGED, SolveStatus.FAILED)
    assert res.failure is not None
    assert res.failure.kind == FailureKind.NAN_POISON
    assert res.iterations <= inject_at + 5     # NOT max_iters
    assert res.failure.iteration is not None
    assert res.failure.iteration <= inject_at + 5
    assert fired == 1 and len(ev) == 1
    # count exhausted: the very next solve retraces clean and converges
    again = s.solve(b)
    assert again.status == SolveStatus.SUCCESS
    assert again.iterations == clean.iterations


def test_krylov_zero_flags_krylov_breakdown():
    s, A = _pcg()
    b = np.ones(A.shape[0])
    faultinject.configure("krylov_zero:iter=3:count=1")
    res = s.solve(b)
    assert res.status == SolveStatus.FAILED
    assert res.failure is not None
    assert res.failure.kind == FailureKind.KRYLOV_BREAKDOWN
    assert res.iterations <= 3 + 5
    assert s.solve(b).status == SolveStatus.SUCCESS


# ---------------------------------------------------------------------------
# naturally reachable kinds: indefinite operator, divergence, stagnation
# ---------------------------------------------------------------------------
def test_indefinite_operator_detected():
    """CG on a genuinely indefinite operator flags INDEFINITE within
    the loop (pAp < 0) instead of silently wandering to max_iters."""
    n = 32
    d = np.ones(n)
    d[8:] = -1.0          # diag(+1 ×8, -1 ×24): pAp = 8-24 < 0
    A = sp.diags(d).tocsr()
    s = SolverFactory.create("CG", amgx.AMGConfig(
        "config_version=2, solver(s)=CG, s:max_iters=100, "
        "s:monitor_residual=1, s:tolerance=1e-10, "
        "s:convergence=RELATIVE_INI"), "s")
    s.setup(amgx.Matrix(A))
    res = s.solve(np.ones(n))
    assert res.status in (SolveStatus.FAILED, SolveStatus.DIVERGED)
    assert res.failure is not None
    assert res.failure.kind in (FailureKind.INDEFINITE_OPERATOR,
                                FailureKind.NAN_POISON)
    assert res.iterations < 100


def test_divergence_detected_as_divergence_not_nan():
    """A residual that overflows to inf (no NaN) classifies as
    DIVERGENCE — the inf-vs-NaN split the taxonomy promises."""
    A = sp.csr_matrix(np.array([[1.0, 3.0], [3.0, 1.0]]))
    s = SolverFactory.create("BLOCK_JACOBI", amgx.AMGConfig(
        "config_version=2, solver(s)=BLOCK_JACOBI, s:max_iters=900, "
        "s:relaxation_factor=1.0, s:monitor_residual=1, "
        "s:tolerance=1e-12, s:convergence=RELATIVE_INI"), "s")
    s.setup(amgx.Matrix(A))
    res = s.solve(np.ones(2))
    assert res.failure is not None
    assert res.failure.kind == FailureKind.DIVERGENCE
    assert res.iterations < 900           # stopped at the overflow


def test_stagnation_kind_on_budget_exhaustion():
    s, A = _pcg(", s:max_iters=3")
    res = s.solve(np.ones(A.shape[0]))
    assert res.status == SolveStatus.NOT_CONVERGED
    assert res.failure is not None
    assert res.failure.kind == FailureKind.STAGNATION


# ---------------------------------------------------------------------------
# knobs-off parity: bit-identical solve, zero extra retraces
# ---------------------------------------------------------------------------
def test_knobs_off_bit_identical_and_zero_retraces():
    s, A = _pcg()
    b = np.ones(A.shape[0])
    x_ref = np.asarray(s.solve(b).x)
    telemetry.enable(4096)
    try:
        telemetry.reset()
        before = trace_stats()["traces"]
        res = s.solve(b)
        after = trace_stats()["traces"]
    finally:
        telemetry.disable()
        telemetry.reset()
    # zero extra retraces with the knobs off (monitoring-counter
    # asserted), and a bitwise-identical solution
    assert after - before == 0
    np.testing.assert_array_equal(np.asarray(res.x), x_ref)
    # arm → fire → disarm returns to the SAME bits as never-armed
    faultinject.configure("values_nan:iter=2:count=1")
    s.solve(b)
    faultinject.reset()
    np.testing.assert_array_equal(np.asarray(s.solve(b).x), x_ref)


# ---------------------------------------------------------------------------
# worker death (utils/thread_manager.py) — satellite: respawn coverage
# ---------------------------------------------------------------------------
def test_worker_death_pool_survives_and_counts():
    tm = ThreadManager(max_workers=2)
    tm.spawn_threads()
    faultinject.configure("worker_death:count=1")
    ran = []
    tm.push_work(lambda: ran.append("a"))     # dies (injected)
    tm.push_work(lambda: ran.append("b"))     # must still run
    with pytest.raises(faultinject.WorkerDeathError):
        tm.wait_threads()
    assert tm.failed_tasks == 1
    assert "b" in ran
    tm.push_work(lambda: ran.append("c"))     # pool alive after death
    tm.wait_threads()
    assert "c" in ran
    tm.join_threads()


def test_worker_pool_respawns_after_out_of_band_shutdown():
    tm = ThreadManager(max_workers=2)
    tm.spawn_threads()
    tm._pool.shutdown(wait=True)              # simulate a dead pool
    ran = []
    tm.push_work(lambda: ran.append("x"))     # must respawn, not raise
    tm.wait_threads()
    assert ran == ["x"]
    assert tm.respawns == 1
    tm.join_threads()


def test_serve_worker_death_fails_inflight_cleanly(rng):
    """A worker dying mid-batch: the in-flight request completes with a
    terminal error outcome (not a hang), the failure counter
    increments, and the service keeps serving."""
    from amgx_tpu.serve import SolveService
    A = sp.csr_matrix(poisson5pt(8, 8))
    m = amgx.Matrix(A)
    cfg = amgx.AMGConfig(
        PCG_CFG + ", serve_batch_window_ms=5, serve_workers=2")
    telemetry.enable(4096)
    try:
        telemetry.reset()
        with SolveService(cfg) as svc:
            faultinject.configure("worker_death:count=1")
            p = svc.submit(m, np.ones(A.shape[0]))
            assert p.wait_done(60)
            assert p.rc != RC.OK and p.result is None
            assert p.error and "worker death" in p.error
            faultinject.reset()
            res = svc.solve(m, np.ones(A.shape[0]), timeout=120)
            assert res.status == SolveStatus.SUCCESS
            st = svc.stats()
        assert st["worker_task_failures"] == 1
        reg = telemetry.registry()
        assert reg.get_counter("amgx_worker_task_failures_total") == 1
    finally:
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# AOT-store corruption
# ---------------------------------------------------------------------------
def test_aot_corrupt_falls_back_and_recompiles(tmp_path):
    from amgx_tpu.serve import aot
    store_dir = str(tmp_path / "aot")
    cfg = amgx.AMGConfig(
        "config_version=2, solver(s)=CG, s:max_iters=50, "
        "s:monitor_residual=1, s:tolerance=1e-8, "
        f"s:convergence=RELATIVE_INI, s:aot_store_dir={store_dir}")
    A = sp.csr_matrix(poisson5pt(8, 8))
    b = np.ones(A.shape[0])
    try:
        s1 = SolverFactory.create("CG", cfg, "s")
        s1.setup(amgx.Matrix(A))
        x_ref = np.asarray(s1.solve(b).x)
        assert aot.get_store() is not None
        saved = aot.get_store().stats()["saves"]
        assert saved >= 1                 # the solve body persisted
        # fresh store object (cold in-memory cache) + injected
        # corruption: the load falls back, the solve still works, the
        # healthy on-disk entry survives
        aot.reset_store()
        aot.configure(store_dir)
        faultinject.configure("aot_corrupt:count=1")
        s2 = SolverFactory.create("CG", cfg, "s")
        s2.setup(amgx.Matrix(A))
        res = s2.solve(b)
        assert res.status == SolveStatus.SUCCESS
        np.testing.assert_allclose(np.asarray(res.x), x_ref,
                                   rtol=1e-12)
        st = aot.get_store().stats()
        assert st["fallbacks"] >= 1
        assert st["entries"] >= 1             # nothing was deleted
    finally:
        faultinject.reset()
        aot.reset_store()


# ---------------------------------------------------------------------------
# distributed: halo-exchange failure on the 8-device CPU mesh
# ---------------------------------------------------------------------------
def test_distributed_halo_exchange_failure_and_retry():
    import jax
    mesh = jax.make_mesh((8,), ("p",))
    A = poisson7pt(8, 8, 8)
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    s = SolverFactory.create("PCG", amgx.AMGConfig(PCG_CFG), "s")
    s.setup(m)
    b = np.ones(A.shape[0])
    faultinject.configure("halo_exchange:count=1")
    with pytest.raises(AMGXError) as ei:
        s.solve(b)
    assert ei.value.rc == RC.CUDA_FAILURE     # device_error RC
    # one-shot fault: the retried solve completes on the mesh
    res = s.solve(b)
    assert res.status == SolveStatus.SUCCESS
    relres = np.linalg.norm(b - A @ np.asarray(res.x)) \
        / np.linalg.norm(b)
    assert relres < 1e-7


# ---------------------------------------------------------------------------
# deadline kind (serving): the shed path is the taxonomy's deadline
# ---------------------------------------------------------------------------
def test_deadline_outcome_expired(rng):
    from amgx_tpu.serve import SolveService
    A = sp.csr_matrix(poisson5pt(8, 8))
    cfg = amgx.AMGConfig(
        PCG_CFG + ", serve_batch_window_ms=40, serve_workers=1")
    with SolveService(cfg) as svc:
        p = svc.submit(amgx.Matrix(A), np.ones(A.shape[0]),
                       deadline_s=1e-4)
        assert p.wait_done(60)
        assert p.rc == RC.REJECTED
        assert "deadline" in (p.error or "")
