"""Solver framework.

TPU-native re-design of the reference solver contract
(``base/include/solvers/solver.h:44-325``, ``base/src/solvers/solver.cu``):

* :class:`Solver` — base class owning A, convergence criterion, norms and the
  generic ``setup()`` / ``solve()`` drivers (reference ``solver.cu:380-970``).
* :class:`SolverFactory` — named registry; nested solvers are allocated from
  a config scope (reference ``solver.h:287-325`` + ``core.cu:612-641``).
* Convergence criteria: ABSOLUTE / RELATIVE_INI(_CORE) / RELATIVE_MAX(_CORE) /
  COMBINED_REL_INI_ABS (``core/src/convergence/``).

Execution model (the TPU-first redesign): ``setup()`` runs on host (irregular
graph work → frozen device arrays); the whole ``solve()`` loop is traced once
and executed as a single XLA computation via ``lax.while_loop`` over a state
pytree.  Preconditioner/smoother application is traced inline into the outer
iteration (the reference achieves composition via virtual calls at run time;
here composition happens at trace time, letting XLA fuse across the stack).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..config import AMGConfig
from ..core.matrix import DeviceMatrix, Matrix
from ..errors import (AMGXError, BadConfigurationError,
                      BadParametersError, FailureInfo, FailureKind,
                      RC, SolveStatus, breakdown_kind,
                      BREAKDOWN_KRYLOV, BREAKDOWN_NAN,
                      BREAKDOWN_DIVERGENCE)
from ..utils import faultinject
from ..ops import blas
from ..ops.spmv import spmv
from ..utils.logging import amgx_output
from ..utils.profiler import cpu_profiler


def _refine_scope():
    """The ``amgx/krylov/refine`` named scope: a solve's true residuals
    and the f64 refinement's corrections, outside the Krylov stages."""
    return telemetry.scopes.scope("krylov", "refine")


# --------------------------------------------------------------------------
# Convergence criteria (core/src/convergence/)
# --------------------------------------------------------------------------

def check_convergence(criterion: str, nrm, nrm_ini, nrm_max, tolerance,
                      alt_rel_tolerance):
    """Return a boolean scalar: has the solve converged?

    All comparisons are per block-component and must hold for every
    component (reference block norms).
    """
    if criterion in ("ABSOLUTE",):
        ok = nrm <= tolerance
    elif criterion in ("RELATIVE_INI", "RELATIVE_INI_CORE"):
        ok = nrm <= tolerance * nrm_ini
    elif criterion in ("RELATIVE_MAX", "RELATIVE_MAX_CORE"):
        ok = nrm <= tolerance * nrm_max
    elif criterion == "COMBINED_REL_INI_ABS":
        ok = (nrm <= tolerance) | (nrm <= alt_rel_tolerance * nrm_ini)
    else:
        raise BadConfigurationError(f"unknown convergence {criterion!r}")
    return jnp.all(ok)


def _inject_fault(fault, it, x, state):
    """Apply a traced fault-injection point to the iteration state at
    its target iteration (``fault = (mode, iteration)`` — see
    ``utils.faultinject.TRACED_POINTS``).  Only ever traced when a
    point is armed; the clean path never calls this."""
    mode, f_it = fault
    tgt = jnp.asarray(int(f_it), jnp.int32)
    if mode == "values_nan":
        def poison(v):
            if not jnp.issubdtype(v.dtype, jnp.inexact):
                return v
            bad = jnp.asarray(float("nan"), v.dtype)
            return jnp.where(it == tgt, v * bad, v)
        return poison(x), jax.tree_util.tree_map(poison, state)
    # krylov_zero: collapse the 0-dim Krylov scalars (CG's rho) while
    # the residual vectors stay healthy — the classic rho-breakdown
    def zero_scalar(v):
        if not jnp.issubdtype(v.dtype, jnp.inexact) or v.ndim != 0:
            return v
        return jnp.where(it == tgt, jnp.zeros_like(v), v)
    return x, jax.tree_util.tree_map(zero_scalar, state)


@dataclasses.dataclass
class SolveResult:
    x: jax.Array
    iterations: int
    status: SolveStatus
    residual_norm: Optional[np.ndarray]
    residual_history: Optional[np.ndarray]
    setup_time: float = 0.0
    solve_time: float = 0.0
    #: what went wrong (errors.FailureInfo: taxonomy kind + the first
    #: iteration the in-loop guards observed it at); None on SUCCESS
    failure: Optional[FailureInfo] = None
    #: recovery-ladder audit (solvers/recovery.py) when the solve was
    #: retried: {"kind", "action", "attempts", "outcome"}; None when no
    #: recovery ran
    recovery: Optional[dict] = None


# --------------------------------------------------------------------------
# Factory registry (reference SolverFactory, solver.h:287-325)
# --------------------------------------------------------------------------
_solver_registry: Dict[str, Type["Solver"]] = {}


def register_solver(name: str):
    def deco(cls):
        _solver_registry[name] = cls
        cls.config_name = name
        return cls
    return deco


class SolverFactory:
    @staticmethod
    def allocate(cfg: AMGConfig, scope: str, param_name: str) -> "Solver":
        """Allocate the solver named by config param ``param_name`` in
        ``scope``; the solver reads its own params from its new scope.

        Reference: ``SolverFactory::allocate(cfg, current_scope,
        solver_name)`` pattern used e.g. at ``fgmres_solver.cu:243-253``.
        """
        value, new_scope = cfg.get_scoped(param_name, scope)
        return SolverFactory.create(str(value), cfg, new_scope)

    @staticmethod
    def create(name: str, cfg: Optional[AMGConfig] = None,
               scope: str = "default") -> "Solver":
        if name not in _solver_registry:
            raise BadConfigurationError(f"unknown solver {name!r}; known: "
                                        f"{sorted(_solver_registry)}")
        return _solver_registry[name](cfg or AMGConfig(), scope)

    @staticmethod
    def registered() -> Dict[str, Type["Solver"]]:
        return dict(_solver_registry)


# --------------------------------------------------------------------------
# Solver base
# --------------------------------------------------------------------------
def _window_fits(csr) -> "Optional[bool]":
    """Does a CSR matrix fit the windowed-kernel budget?  True/False, or
    None when it is outside the kernel's row-width envelope entirely
    (K > 160 — no reordering can rescue that)."""
    from ..core.matrix import ell_layout
    from ..ops.pallas_ell import ell_window_pack
    for_rows, pos, k = ell_layout(csr.indptr, csr.indices)
    if k > 160:
        return None
    cols = np.zeros((csr.shape[0], k), dtype=np.int32)
    cols[for_rows, pos] = csr.indices
    return ell_window_pack(cols) is not None


def _binned_fits(csr) -> bool:
    """Does the binned sliced-ELL plan (ops/pallas_csr.py) carry this
    matrix efficiently?  True when the plan's padded-lane factor is
    small enough that the binned kernel's throughput class matches or
    beats the windowed kernel an RCM permute could rescue — AUTO
    reordering then skips the O(nnz log n) RCM pass (and the permuted
    solve boundary) entirely.  The probe re-runs the pack's layout plan
    (one extra O(nnz log nnz) host pass at setup); that is the price of
    the decision, still well under the RCM+repack it avoids."""
    from ..ops.pallas_csr import binned_pad_factor
    pf = binned_pad_factor(csr.indptr, csr.indices, csr.shape[1])
    return pf is not None and pf <= 2.0


class Solver:
    """Base solver: common parameter handling + generic solve driver.

    Subclasses implement host-side :meth:`solver_setup` and the traced
    :meth:`solve_init` / :meth:`solve_iteration`.
    """

    config_name = "?"
    #: True for relaxation methods whose one iteration is one sweep
    is_smoother = False

    def __init__(self, cfg: AMGConfig, scope: str = "default"):
        self.cfg = cfg
        self.scope = scope
        g = lambda name: cfg.get(name, scope)
        self.max_iters = int(g("max_iters"))
        self.tolerance = float(g("tolerance"))
        self.alt_rel_tolerance = float(g("alt_rel_tolerance"))
        self.convergence = str(g("convergence"))
        self.norm_type = str(g("norm"))
        self.monitor_residual = bool(g("monitor_residual"))
        self.use_scalar_norm = bool(g("use_scalar_norm"))
        self.store_res_history = bool(g("store_res_history"))
        self.print_solve_stats = bool(g("print_solve_stats"))
        self.obtain_timings = bool(g("obtain_timings"))
        self.relaxation_factor = float(g("relaxation_factor"))
        # communication-avoiding Krylov (ops/blas.py): the knob picks
        # the reduction layout (CLASSIC / CA / PIPELINED); the ledger
        # counts, at trace time, the reduction ops one iteration body
        # performs — the truth behind amgx_krylov_collectives_total
        self.krylov_comm = str(g("krylov_comm"))
        self.ca_residual_replace = int(g("ca_residual_replace"))
        self._collective_ledger = blas.CollectiveLedger()
        # structured telemetry (amgx_tpu/telemetry/): the knob enables
        # the process-global recorder; keeping the residual history is
        # what makes per-iteration residual records available post-solve
        self.telemetry_path = str(g("telemetry_path"))
        if int(g("telemetry")):
            telemetry.enable(int(g("telemetry_ring_size")))
            self.store_res_history = True
        # convergence forensics (telemetry/forensics.py): cycle-anatomy
        # instrumentation rides the hierarchy (amg/cycles.py reads the
        # same knob); at this layer the knob keeps the residual history
        # so the asymptotic convergence-factor estimate can be computed
        # per solve
        self.forensics = bool(int(g("forensics")))
        if self.forensics:
            self.store_res_history = True
        # setup profiler (telemetry/setup_profile.py): per-phase setup
        # attribution with compile/transfer/memory splits; the knob
        # enables the process-global profiler (which enables the
        # telemetry recorder — phase records live in the same ring)
        if int(g("setup_profile")):
            telemetry.setup_profile.enable()
        # HBM ledger (telemetry/memledger.py): device-memory ownership
        # attribution + OOM post-mortems.  Off by default — with the
        # knob off every registration site is one attribute check and
        # solve traces are byte-identical
        if int(g("memledger")):
            telemetry.memledger.enable(
                sample_s=float(g("memledger_sample_s")))
        # zero cold-start (utils/jaxcompat.py + serve/aot.py): the
        # persistent compile cache is placed at import time;
        # aot_store_dir additionally serializes the hot executables so
        # a fresh process skips tracing too
        aot_dir = str(g("aot_store_dir"))
        if aot_dir:
            from ..serve import aot as _aot
            _aot.configure(aot_dir)
            # cumulative cache-efficacy counters survive restarts in a
            # state file next to the warm-start artifacts
            telemetry.runstate.configure_default(aot_dir)
        # breakdown-aware solving (solvers/recovery.py +
        # utils/faultinject.py): the recovery ladder is opt-in
        # (recovery_policy=AUTO); a non-empty fault_inject spec arms
        # the process-global injection plan — configuring from the
        # solver keeps C-shaped drivers on the one-config-string model
        self.recovery_policy = str(g("recovery_policy"))
        self.recovery_max_attempts = int(g("recovery_max_attempts"))
        fi_spec = str(g("fault_inject"))
        if fi_spec:
            # idempotent per spec: nested/session/twin solvers built
            # from the same config must not re-arm consumed triggers
            faultinject.configure_knob(fi_spec)
        # an EXPLICIT verbosity_level drives the level-gated output
        # stream; the registry default must not clobber a verbosity the
        # host application set programmatically
        if cfg.has("verbosity_level", scope):
            from ..utils.logging import set_verbosity
            set_verbosity(int(g("verbosity_level")))
        self.A: Optional[Matrix] = None
        self.Ad: Optional[DeviceMatrix] = None
        self.scaler = None
        self._solve_fn = None
        self._refined_fn = None
        self._solve_multi = None
        self._solve_multi_refined = None
        self._bindings = None
        self.setup_time = 0.0

    # ------------------------------------------------------------ lifecycle
    def setup(self, A: "Matrix | DeviceMatrix"):
        """Host-side setup (reference ``Solver::setup``, solver.cu:380-556):
        optional scaling → solver-specific setup."""
        phase = "resetup" if getattr(self, "_numeric_resetup", False) \
            else "setup"
        # nested solvers (smoothers, coarse solver, preconditioner)
        # re-enter setup(): their spans nest in the trace (that is how
        # "where did setup time go" reads), but the phase METRICS are
        # top-level only — 8 overlapping amgx_setup_seconds samples per
        # user-facing setup would inflate every aggregate
        toplevel = bool(getattr(self, "_toplevel", False))
        t0 = time.perf_counter()
        # setup attribution (telemetry/setup_profile.py): only the
        # TOP-LEVEL setup opens a profile scope — nested smoother/
        # coarse-solver setups contribute phases into it
        _sp = telemetry.setup_profile
        prof = _sp.profile_setup(self.config_name) if toplevel \
            else _sp.null()
        try:
            with telemetry.span(phase, solver=self.config_name,
                                scope=self.scope, toplevel=toplevel), \
                    prof:
                self._setup_impl(A)
        except Exception as e:
            # device OOM (real RESOURCE_EXHAUSTED or the injected
            # fault_inject `oom` point): emit the ledger post-mortem
            # before the failure propagates — what was resident is
            # exactly the forensic record an OOM destroys
            if telemetry.memledger.is_oom_error(e):
                telemetry.memledger.emit_postmortem(
                    e, "setup", in_recovery=bool(
                        getattr(self, "_in_recovery", False)))
            raise
        self.setup_time = time.perf_counter() - t0
        if toplevel:
            telemetry.memledger.maybe_sample(phase=phase)
        if toplevel and telemetry.is_enabled():
            telemetry.hist_observe(f"amgx_{phase}_seconds",
                                   self.setup_time)
            telemetry.gauge_set("amgx_last_setup_seconds",
                                self.setup_time)
            if self.Ad is not None:
                # the fine operator's static cost descriptor
                # (telemetry/costmodel.py): bytes/FLOPs per apply,
                # padding waste, halo wire bytes when sharded — the
                # doctor pairs it with span durations for
                # achieved-vs-peak fractions
                try:
                    from ..telemetry import costmodel
                    telemetry.event(
                        "operator_cost", solver=self.config_name,
                        **costmodel.spmv_cost(
                            self.Ad,
                            nnz=self.A.nnz if self.A is not None
                            else None))
                except Exception:
                    pass    # a cost-model gap must never break setup
            # device setup engine (amg/device_setup/): plan-cache
            # hit/miss/fallback counts of the pattern-keyed Galerkin
            # executables — None (and zero cost) when nothing ever
            # instantiated the engine
            try:
                from ..amg.device_setup import engine_stats
                st = engine_stats()
                if st is not None:
                    telemetry.event("device_setup_cache", **st)
            except Exception:
                pass        # observability must never break setup
            if self.telemetry_path:
                telemetry.flush_jsonl(self.telemetry_path)
        return self

    def _apply_precision_knobs(self, A: Matrix) -> Matrix:
        """``krylov_dtype`` / ``tpu_matrix_dtype``: the TOP-LEVEL
        solver's device pack dtype — which IS the Krylov loop's
        vector/dot/monitoring precision.  Only the outermost solver
        applies it: nested smoothers get their storage precision from
        the hierarchy policy (``hierarchy_dtype``), and a default-scope
        knob leaking into every nested setup would override it.

        Returns a shallow VIEW when the knob applies — the caller's
        matrix is never mutated (a second solver sharing the same
        Matrix must see its own dtype choice, not this one's).  Packs
        that would lose an f32-only kernel layout keep their dtype
        (``precision.precision_view`` returns ``A`` unchanged)."""
        from ..core import precision
        kd = precision.resolve_dtype(
            str(self.cfg.get("krylov_dtype", self.scope)))
        if kd is None:
            kd = precision.resolve_dtype(
                str(self.cfg.get("tpu_matrix_dtype", self.scope)))
        if kd is None:
            return A
        cur = np.dtype(A.device_dtype or A.dtype)
        kd = np.dtype(kd)
        if cur == kd:
            return A
        dev = A._device
        if dev is not None and kd.itemsize > \
                np.dtype(dev.dtype).itemsize:
            # widening an existing pack must rebuild from the wide host
            # values (an on-device upcast would keep the narrow digits)
            import copy
            m = copy.copy(A)
            m.device_dtype = kd
            m._device = None
            m._device_dtype = None
            m._dinv_dev = None
            return m
        # narrowing (or no pack yet): on-device cast when a pack
        # exists (zero wire bytes), cast-on-upload otherwise
        return precision.precision_view(A, kd)

    def _setup_impl(self, A: "Matrix | DeviceMatrix"):
        # the matrix AS THE CALLER PASSED IT (pre-scaling/reorder): the
        # recovery ladder's conservative/resetup rungs rebuild from it —
        # re-running setup on the scaled copy would scale twice.  Only
        # retained when the ladder can use it: with recovery off,
        # pinning the original next to a scaled/reordered copy would
        # double host matrix retention for nothing
        if self.recovery_policy not in ("", "NONE"):
            self._setup_input = A
        faultinject.maybe_raise(
            "setup_error", AMGXError("injected setup failure", RC.CORE))
        self.scaler = None
        self._reorder = None
        scaling = str(self.cfg.get("scaling", self.scope))
        if isinstance(A, Matrix):
            if getattr(self, "_toplevel", False) and A.dist is None:
                A = self._apply_precision_knobs(A)
            if scaling != "NONE" and A.dist is None and A.block_dim == 1:
                # scale a copy (reference scales in place then "unscales";
                # solver.cu:441-475 documents that workaround — a copy is
                # cleaner and setup-phase only)
                from .scalers import create_scaler
                with cpu_profiler("setup_scaling"), \
                        telemetry.setup_profile.phase("scaling"):
                    self.scaler = create_scaler(scaling, self.cfg,
                                                self.scope)
                    self.scaler.setup(A.scalar_csr())
                    dd = A.device_dtype
                    A = Matrix(self.scaler.scale_matrix(A.scalar_csr()))
                    # the scaled copy must keep the precision knobs'
                    # pack dtype (the reorder copy does the same)
                    A.device_dtype = dd
            if getattr(self, "_toplevel", False):
                # reordering is OWNED by the outermost solver: only its
                # solve() has the permute boundary — a nested smoother/
                # preconditioner permuting its operator would be fed
                # residuals in the un-permuted level ordering
                with telemetry.setup_profile.phase("reorder"):
                    A2 = self._maybe_reorder(A)
                if A2 is not None:
                    A = A2
            self.A = A
            faultinject.maybe_raise(
                "upload_error",
                AMGXError("injected transfer/upload failure",
                          RC.CUDA_FAILURE))
            faultinject.maybe_raise(
                "oom", AMGXError("injected device out-of-memory",
                                 RC.NO_MEMORY))
            with cpu_profiler("matrix_pack_device"), \
                    telemetry.setup_profile.phase("pack", kind="device"):
                self.Ad = A.device()
        else:
            self.A = None
            self.Ad = A
        ml = telemetry.memledger
        if ml.is_enabled() and getattr(self, "_toplevel", False) \
                and self.Ad is not None:
            # the top-level operator pack; hierarchy/transfer/smoother
            # packs register themselves (amg/hierarchy.py) and claim
            # their buffers ahead of this aggregate-adjacent owner
            ml.release(getattr(self, "_ml_matrix_tok", None))
            self._ml_matrix_tok = ml.register(
                ml.owner_name("matrix", self.config_name), self.Ad)
        with cpu_profiler(f"setup:{self.config_name}"):
            self.solver_setup()
        if getattr(self, "_numeric_resetup", False) \
                and (self._solve_fn is not None
                     or self._solve_multi is not None
                     or self._solve_multi_refined is not None) \
                and self._bindings is not None:
            # numeric re-setup (resetup() only — a plain setup() keeps
            # its full-rebuild contract): keep the jitted executables and
            # refresh the binding slots in place — with unchanged array
            # shapes jax.jit's cache hits and the recompile is skipped
            # (AMGX_solver_resetup contract: same structure, new values).
            # A structural change alters the argument pytree and
            # retraces automatically.
            if hasattr(self, "_refine_lo"):
                del self._refine_lo       # stale rounding residue
                self._ensure_refine_data()
            self._bindings._discover(self)
            if self.Ad is not None and self.Ad.fmt == "sharded-ell":
                # rebuilt consolidated coarse levels may sit on a device
                # subset again — re-replicate them onto the mesh
                self._bindings.normalize_placement(self.Ad.mesh)
        else:
            self._solve_fn = None
            self._refined_fn = None
            self._solve_multi = None
            self._solve_multi_refined = None
            # a full rebuild replaces hierarchy/level objects: bindings
            # slots referencing the OLD objects would keep serving stale
            # device data to a later solve_multi
            self._bindings = None
            # new matrix values ⇒ stale rounding residue; next refined
            # solve rebuilds it (and the bindings that carry it)
            if hasattr(self, "_refine_lo"):
                del self._refine_lo

    def resetup(self, A: "Matrix | DeviceMatrix"):
        """Numeric refresh after ``replace_coefficients``: same structure,
        new values (``AMGX_solver_resetup``).  Compiled executables,
        nested preconditioner instances, and hierarchy structure survive;
        a plain ``setup()`` remains a full rebuild."""
        self._numeric_resetup = True
        try:
            return self.setup(A)
        finally:
            self._numeric_resetup = False

    def _maybe_reorder(self, A: Matrix) -> Optional[Matrix]:
        """Setup-time RCM bandwidth reduction — the gather-cliff rescue.

        A matrix that is neither DIA-eligible nor within the windowed
        kernel's per-tile column-block budget would fall onto XLA's TPU
        gather lowering (~0.2 GFLOPS, three orders below the window
        kernel).  AUTO mode permutes such matrices with reverse
        Cuthill–McKee ONCE at setup when that makes the window fit; the
        whole solve then runs in permuted space and rhs/solution are
        converted at the solve boundaries (reference analog: setup-time
        renumbering, ``matrix.cu:760-813``).  Returns the permuted
        Matrix, or None to keep ``A``."""
        mode = str(self.cfg.get("matrix_reorder", self.scope))
        # probe private fields, not the .host property: that would lazily
        # assemble CSR for DIA-backed matrices (a device-generated 256³
        # operator never needs a host CSR; AUTO bails on DIA below anyway)
        if mode == "NONE" or not isinstance(A, Matrix) or \
                A.dist is not None or A.block_dim != 1 or \
                (A._host is None and A._dia is None and
                 getattr(A, "_dia_thunk", None) is None) or \
                A.shape[0] != A.shape[1]:
            return None
        if mode == "AUTO":
            if getattr(A, "_dia_offsets_hint", None) is not None:
                # device-generated stencil: DIA-backed by construction,
                # AUTO never reorders those — skip without materialising
                # the host arrays
                return None
            from ..ops.pallas_ell import _INTERPRET
            if not (jax.default_backend() == "tpu" or _INTERPRET):
                return None
            dtype = np.dtype(A.device_dtype or A.dtype)
            if dtype != np.float32 or A.dia_cache(48) is not None:
                return None
            csr0 = A.scalar_csr()
            if _window_fits(csr0) is not False:
                return None     # already window-eligible (or too wide)
            if _binned_fits(csr0):
                # the binned sliced-ELL kernel already carries this
                # matrix at windowed-kernel class or better — no RCM
                return None
        from scipy.sparse.csgraph import reverse_cuthill_mckee
        csr = A.scalar_csr()
        perm = np.asarray(reverse_cuthill_mckee(csr,
                                                symmetric_mode=False),
                          dtype=np.int64)
        csr_p = csr[perm][:, perm].tocsr()
        if mode == "AUTO" and _window_fits(csr_p) is not True:
            return None          # RCM didn't make the window fit
        Ap = Matrix(csr_p)
        Ap.device_dtype = A.device_dtype
        Ap.placement = A.placement
        self._reorder = (perm, np.argsort(perm))
        return Ap

    def solver_setup(self):
        """Override: build device-side data (diag inverse, hierarchy, ...)."""

    # ------------------------------------------------------- traced protocol
    def solve_init(self, b: jax.Array, x: jax.Array) -> Any:
        """Return the solver-specific iteration state pytree."""
        return ()

    def solve_iteration(self, b: jax.Array, x: jax.Array, state: Any,
                        iter_idx: jax.Array):
        """One iteration: return (x_new, state_new).

        ``iter_idx`` is the traced global iteration counter (used e.g. by
        FGMRES for its restart-cycle position).
        """
        raise NotImplementedError

    # ------------------------------------------------ preconditioner protocol
    def apply(self, b: jax.Array, x0: Optional[jax.Array] = None,
              n_iters: Optional[int] = None) -> jax.Array:
        """Traced application as a preconditioner/smoother: run a fixed
        number of iterations with no convergence monitoring (reference
        ``Solver::smooth`` / preconditioner ``solve`` with small max_iters).

        Must be called inside a trace; assumes :meth:`setup` has run.

        Smoother applications carry the ``amgx/smoother/<config_name>``
        named scope (telemetry/scopes.py contract) so the profiler-trace
        correlator can attribute their device time.
        """
        n = self.max_iters if n_iters is None else n_iters
        x = jnp.zeros_like(b) if x0 is None else x0
        if self.is_smoother:
            with telemetry.scopes.scope("smoother", self.config_name):
                state = self.solve_init(b, x)
                for i in range(n):
                    x, state = self.solve_iteration(b, x, state,
                                                    jnp.asarray(i))
            return x
        state = self.solve_init(b, x)
        for i in range(n):
            x, state = self.solve_iteration(b, x, state, jnp.asarray(i))
        return x

    def compute_residual_norm(self, b, x):
        r = b - spmv(self.Ad, x)
        return blas.norm(r, self.norm_type, self.Ad.block_dim,
                         self.use_scalar_norm)

    # ------------------------------------------------------------- solve API
    def _sync_fault_trace(self):
        """Fault injection (utils/faultinject.py): an armed traced
        point (values_nan / krylov_zero) is compiled INTO the loop;
        arming-state changes must invalidate EVERY jitted solve body —
        one list, shared by both drivers, so a future cached variant
        cannot be forgotten on one path and serve a poisoned executable
        on the clean one.  Returns the active ``(mode, iteration)`` or
        None; costs one getattr when disarmed."""
        fault = faultinject.trace_mode()
        if fault != getattr(self, "_fault_trace", None):
            self._fault_trace = fault
            self._invalidate_solve_fns()
        return fault

    def _invalidate_solve_fns(self):
        """Drop every cached jitted solve body — anything compiled INTO
        the loop (fault points, the krylov_comm reduction layout) must
        call this when it changes."""
        self._solve_fn = None
        self._refined_fn = None
        self._solve_multi = None
        self._solve_multi_refined = None

    def _tolerance_floor(self, dtype) -> float:
        """Smallest relative residual honestly reachable in ``dtype``
        (core/precision.py owns the floor formula and the ladder)."""
        from ..core.precision import tolerance_floor
        return tolerance_floor(dtype)

    def _promotion_plan(self):
        """(refine_active, wide_dtype, structural_block) for the
        current tolerance.

        ``refine_active`` says whether the mixed-precision
        defect-correction outer loop (``_solve_refined``) runs;
        ``wide_dtype`` is the ladder rung recomputing true residuals
        (``core.precision.promotion_target``: bf16 → f32, f32 → f64 —
        one rounding-residue plane per promotion).  The rung needs the
        wide HOST matrix: ``lo = vals_w − w(pack(vals_w))``
        reconstructs the exact wide operator only against genuinely
        wider uploaded values.  ``structural_block`` is True when
        refinement is unavailable for reasons no precision choice can
        fix (block distribution, scaling, device-only operator, complex
        modes) — the single predicate ``_check_tolerance_floor`` keys
        its warn-vs-raise split on."""
        dtype = self.Ad.dtype
        # breakdown-triggered promotion (solvers/recovery.py "promote"
        # rung): the ladder may force a promotion even when the
        # tolerance sits above the dtype floor — a stagnating/poisoned
        # narrow solve is re-run one rung wider
        forced = bool(getattr(self, "_force_promotion", False))
        if not (self.monitor_residual
                and (forced
                     or self.tolerance < self._tolerance_floor(dtype))):
            return False, None, False
        from ..core import precision
        if self.tolerance <= 0 \
                or (self.Ad.fmt == "sharded-ell"
                    and self.Ad.block_dim > 1) \
                or self.scaler is not None or self.A is None \
                or not precision.is_floating(np.dtype(dtype)):
            # tolerance<=0 is the run-to-max_iters convention (the
            # reference's "never converge, fixed sweeps") — no
            # convergence claim is ever made, so no honesty error; it
            # keeps the historical warn-and-run like the other
            # structurally-unrefinable cases
            return False, None, True
        # Matrix.dtype, not .host.dtype: the property would lazily
        # assemble CSR for DIA-backed operators
        host_dt = np.dtype(self.A.dtype)
        if host_dt.itemsize <= np.dtype(dtype).itemsize:
            return False, None, False
        wide = precision.promotion_target(dtype, host_dt,
                                          self.tolerance)
        if wide is None and forced:
            # the tolerance alone asked for no rung — take the next one
            # up anyway (bounded by the host dtype and the hi+lo
            # reconstruction limit, same gates as promotion_target)
            ddt = np.dtype(dtype)
            for rung in precision.LADDER:
                if ddt.itemsize < rung.itemsize <= host_dt.itemsize \
                        and rung.itemsize <= 2 * ddt.itemsize:
                    wide = rung
                    break
        if wide is None:
            return False, None, False
        return True, np.dtype(wide), False

    def _check_tolerance_floor(self, refine: bool, structural: bool):
        """Below-floor tolerances without a promotion rung are a
        configuration error, not a silent stall: the solve would burn
        its full iteration budget and report NOT_CONVERGED at best —
        or, in a narrow dtype, declare a convergence no true residual
        supports.  Structurally-unrefinable solves (``structural`` from
        ``_promotion_plan``: complex modes, block distribution, scaling,
        device-only operators) keep the historical warn-and-run — an
        error whose guidance could not help them would break existing
        deep-tolerance workflows."""
        dtype = self.Ad.dtype
        floor = self._tolerance_floor(dtype)
        if refine or not self.monitor_residual \
                or self.tolerance >= floor:
            return
        if structural:
            amgx_output(
                f"WARNING: tolerance {self.tolerance:g} is below the "
                f"{np.dtype(dtype).name} precision floor (~{floor:.1g});"
                " convergence to it cannot be honestly declared.\n")
            return
        raise BadParametersError(
            f"tolerance {self.tolerance:g} is below the "
            f"{np.dtype(dtype).name} precision floor (~{floor:.1g}) "
            "and no promotion rung is available: upload the matrix at "
            "a wider dtype (f64 host + narrow device pack enables the "
            "defect-correction ladder), raise the tolerance, or run "
            "the Krylov loop wider (krylov_dtype=float32 with "
            "hierarchy_dtype=bfloat16 keeps the bandwidth win)")

    def solve(self, b, x0=None, zero_initial_guess: bool = False
              ) -> SolveResult:
        """Full solve with convergence monitoring (solver.cu:589-970).

        The entire loop runs as one jitted ``lax.while_loop``; the residual
        history (when requested) is written into a fixed-size device buffer.

        Honesty contract (the reference recomputes true residuals in its
        convergence loop, ``solver.cu:776-805``): the *final* reported norm
        is always a freshly computed true residual — solvers' cheap
        in-loop estimates (FGMRES quasi-residual, CG recursion) only steer
        the loop.  When the requested tolerance is below the device dtype's
        precision floor and a higher-precision host matrix is available,
        the solve runs as mixed-precision iterative refinement: fp32 device
        solves corrected by fp64 host residuals (the TPU realisation of the
        reference's dDFI mixed mode).
        """
        if self.Ad is None:
            raise BadConfigurationError("solve() before setup()")
        dtype = self.Ad.dtype
        # the caller's untouched rhs/guess: the recovery ladder
        # (solvers/recovery.py) re-enters solve() with these — the
        # scaled/permuted/sharded forms below are per-attempt state
        b_caller, x0_caller = b, x0
        fault = self._sync_fault_trace()
        if self.scaler is not None:
            b = self.scaler.scale_rhs(np.asarray(b, dtype=dtype))
            if x0 is not None and not zero_initial_guess:
                x0 = self.scaler.scale_initial_guess(
                    np.asarray(x0, dtype=dtype))
        if self._reorder is not None:
            # the pack lives in RCM space (see _maybe_reorder): permute
            # the rhs/guess in AFTER scaling (setup scaled first, then
            # permuted — the pack is P·S·A·S·Pᵀ) and un-permute the
            # solution BEFORE unscaling on the way out; norms are
            # permutation-invariant, so monitoring is unchanged
            perm, _ = self._reorder
            b = np.asarray(b)[perm]
            if x0 is not None and not zero_initial_guess:
                x0 = np.asarray(x0)[perm]
        b_in = b
        x0_in = None if zero_initial_guess else x0
        dist = self.Ad.fmt == "sharded-ell"

        # the promotion ladder (core/precision.py): inner solves at the
        # pack dtype, true residuals recomputed one rung wider
        # (bf16 → f32, f32 → f64), bounded by the uploaded host matrix
        refine, wide, structural = self._promotion_plan()
        self._check_tolerance_floor(refine, structural)

        if dist:
            from ..distributed.matrix import shard_vector
            b = shard_vector(self.Ad, b)
            if x0 is not None and not zero_initial_guess:
                x0 = shard_vector(self.Ad, x0)
        pin = None
        if not dist:
            # pinned packs (host modes; complex modes on a TPU runtime
            # without complex support) pull the solve vectors onto THEIR
            # device — jit rejects mixed device sets
            try:
                devs = list(self.Ad.diag.devices())
                if len(devs) == 1 and devs[0] != jax.devices()[0]:
                    pin = devs[0]
            except Exception:
                pin = None
        if not dist and not refine:
            # device-resident b stays put; anything else uploads — and a
            # wrong-dtype device array is cast so the loop never silently
            # retraces in (TPU-emulated) f64.  Pinned solves go STRAIGHT
            # to the pin: staging through the default device would ship
            # (and, for complex dtypes, hang) on a backend that cannot
            # hold the data.
            if pin is not None:
                if not (isinstance(b, jax.Array) and b.dtype == dtype
                        and set(b.devices()) == {pin}):
                    b = jax.device_put(np.asarray(b, dtype=dtype), pin)
            else:
                b = jnp.asarray(b, dtype) if isinstance(b, jax.Array) \
                    else jnp.asarray(np.asarray(b), dtype=dtype)
        if not refine:
            if x0 is None or zero_initial_guess:
                if pin is not None:
                    x0 = jax.device_put(
                        np.zeros(np.shape(b), dtype=dtype), pin)
                else:
                    x0 = jnp.zeros_like(b)
            elif not dist:
                if pin is not None:
                    if not (isinstance(x0, jax.Array)
                            and x0.dtype == dtype
                            and set(x0.devices()) == {pin}):
                        x0 = jax.device_put(np.asarray(x0, dtype=dtype),
                                            pin)
                else:
                    x0 = jnp.asarray(x0, dtype) \
                        if isinstance(x0, jax.Array) \
                        else jnp.asarray(np.asarray(x0), dtype=dtype)

        if refine and not hasattr(self, "_refine_lo"):
            # refine became active after a non-refined solve (e.g. the user
            # tightened .tolerance): the bindings must be rebuilt so the
            # refine pack rides as a jit argument, not a trace constant
            self._solve_fn = None
        if self._solve_fn is None:
            # Device data (matrix pack, hierarchy levels, smoother arrays)
            # is passed INTO the jitted function as an argument pytree, not
            # captured as trace-time constants: XLA would bake constants
            # into the executable, which dies at benchmark scale (the
            # reference contract is any-N kernels, multiply.cu:75-196).
            from ._bind import DeviceBindings, bind_for_trace
            if refine:
                self._ensure_refine_data()
            self._bindings = DeviceBindings(self)
            # the batched executables close over the bindings object —
            # a rebuilt bindings set means they must re-bind too
            self._solve_multi = None
            self._solve_multi_refined = None
            if dist:
                self._bindings.normalize_placement(self.Ad.mesh)
            self._solve_fn = jax.jit(
                bind_for_trace(self._bindings, self._packed_solve_fn()))
            self._refined_fn = None
            self._ml_register_bindings()

        t0 = time.perf_counter()
        try:
            with telemetry.span("solve", solver=self.config_name,
                                scope=self.scope, refined=bool(refine)), \
                    cpu_profiler(f"solve:{self.config_name}"):
                if refine:
                    # refinement must see the caller's full-precision
                    # rhs/guess — the dtype-cast b/x0 above would fold
                    # the fp32 rounding of b itself into the
                    # "converged" solution
                    x, iters, brk_code, first_bad, nrm, nrm_ini, \
                        history = self._solve_refined(b_in, x0_in, wide)
                else:
                    import contextlib
                    ctx = jax.default_device(pin) if pin is not None \
                        else contextlib.nullcontext()
                    # tolerances compare against REAL norms (complex
                    # modes)
                    rdt = np.zeros((), dtype).real.dtype
                    with ctx:
                        # the scalar operands are created INSIDE the pin
                        # context — built outside they would land on the
                        # default device and ship per solve
                        call_args = (self._bindings.collect(), b, x0,
                                     jnp.asarray(self.tolerance, rdt),
                                     jnp.asarray(self.max_iters,
                                                 jnp.int32))
                        fn = self._solve_fn
                        if not dist:
                            # warm-start layer: load/compile-and-save
                            # the AOT executable for these shapes (no-op
                            # without a configured store); sharded packs
                            # keep jit.  Pinned packs (multi-lane
                            # serving: one executor lane per device)
                            # participate with a device-qualified key —
                            # a serialized executable bakes in its
                            # device assignment, so lane 3's entry must
                            # never load on lane 0
                            fn = self._maybe_aot("solve", fn, call_args,
                                                 device=pin)
                        x, stats, history = fn(*call_args)
                    # ONE small host fetch for (iters, breakdown,
                    # norms): one transfer call, not five
                    iters, brk_code, first_bad, nrm, nrm_ini = \
                        self._decode_stats(np.asarray(stats))
        except Exception as e:
            # device OOM mid-solve: the ledger post-mortem is the only
            # record of what was resident when the allocator gave up
            if telemetry.memledger.is_oom_error(e):
                telemetry.memledger.emit_postmortem(
                    e, "solve", in_recovery=bool(
                        getattr(self, "_in_recovery", False)))
            raise
        solve_time = time.perf_counter() - t0
        telemetry.memledger.maybe_sample(phase="solve")
        # record the injection only when it actually PROVOKED something
        # (a solve converging before the target iteration — or a
        # solver whose recursion recomputes the zeroed scalar, like
        # BiCGStab under krylov_zero — must not claim a fault that
        # never bit): on monitored solves the breakdown flag is the
        # evidence; unmonitored solves can only witness the iteration
        # count
        if fault is not None and \
                (bool(brk_code) if self.monitor_residual
                 else int(iters) > int(fault[1])):
            faultinject.fired(fault[0], iteration=fault[1])
        if dist:
            from ..distributed.matrix import unshard_vector
            x = unshard_vector(self.Ad, x)
        if self._reorder is not None:
            x = np.asarray(x)[self._reorder[1]]
        if self.scaler is not None:
            x = self.scaler.unscale_solution(np.asarray(x))

        iters = int(iters)
        nrm = np.atleast_1d(np.asarray(nrm))
        nrm_ini_np = np.atleast_1d(np.asarray(nrm_ini))
        failure = None
        if self.monitor_residual:
            nrm_max_np = nrm_ini_np
            if self.convergence in ("RELATIVE_MAX", "RELATIVE_MAX_CORE") \
                    and history is not None:
                # the true running max of the monitored norms — treating
                # max as ini under-reported legitimately converged solves
                # against a growing nrm_max (solver.cu:776-805 tracks it)
                h = np.atleast_2d(np.asarray(history))[:iters + 1]
                h = self._finite_history(h, context="nrm_max")
                if h.size:
                    nrm_max_np = np.maximum(nrm_ini_np, h.max(axis=0))
            conv = bool(np.all(self._host_converged(nrm, nrm_ini_np,
                                                    nrm_max_np)))
            diverged = bool(np.any(~np.isfinite(nrm)))
            # breakdown codes with a finite terminal residual (krylov
            # rho-collapse, indefinite pAp) report FAILED — the loop was
            # cut short by the guard, not by the iteration budget
            status = (SolveStatus.SUCCESS if conv else
                      (SolveStatus.DIVERGED if diverged else
                       (SolveStatus.FAILED if brk_code
                        else SolveStatus.NOT_CONVERGED)))
            failure = self._classify_failure(conv, diverged, brk_code,
                                             first_bad, nrm, iters)
        else:
            status = SolveStatus.SUCCESS
        history_np = None
        if self.store_res_history or self.print_solve_stats:
            history_np = np.asarray(history)[:iters + 1]
        if self.print_solve_stats:
            self._print_solve_stats(history_np, iters, status)
        if self.obtain_timings:
            amgx_output(f"Total Time: {self.setup_time + solve_time:10.6f}\n"
                        f"    setup: {self.setup_time:10.6f} s\n"
                        f"    solve: {solve_time:10.6f} s\n"
                        f"    solve(per iteration): "
                        f"{solve_time / max(iters, 1):10.6f} s\n")
        if telemetry.is_enabled():
            self._emit_solve_telemetry(iters, nrm, nrm_ini_np, status,
                                       history_np, solve_time,
                                       failure=failure)
        res = SolveResult(x=x, iterations=iters, status=status,
                          residual_norm=nrm, residual_history=history_np,
                          setup_time=self.setup_time,
                          solve_time=solve_time, failure=failure)
        if status != SolveStatus.SUCCESS \
                and self.recovery_policy not in ("", "NONE") \
                and self.monitor_residual \
                and not getattr(self, "_in_recovery", False) \
                and not getattr(self, "_suppress_recovery", False):
            # bounded, telemetry-audited escalation (restart → promote
            # → conservative smoother → full re-setup); the ladder
            # re-enters solve() with _in_recovery set, so it can never
            # recurse into itself
            from .recovery import maybe_recover
            res = maybe_recover(self, b_caller, x0_caller,
                                zero_initial_guess, res)
        return res

    def _ml_register_bindings(self):
        """HBM-ledger registration of the solve-loop binding pytree
        (owner ``amgx/solve/bindings`` — an AGGREGATE owner: buffers the
        hierarchy/smoother/matrix owners already claimed stay theirs,
        so this names only the otherwise-unowned solve transients).
        One attribute check when the ledger is off."""
        ml = telemetry.memledger
        if not ml.is_enabled() or self._bindings is None:
            return
        # binding discovery just FORCED the lazy device packs (P/R
        # transfer operators materialize on first touch) — re-register
        # the hierarchies so those buffers claim under amgx/transfer/…
        # instead of falling through to this aggregate.  The hierarchy
        # hangs off self for a standalone AMG solve and off the
        # preconditioner chain for a Krylov-wrapped one
        obj, seen = self, set()
        while obj is not None and id(obj) not in seen:
            seen.add(id(obj))
            h = getattr(obj, "hierarchy", None)
            if h is not None and hasattr(h, "_register_memledger"):
                h._register_memledger()
            obj = getattr(obj, "preconditioner", None)
        ml.release(getattr(self, "_ml_bind_tok", None))
        self._ml_bind_tok = ml.register(
            ml.owner_name("solve", "bindings"), self._bindings.collect())

    def release_memledger(self):
        """Drop this solver's HBM-ledger registrations (teardown): the
        operator pack, the solve bindings, and — for AMG solvers — the
        hierarchy/transfer/smoother/coarse entries.  Weakref-backed
        entries stop counting when the arrays die anyway; explicit
        release keeps the register/release balance exact."""
        ml = telemetry.memledger
        ml.release(getattr(self, "_ml_matrix_tok", None))
        ml.release(getattr(self, "_ml_bind_tok", None))
        self._ml_matrix_tok = self._ml_bind_tok = None
        obj, seen = self, set()
        while obj is not None and id(obj) not in seen:
            seen.add(id(obj))
            h = getattr(obj, "hierarchy", None)
            if h is not None and hasattr(h, "release_memledger"):
                h.release_memledger()
            obj = getattr(obj, "preconditioner", None)

    def _maybe_aot(self, tag: str, jit_fn: Callable, args: tuple,
                   device=None) -> Callable:
        """The AOT-store executable for ``jit_fn(*args)`` when the
        warm-start layer is configured and this solve path serializes
        cleanly; else ``jit_fn`` unchanged.  Serialization gates:
        forensics inserts ``jax.debug.callback``s (host callbacks do
        not survive serialization across processes), so instrumented
        solves keep the plain jit path — the persistent compilation
        cache still covers their XLA compile.  ``device``: the pin of a
        device-pinned solve (host modes; multi-lane serving's per-chip
        executor lanes) — qualifies the store key, because a serialized
        executable carries its device assignment and must only ever be
        reloaded for that same device."""
        if self.forensics:
            return jit_fn
        if getattr(self, "_fault_trace", None) is not None:
            # a traced fault injection is compiled INTO this body — it
            # must never be serialized under the clean executable's key
            return jit_fn
        try:
            from ..serve import aot
            if aot.get_store() is None:
                return jit_fn
            if device is not None:
                tag = f"{tag}@{device.platform}{device.id}"
            # per-solve memo, living ON the bindings object: the full
            # key digests the whole bindings pytree (kilobytes for a
            # deep hierarchy) — too costly per warmed millisecond-class
            # solve.  Binding avals are fixed for a bindings object's
            # lifetime (a structural rebuild replaces it), so (tag, RHS
            # shape/dtype) identifies the executable within it.
            memo = getattr(self._bindings, "_aot_memo", None)
            if memo is None:
                memo = self._bindings._aot_memo = {}
            rhs = args[1]
            mk = (tag, getattr(rhs, "shape", None),
                  str(getattr(rhs, "dtype", "")))
            hit = memo.get(mk)
            if hit is not None:
                return hit
            if not hasattr(self, "_aot_cfg_hash"):
                self._aot_cfg_hash = self.cfg.stable_hash()
            from ..core.matrix import pack_kind
            meta = {"solver": self.config_name, "scope": self.scope,
                    "pack": pack_kind(self.Ad) if self.Ad is not None
                    else None,
                    "n_rows": int(self.Ad.n_rows)
                    if self.Ad is not None else None,
                    "dtype": str(self.Ad.dtype)
                    if self.Ad is not None else None}
            fn = aot.aot_compile(
                f"{tag}:{self.config_name}:{self.scope}", jit_fn, args,
                cfg_hash=self._aot_cfg_hash, meta=meta)
            memo[mk] = fn
            return fn
        except Exception:   # the warm-start layer must never break solve
            return jit_fn

    def _packed_solve_fn(self) -> Callable:
        """The solve body with (iters, breakdown, nrm, nrm_ini) packed
        into one f64 stats vector — ONE small host fetch per solve.
        Shared by the single-RHS driver and the vmapped multi-RHS
        driver so both stay on the same wire layout (decoded by
        :meth:`_decode_stats`: ``[it, brk_code, first_bad, nrm*m,
        nrm_ini*m]``)."""
        body = self._build_solve_fn()

        def packed(b, x0, tol, it_limit):
            x, it, nrm, nrm_ini, history, fail = body(b, x0, tol,
                                                      it_limit)
            stats = jnp.concatenate([
                it[None].astype(jnp.float64),
                fail.astype(jnp.float64),
                jnp.ravel(nrm).astype(jnp.float64),
                jnp.ravel(nrm_ini).astype(jnp.float64)])
            return x, stats, history

        return packed

    @staticmethod
    def _decode_stats(stats: np.ndarray):
        """Inverse of :meth:`_packed_solve_fn`'s wire layout:
        ``(iters, brk_code, first_bad, nrm, nrm_ini)``."""
        iters = int(stats[0])
        brk_code = int(stats[1])
        first_bad = int(stats[2])
        m = (len(stats) - 3) // 2
        return iters, brk_code, first_bad, stats[3:3 + m], stats[3 + m:]

    def _classify_failure(self, conv: bool, diverged: bool,
                          brk_code: int, first_bad: int, nrm,
                          iters: int) -> Optional[FailureInfo]:
        """The terminal :class:`~amgx_tpu.errors.FailureInfo` of a
        monitored solve (None on convergence): the in-loop guard's code
        wins (it carries the first-bad iteration); a non-finite final
        norm without one classifies by NaN-vs-inf; anything else that
        burned the budget is stagnation."""
        if conv:
            return None
        if brk_code:
            kind = breakdown_kind(brk_code)
            if kind is not None:
                return FailureInfo(
                    kind=kind,
                    iteration=first_bad if first_bad >= 0 else None)
        if diverged:
            nan = bool(np.any(np.isnan(np.asarray(nrm))))
            return FailureInfo(
                kind=(FailureKind.NAN_POISON if nan
                      else FailureKind.DIVERGENCE),
                iteration=iters)
        return FailureInfo(kind=FailureKind.STAGNATION, iteration=iters)

    def _finite_history(self, h: np.ndarray,
                        context: str = "") -> np.ndarray:
        """Filter non-finite rows out of a residual-history slab — and
        SAY SO: the old silent ``np.isfinite(...).all(axis=1)`` filters
        dropped the very rows a breakdown forensics needs, with no
        trace that the iteration record was truncated."""
        if h.size == 0:
            return h
        mask = np.isfinite(h).all(axis=1)
        if mask.all():
            return h
        first_bad = int(np.argmin(mask))
        dropped = int((~mask).sum())
        if telemetry.is_enabled():
            telemetry.counter_inc("amgx_history_truncated_total")
            telemetry.event("history_truncated",
                            first_bad_iteration=first_bad,
                            dropped=dropped, context=context,
                            solver=self.config_name)
        return h[mask]

    # ------------------------------------------------------ multi-RHS solve
    def solve_multi(self, B, X0=None, zero_initial_guess: bool = False,
                    pad_to_bucket: bool = False) -> "list[SolveResult]":
        """Batched solve of k right-hand sides against ONE operator in a
        single executable — the serving layer's micro-batch path
        (serve/batch.py).

        ``B`` is (k, n) (or a sequence of k vectors); returns one
        :class:`SolveResult` per RHS.  The batched loop is the
        single-RHS solve body vmapped over the RHS axis: per-request
        convergence monitoring is preserved (the batched ``while_loop``
        runs until every lane is done, a converged lane's state frozen
        by the standard select-masking), so one RHS can converge in 3
        iterations while its batchmate runs to the iteration limit, each
        reporting its own count, status and true final residual.
        Configurations whose executable shape is not RHS-batchable —
        distributed operators, mixed-precision refinement below the
        dtype floor, device-pinned host-mode packs — fall back to
        sequential :meth:`solve` calls with identical per-request
        results.

        ``pad_to_bucket`` (the serving micro-batcher's mode): pad the
        batch axis to the next power of two with zero RHS so a stream
        of ragged batch sizes compiles at most log2(max) executables —
        pad lanes converge at iteration 0, are excluded from telemetry,
        and only the k live results are returned."""
        if self.Ad is None:
            raise BadConfigurationError("solve_multi() before setup()")
        B = [B[i] for i in range(B.shape[0])] \
            if isinstance(B, (np.ndarray, jax.Array)) and np.ndim(B) == 2 \
            else list(B)
        k = len(B)
        if k == 0:
            return []
        dtype = self.Ad.dtype
        dist = self.Ad.fmt == "sharded-ell"
        fault = self._sync_fault_trace()
        refine, wide, structural = self._promotion_plan()
        self._check_tolerance_floor(refine, structural)
        # the bf16 → f32 promotion rung is BATCHABLE: the refined outer
        # loop vmaps like the plain solve body (f32 is TPU-native); the
        # f32 → f64 rung keeps the sequential fallback — emulated-f64
        # SpMVs under vmap blow past sane executable sizes
        refined_batch = (refine and wide == np.dtype(np.float32)
                         and not dist)
        pin = None
        if not dist:
            try:
                devs = list(self.Ad.diag.devices())
                if len(devs) == 1 and devs[0] != jax.devices()[0]:
                    pin = devs[0]
            except Exception:
                pin = None
        # device-pinned packs ride the batched path too (the multi-lane
        # serving layer pins one executor lane per device — losing
        # micro-batching there would cap every non-default lane at
        # single-RHS throughput); only the refinement ladder keeps its
        # sequential fallback under a pin
        if k == 1 or dist or (refine and not refined_batch) \
                or (refine and pin is not None):
            # sequential fallback: recovery stays OFF here so
            # solve_multi behaves uniformly across batch sizes — the
            # serving layer executes everything through this API, and
            # a ladder engaging only when a request happened to batch
            # alone would multiply that batch's deadline by the
            # attempt count (recovery.maybe_recover's scope contract)
            suppress = not getattr(self, "_suppress_recovery", False)
            if suppress:
                self._suppress_recovery = True
            try:
                out = []
                for j, bj in enumerate(B):
                    xj = None if X0 is None else X0[j]
                    out.append(self.solve(bj, x0=xj,
                                          zero_initial_guess=
                                          zero_initial_guess))
                return out
            finally:
                if suppress:
                    self._suppress_recovery = False

        Bm = np.stack([np.asarray(bj).ravel() for bj in B])
        if self.scaler is not None:
            Bm = np.stack([self.scaler.scale_rhs(r.astype(dtype))
                           for r in Bm])
        X0m = None
        if X0 is not None and not zero_initial_guess:
            X0m = np.stack([np.asarray(x).ravel() for x in X0])
            if self.scaler is not None:
                X0m = np.stack([self.scaler.scale_initial_guess(
                    r.astype(dtype)) for r in X0m])
        if self._reorder is not None:
            perm, _ = self._reorder
            Bm = Bm[:, perm]
            if X0m is not None:
                X0m = X0m[:, perm]
        if pad_to_bucket:
            bucket = 1
            while bucket < k:
                bucket <<= 1
            if bucket > k:
                Bm = np.concatenate(
                    [Bm, np.zeros((bucket - k, Bm.shape[1]), Bm.dtype)])
                if X0m is not None:
                    X0m = np.concatenate(
                        [X0m, np.zeros((bucket - k, X0m.shape[1]),
                                       X0m.dtype)])
        t0 = time.perf_counter()
        with telemetry.span("solve_multi", solver=self.config_name,
                            scope=self.scope, batch=k,
                            refined=bool(refined_batch)), \
                cpu_profiler(f"solve_multi:{self.config_name}"):
            if refined_batch:
                X, stats, history = self._solve_multi_refined_call(
                    Bm, X0m, wide)
            else:
                import contextlib
                # pinned packs: the batch arrays and scalar operands
                # are created INSIDE the pin context so the jitted
                # call never sees a mixed device set (same contract as
                # the single-RHS pin path above)
                ctx = jax.default_device(pin) if pin is not None \
                    else contextlib.nullcontext()
                with ctx:
                    Bd = jnp.asarray(Bm, dtype)
                    X0d = jnp.zeros_like(Bd) if X0m is None \
                        else jnp.asarray(X0m, dtype)
                    if self._solve_multi is None:
                        from ._bind import DeviceBindings, bind_for_trace
                        if self._bindings is None:
                            self._bindings = DeviceBindings(self)
                            self._ml_register_bindings()
                        bindings = self._bindings
                        vm = jax.vmap(self._packed_solve_fn(),
                                      in_axes=(0, 0, None, None))
                        self._solve_multi = (
                            bindings,
                            jax.jit(bind_for_trace(bindings, vm)))
                    bindings, fn = self._solve_multi
                    rdt = np.zeros((), dtype).real.dtype
                    call_args = (bindings.collect(), Bd, X0d,
                                 jnp.asarray(self.tolerance, rdt),
                                 jnp.asarray(self.max_iters, jnp.int32))
                    # warm-start layer: each batch bucket (Bd's leading
                    # dim) is its own AOT executable — the serving
                    # micro-batcher's power-of-two padding keeps that
                    # set log2(max_batch)-sized; pinned lanes key by
                    # device (see _maybe_aot)
                    X, stats, history = self._maybe_aot(
                        "solve_multi", fn, call_args,
                        device=pin)(*call_args)
            stats = np.asarray(stats)      # ONE host fetch: (k, 3+2m)
        solve_time = time.perf_counter() - t0
        if fault is not None and \
                (bool((stats[:, 1] != 0).any()) if self.monitor_residual
                 else int(stats[:, 0].max()) > int(fault[1])):
            # provoked iff ANY lane flagged the breakdown (monitored) /
            # reached the target iteration (unmonitored)
            faultinject.fired(fault[0], iteration=fault[1], batch=k)
        Xh = None
        if self._reorder is not None or self.scaler is not None:
            Xh = np.asarray(X)
        hist_all = None
        if self.store_res_history or self.print_solve_stats \
                or self.convergence in ("RELATIVE_MAX",
                                        "RELATIVE_MAX_CORE"):
            # RELATIVE_MAX needs the monitored trajectory for the true
            # running max even when the caller didn't ask to keep it —
            # same as solve()'s nrm_max recovery
            hist_all = np.asarray(history)

        results = []
        m = (stats.shape[1] - 3) // 2
        for j in range(k):
            iters, brk_code, first_bad, nrm, nrm_ini = \
                self._decode_stats(stats[j])
            nrm = np.atleast_1d(nrm)
            nrm_ini = np.atleast_1d(nrm_ini)
            failure = None
            if Xh is not None:
                xj = Xh[j]
                if self._reorder is not None:
                    xj = xj[self._reorder[1]]
                if self.scaler is not None:
                    xj = self.scaler.unscale_solution(np.asarray(xj))
            else:
                xj = X[j]
            history_np = None
            if hist_all is not None:
                history_np = np.atleast_2d(hist_all[j])[:iters + 1]
            if self.monitor_residual:
                nrm_max = nrm_ini
                if self.convergence in ("RELATIVE_MAX",
                                        "RELATIVE_MAX_CORE") \
                        and history_np is not None:
                    h = self._finite_history(history_np,
                                             context=f"nrm_max[{j}]") \
                        if history_np.size else history_np
                    if h.size:
                        nrm_max = np.maximum(nrm_ini, h.max(axis=0))
                conv = bool(np.all(self._host_converged(nrm, nrm_ini,
                                                        nrm_max)))
                diverged = bool(np.any(~np.isfinite(nrm)))
                status = (SolveStatus.SUCCESS if conv else
                          (SolveStatus.DIVERGED if diverged else
                           (SolveStatus.FAILED if brk_code
                            else SolveStatus.NOT_CONVERGED)))
                failure = self._classify_failure(conv, diverged,
                                                 brk_code, first_bad,
                                                 nrm, iters)
            else:
                status = SolveStatus.SUCCESS
            if telemetry.is_enabled():
                label = ("SUCCESS" if status == SolveStatus.SUCCESS
                         else ("DIVERGED"
                               if bool(np.any(~np.isfinite(nrm)))
                               else "NOT_CONVERGED"))
                telemetry.counter_inc("amgx_solves_total", status=label)
                if failure is not None:
                    # the serving layer executes everything through this
                    # path — production breakdowns must land in the same
                    # taxonomy counter/event the single-RHS path emits
                    telemetry.counter_inc("amgx_solve_failures_total",
                                          kind=failure.kind.value)
                    telemetry.event("breakdown",
                                    solver=self.config_name,
                                    kind=failure.kind.value,
                                    iteration=failure.iteration,
                                    batch_lane=j)
            results.append(SolveResult(
                x=xj, iterations=iters, status=status,
                residual_norm=nrm,
                # history is RETURNED only on request (solve() parity);
                # a RELATIVE_MAX fetch above serves the status math only
                residual_history=(history_np
                                  if self.store_res_history
                                  or self.print_solve_stats else None),
                setup_time=self.setup_time, solve_time=solve_time,
                failure=failure))
        if telemetry.is_enabled():
            if self.forensics:
                # drain in-flight forensics callbacks (see
                # _emit_solve_telemetry) before the flush below
                try:
                    jax.effects_barrier()
                except Exception:
                    pass
            telemetry.hist_observe("amgx_solve_seconds", solve_time)
            telemetry.gauge_set("amgx_last_solve_seconds", solve_time)
            if self.telemetry_path:
                telemetry.flush_jsonl(self.telemetry_path)
        return results

    def _solve_multi_refined_call(self, Bm, X0m, wide):
        """The batched bf16 → f32 promotion rung: the refined outer
        loop (``_build_refined_fn``) vmapped over the RHS axis — each
        lane runs its own defect-correction ladder with per-lane
        convergence, so a narrow-pack multi-RHS batch stays one
        executable instead of falling back to sequential solves (the
        f64 rung keeps that fallback; see ``solve_multi``)."""
        dtype = self.Ad.dtype
        wide = np.dtype(wide)
        had_refine = hasattr(self, "_refine_lo")
        self._ensure_refine_data()
        if self._solve_multi_refined is None \
                or self._solve_multi_refined[0] != wide:
            from ._bind import DeviceBindings, bind_for_trace
            if self._bindings is None or not had_refine:
                # fresh bindings so the refine residue (when present)
                # rides as a bound argument, never a trace constant —
                # executables closing over the OLD bindings object must
                # re-bind.  Bindings that already cover the refine data
                # are REUSED: replacing them here would invalidate
                # _solve_fn, whose next call would invalidate this
                # executable right back — a retrace ping-pong for
                # workloads alternating single- and multi-RHS solves
                self._bindings = DeviceBindings(self)
                self._ml_register_bindings()
                self._solve_fn = None
                self._refined_fn = None
                self._solve_multi = None
            vm = jax.vmap(self._build_refined_fn(wide),
                          in_axes=(0, 0, 0, 0, None, None))
            self._solve_multi_refined = (
                wide, self._bindings,
                jax.jit(bind_for_trace(self._bindings, vm)))
        _, bindings, fn = self._solve_multi_refined
        lo_dt = np.float32
        B64 = Bm.astype(np.float64, copy=False)
        Bhi = B64.astype(dtype)
        Blo = (B64 - Bhi.astype(np.float64)).astype(lo_dt)
        if X0m is None:
            Xhi = np.zeros_like(Bhi)
            Xlo = np.zeros(Bhi.shape, dtype=lo_dt)
        else:
            X64 = X0m.astype(np.float64, copy=False)
            Xhi = X64.astype(dtype)
            Xlo = (X64 - Xhi.astype(np.float64)).astype(lo_dt)
        wdt = jnp.dtype(wide.name)
        call_args = (bindings.collect(), jnp.asarray(Bhi),
                     jnp.asarray(Blo), jnp.asarray(Xhi),
                     jnp.asarray(Xlo),
                     jnp.asarray(self.tolerance, wdt),
                     jnp.asarray(self.max_iters, jnp.int32))
        # the warm-start layer covers the refined batches too: without
        # it a restarted mixed-precision serving process would pay the
        # full trace+compile on the first batch of every bucket size
        return self._maybe_aot("solve_multi_refined", fn,
                               call_args)(*call_args)

    def _emit_solve_telemetry(self, iters, nrm, nrm_ini, status,
                              history, solve_time, failure=None):
        """Per-solve telemetry: phase duration, iteration count, final
        relative residual, convergence-rate estimate, divergence event
        and the per-iteration residual trajectory (iteration 0 = the
        initial residual, matching ``AMGX_solver_get_iteration_residual``
        indexing)."""
        if self.forensics:
            # cycle-anatomy events arrive through unordered
            # jax.debug.callback: on an async backend they may still be
            # in flight when the solve returns — drain them before the
            # trace is scanned/flushed (else the doctor undercounts
            # cycles and a capture scope closing would drop them)
            try:
                jax.effects_barrier()
            except Exception:
                pass
        telemetry.hist_observe("amgx_solve_seconds", solve_time)
        telemetry.gauge_set("amgx_last_solve_seconds", solve_time)
        telemetry.gauge_set("amgx_solve_iterations", iters)
        # NOT_CONVERGED aliases DIVERGED in the reference enum (both 2);
        # distinguish by the non-finite check the status was derived from
        diverged = bool(np.any(~np.isfinite(np.asarray(nrm))))
        label = ("SUCCESS" if status == SolveStatus.SUCCESS else
                 ("DIVERGED" if diverged else "NOT_CONVERGED"))
        telemetry.counter_inc("amgx_solves_total", status=label)
        if self.monitor_residual:
            nrm_m = float(np.max(nrm))
            ini_m = float(np.max(nrm_ini))
            relres = nrm_m / ini_m if ini_m > 0 else nrm_m
            telemetry.gauge_set("amgx_solve_final_relres", relres)
            if iters > 0 and np.isfinite(relres) and relres > 0:
                telemetry.gauge_set("amgx_solve_convergence_rate",
                                    relres ** (1.0 / iters))
            if self.forensics and history is not None:
                # asymptotic convergence factor: trailing-half estimate
                # (telemetry/forensics.py) — the number that predicts
                # iteration growth, vs the whole-solve geometric mean
                # above which the fast early iterations flatter
                from ..telemetry import forensics
                rate = forensics.asymptotic_rate(
                    [float(np.max(row))
                     for row in np.atleast_2d(history)])
                if rate is not None:
                    telemetry.gauge_set(
                        "amgx_forensics_asymptotic_rate", rate)
                    telemetry.event("solve_forensics",
                                    solver=self.config_name,
                                    iterations=iters,
                                    asymptotic_rate=rate)
            if diverged:
                telemetry.counter_inc("amgx_solve_diverged_total")
                telemetry.event("divergence", solver=self.config_name,
                                iteration=iters, norm=nrm_m)
            if failure is not None:
                # the taxonomy-kinded failure record (errors.FailureKind)
                # — what the doctor's "failures & recovery" section and
                # the recovery ladder's audit key on
                telemetry.counter_inc("amgx_solve_failures_total",
                                      kind=failure.kind.value)
                telemetry.event("breakdown", solver=self.config_name,
                                kind=failure.kind.value,
                                iteration=failure.iteration)
            if history is not None:
                for i, row in enumerate(np.atleast_2d(history)):
                    telemetry.event("residual", iteration=i,
                                    norm=float(np.max(row)))
        self._emit_krylov_comm_telemetry(iters)
        if self.telemetry_path:
            telemetry.flush_jsonl(self.telemetry_path)

    def _emit_krylov_comm_telemetry(self, iters: int):
        """Per-solve communication accounting: the trace-time reduction
        profile (ops/blas.py ledger) scaled by executed iterations.  The
        counters are the measured truth ISSUE 16 gates on; the event
        additionally carries the modelled SpMV-vs-reduction split the
        doctor's latency-bound hint keys on.  Silent when the loop body
        was never traced this session (pure AOT-load path)."""
        led = getattr(self, "_collective_ledger", None)
        if led is None or not led.counts:
            return
        prof = {op: int(c) for op, c in led.counts.items()}
        iters = max(int(iters), 0)
        for op, c in prof.items():
            if iters > 0:
                telemetry.counter_inc("amgx_krylov_collectives_total",
                                      float(c * iters), op=op)
        rep = int(getattr(self, "ca_residual_replace", 0) or 0)
        n_rep = (iters - 1) // rep if (rep > 0 and iters > 1
                                       and led.replace) else 0
        if n_rep > 0:
            telemetry.counter_inc(
                "amgx_krylov_collectives_total",
                float(sum(led.replace.values()) * n_rep), op="replace")
        mode = (self._comm_mode() if hasattr(self, "_comm_mode")
                else "CLASSIC")
        ev = {
            "solver": self.config_name,
            "mode": mode,
            "iterations": iters,
            "per_iter": prof,
            "collectives_per_iter": int(sum(prof.values())),
            "fused": bool("fused" in prof),
        }
        model = telemetry.costmodel.krylov_reduction_cost(
            self.Ad, ev["collectives_per_iter"]) \
            if self.Ad is not None else None
        if model is not None:
            ev.update(model)
        else:
            ev["n_parts"] = int(getattr(self.Ad, "n_parts", 1) or 1) \
                if self.Ad is not None else 1
        telemetry.event("krylov_comm", **ev)

    def _host_norm(self, v: np.ndarray):
        """Numpy twin of ops.blas.norm — outer refinement norms must match
        the configured norm type/blocking, computed on host (device ops
        here would add a transfer every outer pass)."""
        nt, bd = self.norm_type, self.Ad.block_dim
        if self.use_scalar_norm or bd == 1:
            if nt in ("L1", "L1_SCALED"):
                r = np.sum(np.abs(v))
                return r / v.shape[0] if nt == "L1_SCALED" else r
            if nt == "LMAX":
                return np.max(np.abs(v))
            return np.linalg.norm(v)
        vb = v.reshape(-1, bd)
        if nt in ("L1", "L1_SCALED"):
            r = np.sum(np.abs(vb), axis=0)
            return r / vb.shape[0] if nt == "L1_SCALED" else r
        if nt == "LMAX":
            return np.max(np.abs(vb), axis=0)
        return np.sqrt(np.sum(np.abs(vb) ** 2, axis=0))

    def _ensure_refine_data(self):
        """Device data for on-device refinement: the rounding residue
        ``lo = vals_w − w(pack(vals_w))`` of the device pack vs the wide
        host matrix, so the traced wide SpMV can reconstruct the exact
        wide operator as ``vals.astype(w) + lo``.  ``lo`` is stored in
        f32 whatever the pack dtype (it exactly carries an f32 pack's
        f64 residue AND a bf16 pack's f32 residue), and is None —
        no extra upload — for integer-valued stencils (Poisson), which
        are exactly representable in the pack dtype."""
        if hasattr(self, "_refine_lo"):
            return
        pdt = np.dtype(self.Ad.dtype)
        if pdt == np.float32 and getattr(self.A, "_vals_f32_exact",
                                         False):
            # device-generated integer-valued stencils declare f32
            # exactness analytically — no host values to scan (a bf16
            # pack still scans: the hint promises f32, not bf16)
            self._refine_lo = None
            return
        # a pack produced by an ON-DEVICE cast holds pdt(via(v)), not
        # pdt(v) — one extra rounding (precision_view records the
        # chain); the residue must model the pack's ACTUAL values or
        # hi+lo reconstructs a subtly wrong wide operator and the
        # refined loop's "true" residual stops being true
        via = getattr(self.A, "_pack_cast_via", None) \
            if self.A is not None else None

        def to_pack(c):
            return (c.astype(via) if via is not None else c).astype(pdt)

        if self.Ad.fmt == "sharded-ell":
            self._refine_lo = self._sharded_refine_lo(to_pack)
            return
        vals64 = self._host_pack_vals64()
        # chunked exactness scan with early exit: integer-valued stencils
        # (the common benchmark operators) are exactly representable in
        # the narrow dtype, and detecting that must not cost four full
        # passes over a ~1 GB fine-level array
        flat = vals64.reshape(-1)
        exact = True
        step = 1 << 22
        for s in range(0, flat.size, step):
            c = flat[s:s + step]
            if not np.array_equal(to_pack(c).astype(np.float64), c):
                exact = False
                break
        if exact:
            self._refine_lo = None
            return
        lo = (vals64 - to_pack(vals64).astype(np.float64)) \
            .astype(np.float32)
        self._refine_lo = jnp.asarray(lo)

    def _sharded_refine_lo(self, to_pack):
        """``_refine_lo`` of a row-partitioned pack: None when every
        host value is exact in the pack dtype (scanned on the host
        values, no layout needed), else the residue in the pack's own
        (P, n_loc, K) layout — rebuilt by the same packer at f64 —
        placed like the pack's ``vals``."""
        for blk in self.A.host_row_blocks():
            d = np.asarray(blk.data, dtype=np.float64)
            if not np.array_equal(to_pack(d).astype(np.float64), d):
                break
        else:
            return None
        v64 = np.asarray(self.A.sharded_pack(np.float64).vals)
        lo = (v64 - to_pack(v64).astype(np.float64)).astype(np.float32)
        return jax.device_put(lo, self.Ad.vals.sharding)

    def _host_pack_vals64(self) -> np.ndarray:
        """The device pack's ``vals`` layout rebuilt on host in f64
        (must mirror ``core.matrix.pack_device`` exactly)."""
        Ad = self.Ad
        import scipy.sparse as sp
        from ..core.matrix import dia_arrays, ell_layout
        if Ad.fmt == "dia":
            if Ad.block_dim > 1:
                # block-DIA planes: rebuild (nd, n, b, b) from the BSR
                from ..core.matrix import dia_arrays_block
                b = Ad.block_dim
                bsr = self.A.host if isinstance(self.A.host,
                                                sp.bsr_matrix) else \
                    sp.bsr_matrix(self.A.host, blocksize=(b, b))
                bsr.sort_indices()
                offs, bvals = dia_arrays_block(bsr)
                assert tuple(offs) == tuple(Ad.dia_offsets)
                return bvals.astype(np.float64, copy=False)
            # dia_cache first: for DIA-backed matrices (device-generated
            # operators included) this never assembles the host CSR
            arrs = self.A.dia_cache() if isinstance(self.A, Matrix) \
                else None
            offs, vals = arrs if arrs is not None else \
                dia_arrays(sp.csr_matrix(self.A.host))
            assert tuple(offs) == tuple(Ad.dia_offsets)
            return vals.astype(np.float64, copy=False)
        host = self.A.host
        if Ad.fmt == "dense":
            return np.asarray(sp.csr_matrix(host).todense(),
                              dtype=np.float64)
        b = Ad.block_dim
        if b == 1:
            csr = sp.csr_matrix(host)
            csr.sort_indices()
            indptr, indices, data = csr.indptr, csr.indices, csr.data
            block_shape = ()
        else:
            bsr = host if isinstance(host, sp.bsr_matrix) else \
                sp.bsr_matrix(host, blocksize=(b, b))
            bsr.sort_indices()
            indptr, indices, data = bsr.indptr, bsr.indices, bsr.data
            block_shape = (b, b)
        if Ad.fmt == "csr":
            return data.astype(np.float64)
        for_rows, pos, k = ell_layout(indptr, indices)
        assert k == Ad.ell_width
        out = np.zeros((Ad.n_rows, k) + block_shape, dtype=np.float64)
        out[for_rows, pos] = data
        return out

    def _wide_pack(self, wide=np.float64):
        """The traced wide device pack of the exact host operator
        (``wide`` is the promotion rung: f64 for an f32 pack, f32 for a
        bf16 pack)."""
        Ad64 = self.Ad
        wdt = jnp.dtype(np.dtype(wide).name)
        if Ad64.fmt == "sharded-ell":
            # the interior takes the gather path: the windowed kernel
            # is f32-only
            vals = Ad64.vals.astype(wdt)
            if self._refine_lo is not None:
                vals = vals + self._refine_lo.astype(wdt)
            return dataclasses.replace(
                Ad64, vals=vals, diag=Ad64.diag.astype(wdt),
                win_blocks=None, win_codes=None, win_vals=None)
        if Ad64.fmt == "ell" and Ad64.vals is None:
            # lean windowed pack: the f64 path needs the gather-form
            # arrays — rebuild them as traced views (f64 never takes the
            # f32-only window kernel)
            Ad64 = dataclasses.replace(
                Ad64, vals=Ad64.ell_vals_view(), cols=Ad64.ell_cols_view(),
                win_blocks=None, win_codes=None, win_vals=None)
        if Ad64.bn_codes is not None and Ad64.vals is not None:
            # the wide pack must dispatch on the CORRECTED gather-form
            # vals: under the interpreter the binned kernel serves f64
            # too and would read the UN-corrected bn_vals planes,
            # silently dropping the _refine_lo residue the refinement
            # residual exists for
            Ad64 = dataclasses.replace(
                Ad64, bn_codes=None, bn_vals=None, bn_meta=None,
                bn_pos=None, bn_dims=())
        Ad64 = Ad64.astype(wdt)
        if self._refine_lo is not None:
            Ad64 = dataclasses.replace(
                Ad64, vals=Ad64.vals + self._refine_lo.astype(wdt))
        return Ad64

    def _spmv_wide(self, x64, Ad64=None, wide=np.float64):
        """Traced wide SpMV of the exact host operator (XLA emulates f64
        on TPU — slower than f32 but bit-honest, which is all the
        refinement residual needs; the bf16 → f32 rung runs native).
        Pass a precomputed ``Ad64`` when calling inside a loop: XLA does
        not reliably hoist the ~2×vals widening out of ``while`` bodies,
        and at 256³ that is ~1 GB of rematerialisation per refinement
        pass."""
        return spmv(self._wide_pack(wide) if Ad64 is None else Ad64, x64)

    def _solve_refined(self, b, x0, wide=np.float64):
        """Mixed-precision iterative refinement, entirely on device:
        inner solves run in the pack dtype, true residuals are
        recomputed at the ``wide`` promotion rung (f64 is XLA-emulated
        on TPU; the bf16 → f32 rung runs native) inside the same
        executable, and the outer correction loop is a
        ``lax.while_loop`` — ONE host round trip per solve instead of
        vector transfers every outer pass.  The dDFI
        analog of the reference's mixed modes
        (``amgx_config.h:114-123``).  ``b``/``x0`` arrive in the
        CALLER's precision, never pre-rounded to the device dtype."""
        from ._bind import bind_for_trace
        dtype = self.Ad.dtype
        wide = np.dtype(wide)
        wdt = jnp.dtype(wide.name)
        # the residue plane always rides f32: it must carry digits the
        # pack dtype cannot (a bf16 lo would forfeit the promotion)
        lo_dt = jnp.float32 if jnp.dtype(dtype).itemsize < 4 else dtype

        if self.Ad.fmt == "sharded-ell":
            from ..distributed.matrix import shard_vector

            def put(v):
                return shard_vector(self.Ad, v)
        else:
            put = jnp.asarray

        def split(v):
            """Caller-precision vector → device-dtype (hi, lo residue)."""
            if isinstance(v, jax.Array) and v.dtype == dtype:
                return v, None          # device-resident input: exact
            v64 = np.asarray(v, dtype=np.float64).ravel()
            hi = v64.astype(dtype)
            lo = (v64 - hi.astype(np.float64)).astype(lo_dt)
            return put(hi), (put(lo) if np.any(lo) else None)

        b_hi, b_lo = split(b)
        x_hi = x_lo = None
        if x0 is not None:
            x_hi, x_lo = split(x0)
        if self._refined_fn is None or self._refined_fn[0] != wide:
            self._refined_fn = (wide, jax.jit(bind_for_trace(
                self._bindings, self._build_refined_fn(wide))))
        x64, stats, history = self._refined_fn[1](
            self._bindings.collect(), b_hi, b_lo, x_hi, x_lo,
            jnp.asarray(self.tolerance, wdt),
            jnp.asarray(self.max_iters, jnp.int32))
        # ONE small host fetch; same wire layout as _packed_solve_fn
        iters, brk_code, first_bad, nrm, nrm_ini = \
            self._decode_stats(np.asarray(stats))
        # keep the wide-precision device solution: rounding x back to the
        # device dtype would throw away the digits refinement bought
        return x64, iters, brk_code, first_bad, nrm, nrm_ini, history

    def _build_refined_fn(self, wide=np.float64) -> Callable:
        body = self._build_solve_fn()
        dtype = self.Ad.dtype
        crit, alt_tol = self.convergence, self.alt_rel_tolerance
        inner_tol = max(self.tolerance, 2.0 * self._tolerance_floor(dtype))
        max_iters = self.max_iters
        # each outer pass reduces the wide residual by roughly the
        # inner tolerance; a bf16 inner floor (~0.4 per pass) needs far
        # more rungs to reach its f32 target than the f32 → f64 case's
        # historical 8 — size the budget from the reduction per pass
        import math
        if 0.0 < inner_tol < 1.0:
            need = math.log(max(self.tolerance, 1e-300)) \
                / math.log(inner_tol)
            max_outer = int(min(64, max(8, math.ceil(need) + 4)))
        else:
            max_outer = 8
        keep_history = self.store_res_history or self.print_solve_stats
        f64 = jnp.dtype(np.dtype(wide).name)    # the promotion rung
        tiny = float(np.finfo(np.dtype(wide)).tiny)
        # the history buffer floors at f32: a bf16 pack's residual
        # trajectory spans magnitudes bf16 cannot represent
        hist_dt = jnp.float32 if jnp.dtype(dtype).itemsize < 4 else dtype

        def norm64(r):
            return jnp.atleast_1d(blas.norm(r, self.norm_type,
                                            self.Ad.block_dim,
                                            self.use_scalar_norm))

        def widen(hi, lo):
            w = hi.astype(f64)
            return w if lo is None else w + lo.astype(f64)

        def refined_fn(b_hi, b_lo, x_hi, x_lo, tol, it_limit):
            # the wide residuals, their norms and the corrections run
            # under the krylov/refine scope; the inner solve carries its
            # own stages
            with _refine_scope():
                # widen the operator ONCE, outside the while body (see
                # _spmv_wide: XLA won't hoist the ~2×vals
                # materialisation)
                Ad64 = self._wide_pack(wide)
                b64 = widen(b_hi, b_lo)
                x64 = jnp.zeros_like(b64) if x_hi is None \
                    else widen(x_hi, x_lo)
                r64 = b64 - self._spmv_wide(x64, Ad64, wide)
                nrm_ini = norm64(r64)
                m = nrm_ini.shape[0]
                hist = jnp.zeros((max_iters + 1, m), hist_dt)
                hist = hist.at[0].set(nrm_ini.astype(hist_dt))
                done0 = check_convergence(crit, nrm_ini, nrm_ini, nrm_ini,
                                          tol, alt_tol)

            def cond(c):
                _x, _r, it_tot, _n, done, _h, k, _f = c
                return (~done) & (it_tot < it_limit) & (k < max_outer)

            def outer(c):
                x64, r64, it_tot, _nrm, _done, hist, k, fail = c
                with _refine_scope():
                    scale = jnp.maximum(jnp.max(jnp.abs(r64)),
                                        jnp.asarray(tiny, f64))
                    rb = (r64 / scale).astype(dtype)
                dx, it, _, _, h_in, f_in = body(
                    rb, jnp.zeros_like(rb),
                    jnp.asarray(inner_tol, dtype), it_limit - it_tot)
                with _refine_scope():
                    # the FIRST inner breakdown wins; its first-bad
                    # iteration re-bases onto the global iteration count
                    new = (fail[0] == 0) & (f_in[0] != 0)
                    fail = jnp.where(
                        new, jnp.stack([f_in[0], it_tot + f_in[1]]), fail)
                    x64n = x64 + scale * dx.astype(f64)
                    r64n = b64 - self._spmv_wide(x64n, Ad64, wide)
                    nrm_n = norm64(r64n)
                    if keep_history:
                        # place h_in rows 1..it (scaled) at hist rows
                        # it_tot+1 .. it_tot+it
                        rows = jnp.arange(max_iters + 1)[:, None]
                        src = rows - it_tot
                        take = jnp.broadcast_to(
                            jnp.clip(src, 0, max_iters), (max_iters + 1, m))
                        cand = jnp.take_along_axis(h_in, take, axis=0) \
                            .astype(hist_dt)
                        mask = (src >= 1) & (src <= it)
                        hist = jnp.where(mask, cand * scale.astype(hist_dt),
                                         hist)
                    done_n = check_convergence(crit, nrm_n, nrm_ini, nrm_ini,
                                               tol, alt_tol) \
                        | ~jnp.all(jnp.isfinite(nrm_n)) \
                        | (fail[0] != 0)
                    return (x64n, r64n, it_tot + it, nrm_n, done_n, hist,
                            k + jnp.asarray(1, jnp.int32), fail)

            fail0 = jnp.stack([jnp.asarray(0, jnp.int32),
                               jnp.asarray(-1, jnp.int32)])
            carry = (x64, r64, jnp.asarray(0, jnp.int32), nrm_ini, done0,
                     hist, jnp.asarray(0, jnp.int32), fail0)
            x64, r64, it_tot, nrm, done, hist, k, fail = \
                jax.lax.while_loop(cond, outer, carry)
            stats = jnp.concatenate([it_tot[None].astype(f64),
                                     fail.astype(f64), nrm, nrm_ini])
            return x64, stats, hist

        return refined_fn

    def _host_converged(self, nrm, nrm_ini, nrm_max=None):
        crit = self.convergence
        tol = self.tolerance
        if crit == "ABSOLUTE":
            return nrm <= tol
        if crit in ("RELATIVE_INI", "RELATIVE_INI_CORE"):
            return nrm <= tol * nrm_ini
        if crit in ("RELATIVE_MAX", "RELATIVE_MAX_CORE"):
            return nrm <= tol * (nrm_ini if nrm_max is None else nrm_max)
        if crit == "COMBINED_REL_INI_ABS":
            return (nrm <= tol) | (nrm <= self.alt_rel_tolerance * nrm_ini)
        return nrm <= tol

    def _print_solve_stats(self, history, iters, status):
        if history is None:
            return
        lines = ["           iter      Mem Usage (GB)       residual      "
                 "rate\n",
                 "         --------------------------------------------------"
                 "------------\n"]
        prev = None
        for i, h in enumerate(history):
            hval = float(np.max(h))
            rate = "" if prev in (None, 0.0) else f"{hval / prev:9.4f}"
            label = "Ini" if i == 0 else f"{i - 1:4d}"
            lines.append(f"        {label}              -         "
                         f"{hval:15.6e}  {rate}\n")
            prev = hval
        lines.append("         ----------------------------------------------"
                     "----------------\n")
        lines.append(f"        Total Iterations: {iters}\n")
        amgx_output("".join(lines))

    # ------------------------------------------------------- the jitted loop
    def _build_solve_fn(self) -> Callable:
        monitor = self.monitor_residual
        keep_history = self.store_res_history or self.print_solve_stats
        max_iters = self.max_iters
        crit = self.convergence
        alt_tol = self.alt_rel_tolerance
        # traced fault injection (utils/faultinject.py): None — the
        # default — adds NOTHING to the jaxpr; an armed values_nan /
        # krylov_zero point mutates the iteration state at one target
        # iteration (solve() invalidates this body on arming changes)
        fault = getattr(self, "_fault_trace", None)
        ledger = self._collective_ledger

        def solve_fn(b, x0, tol, it_limit):
            with _refine_scope():
                r0 = b - spmv(self.Ad, x0)
                nrm_ini = blas.norm(r0, self.norm_type, self.Ad.block_dim,
                                    self.use_scalar_norm)
                nrm_ini = jnp.atleast_1d(nrm_ini)
                history = jnp.zeros((max_iters + 1,) + nrm_ini.shape,
                                    dtype=nrm_ini.dtype)
                history = history.at[0].set(nrm_ini)
            state0 = self.solve_init(b, x0)

            def cond(carry):
                x, state, it, nrm, nmax, done, brk, bad_it, hist = carry
                return (~done) & (it < jnp.minimum(it_limit, max_iters))

            def body(carry):
                x, state, it, nrm, nmax, done, brk, bad_it, hist = carry
                # collective ledger: this body traces ONCE per compile,
                # so resetting here and counting through the iteration +
                # monitor estimate yields the steady-state per-iteration
                # reduction profile (host-side; adds nothing to the jaxpr)
                ledger.reset()
                with blas.count_collectives(ledger):
                    x, state = self.solve_iteration(b, x, state, it)
                    if fault is not None:
                        x, state = _inject_fault(fault, it, x, state)
                    est = None
                    if monitor:
                        est = self.residual_norm_estimate(b, x, state)
                        if est is None:
                            est = self.compute_residual_norm(b, x)
                if monitor:
                    nrm = jnp.atleast_1d(est)
                    # device-side breakdown flag: the solver's in-loop
                    # guards (CG pAp/rho) carry a code in their state;
                    # a flagged loop stops at THIS iteration instead of
                    # burning the remaining budget, and the first-bad
                    # iteration rides out in the packed stats.  The
                    # KRYLOV code is provisional (collapsed scalars
                    # also mean ordinary convergence) — it only sticks
                    # while the monitored residual is alive, which the
                    # carried norm already knows for free
                    code = self.breakdown_code(state)
                    if code is not None:
                        alive = jnp.any(nrm > 0)
                        code = jnp.where(
                            (code == BREAKDOWN_KRYLOV) & ~alive,
                            0, code)
                        hit = (brk == 0) & (code != 0)
                        brk = jnp.where(hit, code, brk)
                        bad_it = jnp.where(hit, it + 1, bad_it)
                    nmax = jnp.maximum(nmax, nrm)
                    done = check_convergence(crit, nrm, nrm_ini, nmax,
                                             tol, alt_tol)
                    bad = ~jnp.all(jnp.isfinite(nrm))
                    hit = (brk == 0) & bad
                    brk = jnp.where(
                        hit, jnp.where(jnp.any(jnp.isnan(nrm)),
                                       BREAKDOWN_NAN,
                                       BREAKDOWN_DIVERGENCE), brk)
                    bad_it = jnp.where(hit, it + 1, bad_it)
                    done = done | bad | (brk != 0)
                if keep_history:
                    hist = hist.at[it + 1].set(nrm)
                return (x, state, it + 1, nrm, nmax, done, brk, bad_it,
                        hist)

            done0 = jnp.asarray(False)
            if monitor:
                done0 = check_convergence(crit, nrm_ini, nrm_ini, nrm_ini,
                                          tol, alt_tol)
            carry = (x0, state0, jnp.asarray(0, jnp.int32), nrm_ini,
                     nrm_ini, done0, jnp.asarray(0, jnp.int32),
                     jnp.asarray(-1, jnp.int32), history)
            (x, state, it, nrm, nmax, done, brk, bad_it, history) = \
                jax.lax.while_loop(cond, body, carry)
            x = self.solve_finalize(b, x, state)
            if monitor:
                # the declared norm is a freshly computed TRUE residual —
                # in-loop estimates (quasi-residual, CG recursion) only
                # steer the loop (reference solver.cu:776-805)
                with _refine_scope():
                    nrm = jnp.atleast_1d(self.compute_residual_norm(b, x))
            fail = jnp.stack([brk, bad_it])
            return x, it, nrm, nrm_ini, history, fail

        return solve_fn

    def breakdown_code(self, state) -> Optional[jax.Array]:
        """Traced int32 breakdown code the solver's iteration state
        carries (``errors.BREAKDOWN_*``; 0 = healthy).  Solvers with
        in-loop guards (CG family: ``pAp < 0``, ``rho == 0``) keep a
        ``brk`` field in their state; everything else returns None and
        relies on the non-finite residual check."""
        return getattr(state, "brk", None)

    def residual_norm_estimate(self, b, x, state):
        """Solvers with an implicit residual estimate (FGMRES quasi-residual)
        override this to avoid an extra SpMV per iteration."""
        return None

    def solve_finalize(self, b, x, state):
        return x

    # ------------------------------------------------------------- utilities
    def grid_stats(self) -> str:
        return ""
