"""Krylov solvers: CG, PCG, PCGF, BiCGStab, PBiCGStab, GMRES, FGMRES.

Reference: ``core/src/solvers/{cg,pcg,pcgf,bicgstab,pbicgstab,gmres,
fgmres}_solver.cu``.  Every solver supports an optional nested
preconditioner allocated from its config scope (reference
``fgmres_solver.cu:243-253``), traced inline into the iteration.

TPU design notes:
* (F)GMRES orthogonalisation is two-pass classical Gram-Schmidt (CGS2) —
  two (m+1,n)×(n,) matmuls per iteration instead of the reference's
  sequential Givens-on-Hessenberg MGS loop; numerically as robust as MGS in
  practice and MXU-friendly.  The Givens QR of the Hessenberg column
  (``fgmres_solver.cu:268-273``) is kept, as a sequential scan over the
  (tiny) restart dimension.
* The Krylov basis is a fixed (m+1, n) buffer so the whole solve jits with
  static shapes; restart position is ``iter % m`` computed in-graph.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..errors import BREAKDOWN_INDEFINITE, BREAKDOWN_KRYLOV
from ..ops import blas
from ..ops.spmv import spmv
from ..telemetry import scopes as _tscopes
from .base import Solver, SolverFactory, register_solver


def _cg_breakdown(brk, rz, pq):
    """In-loop CG breakdown guard (reference: the reference detects
    these only post-hoc; the TPU loop flags them ON DEVICE so the
    convergence reduction stops within an iteration of the event):

    * ``rho == 0`` or ``pAp == 0`` — z ⊥ r / A-null search direction,
      the Krylov recursion cannot extend (``BREAKDOWN_KRYLOV``).  This
      code is PROVISIONAL: at true convergence these scalars also
      vanish, so the base monitor block discards it when the monitored
      residual is dead (``Solver.breakdown_code`` contract) — which is
      what lets the guard cost ZERO extra vector work per iteration
      (the old residual-alive dot duplicated the carried norm);
    * ``Re(pAp) < 0`` — the operator (or preconditioner) is not SPD
      (``BREAKDOWN_INDEFINITE``).

    The FIRST code sticks; 0 stays healthy.  NaN comparisons are False,
    so a poisoned state falls through to the monitor's non-finite
    check."""
    kry = (rz == 0) | (pq == 0)
    indef = jnp.real(pq) < 0
    code = jnp.where(indef, BREAKDOWN_INDEFINITE,
                     jnp.where(kry, BREAKDOWN_KRYLOV, 0)) \
        .astype(jnp.int32)
    return jnp.where(brk == 0, code, brk)


class _PrecondMixin:
    """Allocates the nested preconditioner from config scope."""

    def _setup_preconditioner(self, use_precond: bool):
        existing = getattr(self, "preconditioner", None)
        if existing is not None and use_precond \
                and getattr(self, "_numeric_resetup", False):
            # numeric re-setup ONLY: reuse the preconditioner INSTANCE so
            # its hierarchy structure-reuse and compiled executables
            # survive; a plain setup() re-allocates it fresh
            a = self.A if self.A is not None else self.Ad
            existing.resetup(a)
            return
        self.preconditioner: Optional[Solver] = None
        if use_precond and self.cfg.has("preconditioner", self.scope):
            self.preconditioner = SolverFactory.allocate(
                self.cfg, self.scope, "preconditioner")
            a = self.A if self.A is not None else self.Ad
            self.preconditioner.setup(a)

    def _apply_M(self, r):
        if self.preconditioner is None:
            return r
        with _tscopes.scope("krylov", "precond"):
            return self.preconditioner.apply(r)


class _CGState(NamedTuple):
    r: jax.Array
    p: jax.Array
    rz: jax.Array
    brk: jax.Array      # int32 breakdown code (errors.BREAKDOWN_*)


class _CACGState(NamedTuple):
    """Chronopoulos–Gear single-reduction CG state.  Invariants:
    u = M·r, w = A·u, s = A·p; gamma = (r,u), delta = (w,u) for the
    CURRENT vectors (the fused reduction runs at the end of the
    iteration, so the carried scalars are always up to date)."""
    r: jax.Array
    u: jax.Array
    w: jax.Array
    p: jax.Array
    s: jax.Array
    gamma: jax.Array        # (r, u) of current state
    gamma_prev: jax.Array   # previous gamma (for beta)
    delta: jax.Array        # (w, u) of current state
    alpha_prev: jax.Array   # previous step length (for the alpha recurrence)
    rr: jax.Array           # raw norm accumulators of current r, (k,) real
    brk: jax.Array          # int32 breakdown code (errors.BREAKDOWN_*)


class _PipeCGState(NamedTuple):
    """Ghysels–Vanroose pipelined CG state.  Extra auxiliaries keep
    q = M·s and z = A·q so the single fused reduction at the TOP of an
    iteration is independent of the m = M·w / n = A·m applications that
    follow — XLA overlaps the collective with the SpMV + precond.  The
    carried ``rr`` is therefore the norm of the INCOMING residual (lags
    one iteration — the documented price of the overlap)."""
    r: jax.Array
    u: jax.Array
    w: jax.Array
    p: jax.Array
    s: jax.Array
    q: jax.Array
    z: jax.Array
    gamma_prev: jax.Array
    alpha_prev: jax.Array
    rr: jax.Array
    brk: jax.Array


@register_solver("CG")
class CGSolver(Solver):
    """Plain conjugate gradient (reference ``cg_solver.cu``).

    The ``krylov_comm`` knob (or a ``forced_comm`` subclass override)
    selects the communication variant: CLASSIC (two blocking reductions
    per iteration), CA (Chronopoulos–Gear, ONE fused reduction at the end
    of the iteration) or PIPELINED (Ghysels–Vanroose, one fused reduction
    overlapped with the next SpMV + preconditioner apply).  Both CA modes
    recompute the TRUE residual every ``ca_residual_replace`` iterations
    so recurrence drift never fakes convergence."""

    use_preconditioner = False
    forced_comm: Optional[str] = None

    def solver_setup(self):
        if getattr(self, "use_preconditioner", False):
            self._setup_preconditioner(True)

    def _M(self, r):
        return r

    # ---------------------------------------------- communication mode
    def _comm_mode(self) -> str:
        if getattr(self, "_force_krylov_classic", False):
            return "CLASSIC"        # recovery-ladder CA→CLASSIC fallback
        mode = self.forced_comm or self.krylov_comm
        if mode != "CLASSIC" and self.norm_type == blas.NORM_LMAX:
            # LMAX needs a max-reduce and cannot ride the fused psum
            return "CLASSIC"
        return mode

    def _fused_scalars(self, r, u, w):
        """gamma = (r,u), delta = (w,u) and the monitor-norm accumulators
        of r, all from ONE stacked reduction."""
        with _tscopes.scope("krylov", "reduce"):
            terms = [jnp.conj(r) * u, jnp.conj(w) * u]
            terms += blas.norm_terms(r, self.norm_type, self.Ad.block_dim,
                                     self.use_scalar_norm)
            acc = blas.fused_reduce(terms)
            return acc[0], acc[1], jnp.real(acc[2:])

    # ------------------------------------------------------------ init
    def solve_init(self, b, x):
        mode = self._comm_mode()
        if mode == "CLASSIC":
            r = b - spmv(self.Ad, x)
            z = self._M(r)
            rz = blas.dot(r, z)
            return _CGState(r=r, p=z, rz=rz,
                            brk=jnp.zeros((), jnp.int32))
        r = b - spmv(self.Ad, x)
        u = self._M(r)
        w = spmv(self.Ad, u)
        gamma, delta, rr = self._fused_scalars(r, u, w)
        one = jnp.ones((), gamma.dtype)
        zero_v = jnp.zeros_like(r)
        brk = jnp.zeros((), jnp.int32)
        if mode == "CA":
            return _CACGState(r=r, u=u, w=w, p=zero_v, s=zero_v,
                              gamma=gamma, gamma_prev=one, delta=delta,
                              alpha_prev=one, rr=rr, brk=brk)
        return _PipeCGState(r=r, u=u, w=w, p=zero_v, s=zero_v,
                            q=zero_v, z=zero_v, gamma_prev=one,
                            alpha_prev=one, rr=rr, brk=brk)

    # -------------------------------------------------------- iteration
    def solve_iteration(self, b, x, state, iter_idx):
        mode = self._comm_mode()
        if mode == "CA":
            return self._ca_iteration(b, x, state, iter_idx)
        if mode == "PIPELINED":
            return self._pipe_iteration(b, x, state, iter_idx)
        r, p, rz, brk = state
        # breakdown guards: incoming rho collapse / new pAp sign
        # (provisional — the base monitor block validates against the
        # carried residual norm; see _cg_breakdown)
        q = spmv(self.Ad, p)
        pq = blas.dot(p, q)
        brk = _cg_breakdown(brk, rz, pq)
        alpha = jnp.where(pq != 0, rz / jnp.where(pq == 0, 1.0, pq), 0.0)
        x = x + alpha * p
        r = r - alpha * q
        z = self._M(r)
        rz_new = blas.dot(r, z)
        beta = jnp.where(rz != 0, rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        p = z + beta * p
        return x, _CGState(r=r, p=p, rz=rz_new, brk=brk)

    def _cg_scalar_step(self, gamma, gamma_prev, delta, alpha_prev, brk,
                        iter_idx):
        """Shared CA/pipelined scalar recurrence:
        beta_i = gamma_i/gamma_{i-1} (0 at i=0),
        pAp    = delta_i − beta_i·gamma_i/alpha_{i-1}  (== (p_i, A p_i)),
        alpha_i = gamma_i/pAp — with the same breakdown guards the
        classic loop applies to (rho, pAp)."""
        first = iter_idx == 0
        beta = jnp.where(
            first, 0.0,
            gamma / jnp.where(gamma_prev == 0, 1.0, gamma_prev))
        pap = delta - beta * gamma \
            / jnp.where(alpha_prev == 0, 1.0, alpha_prev)
        # gamma_{i-1} == 0 is a true Krylov breakdown the recurrence
        # would otherwise divide through (the classic loop sees it as
        # rho == 0 one iteration earlier) — flag it BEFORE the generic
        # guard so the code is deterministic under krylov_zero injection
        brk = jnp.where((brk == 0) & ~first & (gamma_prev == 0),
                        jnp.asarray(BREAKDOWN_KRYLOV, jnp.int32), brk)
        brk = _cg_breakdown(brk, gamma, pap)
        alpha = jnp.where(pap != 0,
                          gamma / jnp.where(pap == 0, 1.0, pap), 0.0)
        return beta, alpha, brk

    def _ca_iteration(self, b, x, state, iter_idx):
        (r, u, w, p, s, gamma, gamma_prev, delta, alpha_prev, rr,
         brk) = state
        rep = self.ca_residual_replace
        if rep > 0:
            do_rep = (iter_idx > 0) & (jnp.mod(iter_idx, rep) == 0)

            def replace(_):
                # true-residual replacement: recompute r, u = M r,
                # w = A u and s = A p from scratch, plus the carried
                # scalars, so accumulated recurrence drift is flushed
                with blas.replacement_scope():
                    r_t = b - spmv(self.Ad, x)
                    u_t = self._M(r_t)
                    w_t = spmv(self.Ad, u_t)
                    s_t = spmv(self.Ad, p)
                    g_t, d_t, rr_t = self._fused_scalars(r_t, u_t, w_t)
                return r_t, u_t, w_t, s_t, g_t, d_t, rr_t

            def keep(_):
                return r, u, w, s, gamma, delta, rr

            r, u, w, s, gamma, delta, rr = \
                jax.lax.cond(do_rep, replace, keep, None)
        beta, alpha, brk = self._cg_scalar_step(
            gamma, gamma_prev, delta, alpha_prev, brk, iter_idx)
        p = u + beta * p
        s = w + beta * s        # s = A p by linearity
        x = x + alpha * p
        r = r - alpha * s
        u = self._M(r)
        w = spmv(self.Ad, u)
        gamma_new, delta_new, rr_new = self._fused_scalars(r, u, w)
        return x, _CACGState(r=r, u=u, w=w, p=p, s=s, gamma=gamma_new,
                             gamma_prev=gamma, delta=delta_new,
                             alpha_prev=alpha, rr=rr_new, brk=brk)

    def _pipe_iteration(self, b, x, state, iter_idx):
        (r, u, w, p, s, q, z, gamma_prev, alpha_prev, rr, brk) = state
        rep = self.ca_residual_replace
        if rep > 0:
            do_rep = (iter_idx > 0) & (jnp.mod(iter_idx, rep) == 0)

            def replace(_):
                with blas.replacement_scope():
                    r_t = b - spmv(self.Ad, x)
                    u_t = self._M(r_t)
                    w_t = spmv(self.Ad, u_t)
                    s_t = spmv(self.Ad, p)
                    q_t = self._M(s_t)
                    z_t = spmv(self.Ad, q_t)
                return r_t, u_t, w_t, s_t, q_t, z_t

            def keep(_):
                return r, u, w, s, q, z

            r, u, w, s, q, z = jax.lax.cond(do_rep, replace, keep, None)
        # ONE fused reduction on the incoming state; m = M·w and
        # n = A·m below do not depend on it, so the collective's latency
        # hides behind the precond apply + SpMV
        gamma, delta, rr_new = self._fused_scalars(r, u, w)
        m_vec = self._M(w)
        n_vec = spmv(self.Ad, m_vec)
        beta, alpha, brk = self._cg_scalar_step(
            gamma, gamma_prev, delta, alpha_prev, brk, iter_idx)
        z = n_vec + beta * z    # z = A q
        q = m_vec + beta * q    # q = M s
        s = w + beta * s        # s = A p
        p = u + beta * p
        x = x + alpha * p
        r = r - alpha * s
        u = u - alpha * q
        w = w - alpha * z
        return x, _PipeCGState(r=r, u=u, w=w, p=p, s=s, q=q, z=z,
                               gamma_prev=gamma, alpha_prev=alpha,
                               rr=rr_new, brk=brk)

    def residual_norm_estimate(self, b, x, state):
        if isinstance(state, (_CACGState, _PipeCGState)):
            # the fused reduction already carried the norm accumulators —
            # finishing them is collective-free
            return blas.finish_norm(state.rr, self.norm_type,
                                    state.r.shape[0], self.Ad.block_dim,
                                    self.use_scalar_norm)
        return blas.norm(state.r, self.norm_type, self.Ad.block_dim,
                         self.use_scalar_norm)


@register_solver("PCG")
class PCGSolver(_PrecondMixin, CGSolver):
    """Preconditioned CG (reference ``pcg_solver.cu``)."""

    use_preconditioner = True

    def _M(self, r):
        return self._apply_M(r)


@register_solver("PCG_CA")
class PCGCASolver(PCGSolver):
    """Single-reduction (Chronopoulos–Gear) PCG: ``PCG`` with
    ``krylov_comm=CA`` baked in."""

    forced_comm = "CA"


@register_solver("PCG_PIPE")
class PCGPipeSolver(PCGSolver):
    """Pipelined (Ghysels–Vanroose) PCG: ``PCG`` with
    ``krylov_comm=PIPELINED`` baked in."""

    forced_comm = "PIPELINED"


class _PCGFState(NamedTuple):
    r: jax.Array
    z: jax.Array
    p: jax.Array
    rz: jax.Array
    brk: jax.Array      # int32 breakdown code (errors.BREAKDOWN_*)


@register_solver("PCGF")
class PCGFSolver(_PrecondMixin, Solver):
    """Flexible PCG (reference ``pcgf_solver.cu``): Polak–Ribière β
    ⟨z_new, r_new − r_old⟩/⟨z_old, r_old⟩ tolerates a varying
    preconditioner (e.g. AMG with non-stationary smoothing)."""

    def solver_setup(self):
        self._setup_preconditioner(True)

    def solve_init(self, b, x):
        r = b - spmv(self.Ad, x)
        z = self._apply_M(r)
        rz = blas.dot(r, z)
        return _PCGFState(r=r, z=z, p=z, rz=rz,
                          brk=jnp.zeros((), jnp.int32))

    def solve_iteration(self, b, x, state, iter_idx):
        r, z, p, rz, brk = state
        q = spmv(self.Ad, p)
        pq = blas.dot(p, q)
        brk = _cg_breakdown(brk, rz, pq)
        alpha = jnp.where(pq != 0, rz / jnp.where(pq == 0, 1.0, pq), 0.0)
        x = x + alpha * p
        r_new = r - alpha * q
        z_new = self._apply_M(r_new)
        # flexible beta
        rz_new = blas.dot(r_new, z_new)
        beta_num = rz_new - blas.dot(r, z_new)
        beta = jnp.where(rz != 0, beta_num / jnp.where(rz == 0, 1.0, rz), 0.0)
        p = z_new + beta * p
        return x, _PCGFState(r=r_new, z=z_new, p=p, rz=rz_new, brk=brk)

    def residual_norm_estimate(self, b, x, state):
        return blas.norm(state.r, self.norm_type, self.Ad.block_dim,
                         self.use_scalar_norm)


class _BiCGStabState(NamedTuple):
    r: jax.Array
    r_star: jax.Array
    p: jax.Array
    v: jax.Array
    rho: jax.Array
    alpha: jax.Array
    omega: jax.Array
    brk: jax.Array      # int32 breakdown code (errors.BREAKDOWN_*)


class _BiCGStabBase(Solver):
    """BiCGStab skeleton; ``_M`` hooks preconditioning (right)."""

    def _M(self, r):
        return r

    def solve_init(self, b, x):
        r = b - spmv(self.Ad, x)
        one = jnp.asarray(1.0, r.dtype)
        return _BiCGStabState(r=r, r_star=r, p=jnp.zeros_like(r),
                              v=jnp.zeros_like(r), rho=one, alpha=one,
                              omega=one, brk=jnp.zeros((), jnp.int32))

    def solve_iteration(self, b, x, state, iter_idx):
        r, r_star, p, v, rho, alpha, omega, brk = state
        rho_new = blas.dot(r_star, r)
        # the classic BiCGStab serious breakdown: r ⊥ r* — provisional
        # (the base monitor block discards it when the residual is
        # dead, i.e. ordinary convergence)
        brk = jnp.where((brk == 0) & (rho_new == 0),
                        jnp.asarray(BREAKDOWN_KRYLOV, jnp.int32), brk)
        safe = lambda d: jnp.where(d == 0, 1.0, d)
        beta = (rho_new / safe(rho)) * (alpha / safe(omega))
        p = r + beta * (p - omega * v)
        p_hat = self._M(p)
        v = spmv(self.Ad, p_hat)
        alpha = rho_new / safe(blas.dot(r_star, v))
        s = r - alpha * v
        s_hat = self._M(s)
        t = spmv(self.Ad, s_hat)
        tt = blas.dot(t, t)
        omega = jnp.where(tt != 0, blas.dot(t, s) / safe(tt), 0.0)
        x = x + alpha * p_hat + omega * s_hat
        r = s - omega * t
        return x, _BiCGStabState(r=r, r_star=r_star, p=p, v=v, rho=rho_new,
                                 alpha=alpha, omega=omega, brk=brk)

    def residual_norm_estimate(self, b, x, state):
        return blas.norm(state.r, self.norm_type, self.Ad.block_dim,
                         self.use_scalar_norm)


@register_solver("BICGSTAB")
class BiCGStabSolver(_BiCGStabBase):
    """Reference ``bicgstab_solver.cu``."""


@register_solver("PBICGSTAB")
class PBiCGStabSolver(_PrecondMixin, _BiCGStabBase):
    """Right-preconditioned BiCGStab (reference ``pbicgstab_solver.cu``)."""

    def solver_setup(self):
        self._setup_preconditioner(True)

    def _M(self, r):
        return self._apply_M(r)


class _GMRESState(NamedTuple):
    V: jax.Array       # (m+1, n) Krylov basis
    Z: jax.Array       # (m, n) preconditioned basis (FGMRES) or (1,1) dummy
    R: jax.Array       # (m+1, m) triangularised Hessenberg
    g: jax.Array       # (m+1,) LS right-hand side
    cs: jax.Array      # (m,) Givens cosines
    sn: jax.Array      # (m,) Givens sines
    x_base: jax.Array  # x at cycle start
    quasi_res: jax.Array
    j: jax.Array       # current cycle position (last completed column)


class _GMRESBase(Solver):
    flexible = False

    def __init__(self, cfg, scope="default"):
        super().__init__(cfg, scope)
        self.restart = int(cfg.get("gmres_n_restart", scope))
        krylov_dim = int(cfg.get("gmres_krylov_dim", scope))
        if krylov_dim > 0:
            self.restart = min(self.restart, krylov_dim)

    def solver_setup(self):
        self._setup_preconditioner(True)

    def _M(self, r):
        return self._apply_M(r)

    def _comm_mode(self) -> str:
        """CA/PIPELINED both select the fused Arnoldi pass (the second
        CGS2 projection and the normalisation norm share one stacked
        collective); CLASSIC keeps the three reductions per column."""
        if getattr(self, "_force_krylov_classic", False):
            return "CLASSIC"
        return self.krylov_comm

    def solve_init(self, b, x):
        m, n = self.restart, b.shape[0]
        dt = b.dtype
        # the first Arnoldi vector and the basis storage
        with _tscopes.scope("krylov", "arnoldi"):
            r = b - spmv(self.Ad, x)
            beta = blas.nrm2(r)
            V = jnp.zeros((m + 1, n), dt)
            V = V.at[0].set(jnp.where(
                beta > 0, r / jnp.where(beta == 0, 1, beta), 0.0))
            Z = jnp.zeros((m, n), dt) if self.flexible \
                else jnp.zeros((1, 1), dt)
            g = jnp.zeros((m + 1,), dt).at[0].set(beta)
            return _GMRESState(
                V=V, Z=Z, R=jnp.zeros((m + 1, m), dt), g=g,
                cs=jnp.zeros((m,), dt), sn=jnp.zeros((m,), dt),
                x_base=x, quasi_res=jnp.abs(beta),
                j=jnp.asarray(-1, jnp.int32))

    def _solve_ls_and_update(self, state, j):
        """x = x_base + basis · y where R[:j+1,:j+1] y = g[:j+1].

        Unused columns are masked to identity so the fixed-size triangular
        solve is exact for any cycle position j.
        """
        m = self.restart
        with _tscopes.scope("krylov", "update"):
            R = state.R[:m, :m]
            mask = jnp.arange(m) > j
            R = jnp.where(mask[None, :] | mask[:, None], 0.0, R)
            R = R + jnp.diag(jnp.where(mask, 1.0, 0.0))
            g = jnp.where(jnp.arange(m) <= j, state.g[:m], 0.0)
            y = jax.scipy.linalg.solve_triangular(R, g, lower=False)
            if self.flexible:
                dx = state.Z.T @ y
            else:
                y = jnp.where(jnp.arange(m) <= j, y, 0.0)
                dx = self._M(state.V[:m].T @ y)
            return state.x_base + dx

    def solve_iteration(self, b, x, state, iter_idx):
        m = self.restart
        j = jnp.mod(iter_idx, m)
        restart = (j == 0) & (iter_idx > 0)

        # --- restart: recompute the true residual and restart the basis.
        # Only the (n,)-sized pieces ride the branch — rebuilding the whole
        # (m+1, n) state under a cond made XLA materialise a copy of the
        # Krylov basis EVERY iteration (measured ~3× the per-iteration
        # cost at 256³); stale basis rows are instead neutralised by the
        # row masks on the CGS2 coefficients below.
        def fresh_v0(_):
            # ledger: the restart recompute is amortised over the cycle,
            # not part of the steady-state per-iteration profile
            with blas.replacement_scope():
                r = b - spmv(self.Ad, x)
                beta = blas.nrm2(r)
            v0 = jnp.where(beta > 0, r / jnp.where(beta == 0, 1, beta), 0.0)
            # g rides in the basis dtype (complex modes store the real
            # |r| as a complex scalar)
            return v0, jnp.abs(beta).astype(state.g.dtype)

        def keep_v0(_):
            return state.V[0], state.g[0]

        with _tscopes.scope("krylov", "arnoldi"):
            v0, beta = jax.lax.cond(restart, fresh_v0, keep_v0, None)
            V = state.V.at[0].set(v0)
            x_base = jnp.where(restart, x, state.x_base)
            zeros_m = jnp.zeros((m,), V.dtype)
            g = jnp.where(restart,
                          jnp.zeros((m + 1,), V.dtype).at[0].set(beta),
                          state.g)
            cs = jnp.where(restart, zeros_m, state.cs)
            sn = jnp.where(restart, zeros_m, state.sn)
            state = state._replace(V=V, g=g, cs=cs, sn=sn, x_base=x_base)

        # --- Arnoldi step with CGS2 orthogonalisation; rows > j may hold
        # stale directions from the previous cycle — mask their
        # coefficients instead of zeroing the basis storage
        row_ok = (jnp.arange(m + 1) <= j).astype(state.V.real.dtype)
        v_j = state.V[j]
        z_j = self._M(v_j)
        with _tscopes.scope("krylov", "arnoldi"):
            w = spmv(self.Ad, z_j)
            # projections h_i = <v_i, w> are CONJUGATED (complex modes:
            # jnp.conj of a real array is a no-op XLA folds away)
            h1 = blas.gram_dots(state.V, w, row_ok)
            w = w - state.V.T @ h1
            if self._comm_mode() != "CLASSIC":
                # fused Arnoldi: the second CGS2 pass and ‖w‖² ride ONE
                # stacked matmul (3 → 2 collectives per column); after the
                # first pass h2 is O(ε)·‖w‖, so the Pythagorean downdate
                # ‖w − V·h2‖² = ‖w‖² − ‖h2‖² loses no accuracy in practice
                h2, ww = blas.gram_dots_with_norm(state.V, w, row_ok)
                w = w - state.V.T @ h2
                h_next = jnp.sqrt(jnp.maximum(
                    ww - jnp.sum(jnp.abs(h2) ** 2), 0.0))
            else:
                h2 = blas.gram_dots(state.V, w, row_ok)
                w = w - state.V.T @ h2
                h_next = blas.nrm2(w)
            hcol = h1 + h2              # (m+1,)
            V = state.V.at[j + 1].set(
                jnp.where(h_next > 0,
                          w / jnp.where(h_next == 0, 1, h_next), 0.0))
            hcol = hcol.at[j + 1].set(h_next)
            Z = state.Z.at[j].set(z_j) if self.flexible else state.Z

        # --- apply previous Givens rotations to the new column
        # (sequential).  The unitary form G = [[c̄, s̄], [−s, c]] with
        # c = a/r, s = b/r (r = √(|a|²+|b|²)) maps (a, b) → (r, 0) for
        # real AND complex entries alike (conj on reals folds away).
        def rot_body(i, hc):
            ci, si = state.cs[i], state.sn[i]
            hi, hi1 = hc[i], hc[i + 1]
            active = i < j
            new_i = jnp.where(active,
                              jnp.conj(ci) * hi + jnp.conj(si) * hi1, hi)
            new_i1 = jnp.where(active, -si * hi + ci * hi1, hi1)
            return hc.at[i].set(new_i).at[i + 1].set(new_i1)

        with _tscopes.scope("krylov", "givens"):
            hcol = jax.lax.fori_loop(0, m, rot_body, hcol)

            # --- new Givens rotation zeroing h[j+1]
            hj, hj1 = hcol[j], hcol[j + 1]
            denom = jnp.sqrt(jnp.abs(hj) ** 2 + jnp.abs(hj1) ** 2)
            safe = jnp.where(denom == 0, 1.0, denom)
            c = jnp.where(denom == 0, jnp.ones((), hcol.dtype), hj / safe)
            s = jnp.where(denom == 0, jnp.zeros((), hcol.dtype), hj1 / safe)
            hcol = hcol.at[j].set(jnp.conj(c) * hj + jnp.conj(s) * hj1) \
                       .at[j + 1].set(0.0)
            cs = state.cs.at[j].set(c)
            sn = state.sn.at[j].set(s)
            gj = state.g[j]
            g = state.g.at[j].set(jnp.conj(c) * gj).at[j + 1].set(-s * gj)
            R = state.R.at[:, j].set(hcol)
            quasi = jnp.abs(g[j + 1])

        new_state = _GMRESState(V=V, Z=Z, R=R, g=g, cs=cs, sn=sn,
                                x_base=state.x_base, quasi_res=quasi,
                                j=j.astype(jnp.int32))

        # --- end of cycle: fold the LS solution into x
        def finish(st):
            return self._solve_ls_and_update(st, j)

        with _tscopes.scope("krylov", "update"):
            x = jax.lax.cond(j == m - 1, finish, lambda st: st.x_base,
                             new_state)
            # after a boundary update, x_base:=x and clear g so a later
            # solve_finalize adds nothing on top (y solves R·y = 0)
            at_boundary = j == m - 1
            new_state = new_state._replace(
                x_base=jnp.where(at_boundary, x, new_state.x_base),
                g=jnp.where(at_boundary, jnp.zeros_like(g), g))
        return x, new_state

    def residual_norm_estimate(self, b, x, state):
        if self.norm_type == "L2" and (self.use_scalar_norm or
                                       self.Ad.block_dim == 1):
            return state.quasi_res
        return None  # fall back to explicit residual

    def solve_finalize(self, b, x, state):
        # mid-cycle exit: fold the pending LS solution into x (at cycle
        # boundaries solve_iteration already updated x_base and cleared g,
        # making this a no-op).
        return self._solve_ls_and_update(state, state.j)


@register_solver("GMRES")
class GMRESSolver(_PrecondMixin, _GMRESBase):
    """Restarted right-preconditioned GMRES (reference ``gmres_solver.cu``)."""

    flexible = False


@register_solver("FGMRES")
class FGMRESSolver(_PrecondMixin, _GMRESBase):
    """Flexible GMRES (reference ``fgmres_solver.cu``): stores the
    preconditioned vectors Z so the preconditioner may change every
    iteration (AMG V-cycle)."""

    flexible = True
