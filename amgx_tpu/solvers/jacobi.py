"""Jacobi-family smoothers.

Reference: ``core/src/solvers/block_jacobi_solver.cu`` (BLOCK_JACOBI with
1×1/4×4/b×b paths and fused zero-initial-guess kernels),
``jacobi_l1_solver.cu`` (JACOBI_L1), ``cf_jacobi_solver.cu`` (CF_JACOBI).

TPU design: a sweep is ``x + ω·D⁻¹·(b − A·x)`` — one SpMV plus fused
elementwise work, or a batched (n,b,b)×(n,b) block solve for block matrices.
The zero-initial-guess first sweep collapses to ``ω·D⁻¹·b`` exactly as the
reference's fused kernels do (``block_jacobi_solver.cu:1240-1530``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from ..ops.spmv import spmv
from .base import Solver, register_solver


def _invert_block_diag(diag) -> jax.Array:
    """Invert the (block) diagonal: (n,) → reciprocal, (n,b,b) → batched inv.

    Runs on HOST numpy: this is setup-phase work, and issuing it as a
    device op costs one XLA compile per level shape.  One host
    computation + one transfer instead.
    """
    d = np.asarray(diag)
    # sub-f32 storage (bf16 block hierarchies): numpy's LinAlg kernels
    # don't take ml_dtypes — invert at the f32 compute floor and store
    # the RESULT narrow, the same storage-vs-arithmetic split every
    # other smoother-data path applies (core/precision.py)
    from ..core.precision import compute_dtype as _cdt
    store_dt = d.dtype
    work = d.astype(_cdt(d.dtype), copy=False)
    if work.ndim == 1:
        out = np.where(work != 0,
                       1.0 / np.where(work == 0, 1.0, work), 0.0)
    else:
        # scale-invariant singularity test: normalise each block by its
        # max entry first (raw |det| underflows for well-conditioned but
        # small-magnitude blocks, silently replacing D⁻¹ with I)
        bdim = work.shape[-1]
        scale = np.max(np.abs(work), axis=(-2, -1))
        nz = scale > 0
        dn = work / np.where(nz, scale, 1.0)[:, None, None]
        bad = ~nz | (np.abs(np.linalg.det(dn))
                     < bdim * np.finfo(work.dtype).eps)
        safe = np.where(bad[:, None, None],
                        np.eye(bdim, dtype=work.dtype), dn)
        out = np.linalg.inv(safe) / np.where(nz & ~bad, scale,
                                             1.0)[:, None, None]
    return jnp.asarray(out.astype(store_dt))


def _apply_dinv(dinv: jax.Array, v: jax.Array) -> jax.Array:
    if dinv.ndim == 1:
        return dinv * v
    b = dinv.shape[-1]
    return jnp.einsum("nab,nb->na", dinv,
                      v.reshape(-1, b)).reshape(-1)


@functools.lru_cache(maxsize=1)
def _scalar_dinv_fn():
    return jax.jit(lambda d: jnp.where(
        d != 0, 1.0 / jnp.where(d == 0, 1.0, d), 0.0))


def setup_dinv(slv) -> jax.Array:
    """The inverted (block) diagonal for a smoother's setup phase.

    Scalar packs invert the pack's own diagonal ON DEVICE (no per-level
    dinv upload); the sharded path keeps the sharding; block
    matrices factor on host (guarded batched inverse); device readback
    is the last resort (device-only block setup)."""
    Ad, A = slv.Ad, slv.A
    if Ad.fmt == "sharded-ell":
        d = Ad.diag
        if Ad.block_dim > 1:
            # batched b×b inverses, on the pack's sharding (an entrywise
            # reciprocal inverts only diagonal blocks)
            return jax.device_put(_invert_block_diag(d), d.sharding)
        return jnp.where(d != 0, 1.0 / jnp.where(d == 0, 1.0, d), 0.0)
    if A is not None:
        cached = getattr(A, "_dinv_dev", None)
        if cached is not None and cached[0] == Ad.dtype:
            return cached[1]      # rode the hierarchy's batched upload
    if Ad.block_dim == 1:
        return _scalar_dinv_fn()(Ad.diag)
    if A is not None:
        return _invert_block_diag(host_block_diag(A).astype(Ad.dtype))
    return _invert_block_diag(np.asarray(Ad.diag))


def host_block_diag(A) -> np.ndarray:
    """The (block) diagonal from the HOST matrix — avoids a device
    readback during setup."""
    b = A.block_dim
    if b == 1:
        return A.host_diag()
    bsr = A.host if isinstance(A.host, sp.bsr_matrix) else sp.bsr_matrix(
        A.host, blocksize=(b, b))
    bsr.sort_indices()
    n = bsr.shape[0] // b
    rows = np.repeat(np.arange(n), np.diff(bsr.indptr))
    out = np.zeros((n, b, b), dtype=bsr.data.dtype)
    on_diag = bsr.indices == rows
    out[rows[on_diag]] = bsr.data[on_diag]
    return out


@register_solver("BLOCK_JACOBI")
class BlockJacobiSolver(Solver):
    """Damped (block) Jacobi: x ← x + ω·D⁻¹·(b − A·x)."""

    is_smoother = True

    def solver_setup(self):
        self.dinv = setup_dinv(self)

    def solve_iteration(self, b, x, state, iter_idx):
        r = b - spmv(self.Ad, x)
        x = x + self.relaxation_factor * _apply_dinv(self.dinv, r)
        return x, state

    def apply(self, b, x0=None, n_iters=None):
        n = self.max_iters if n_iters is None else n_iters
        if x0 is None:
            # fused zero-initial-guess first sweep (reference :1240-1530)
            x = self.relaxation_factor * _apply_dinv(self.dinv, b)
            start = 1
        else:
            x = x0
            start = 0
        for _ in range(start, n):
            x, _ = self.solve_iteration(b, x, (), None)
        return x


@functools.lru_cache(maxsize=1)
def _l1_dinv_fn():
    from ..ops.spmv import abs_rowsum

    def fn(Ad):
        # abs_rowsum accumulates (and returns) f32 for sub-f32 packs;
        # the STORED dinv rides at the pack dtype — smoother data must
        # not silently upcast (mixed-precision bandwidth contract)
        absrow = abs_rowsum(Ad)
        dinv = 1.0 / jnp.where(absrow == 0, 1.0, absrow)
        return dinv.astype(Ad.diag.dtype)

    return jax.jit(fn)

@register_solver("JACOBI_L1")
class JacobiL1Solver(Solver):
    """L1-Jacobi: D_l1[i] = |a_ii| + Σ_{j≠i}|a_ij| per scalar row
    (reference ``jacobi_l1_solver.cu``); unconditionally convergent smoother
    and the TPU-friendly default for aggressive-coarsening configs."""

    is_smoother = True

    def solver_setup(self):
        if self.Ad.block_dim == 1 and self.Ad.fmt in (
                "dia", "dia3", "ell", "csr", "dense", "sharded-ell"):
            # L1 row sums from the pack ON DEVICE (|diag| + Σ|off-diag| =
            # Σ|row|): zero transfer, works with or without a host
            # matrix (blocks-mode distributed levels included), and
            # pad/explicit zeros contribute 0
            self.dinv = _l1_dinv_fn()(self.Ad)
        elif self.A is not None:
            csr = self.A.scalar_csr()
            absrow = np.asarray(np.abs(csr).sum(axis=1)).ravel()
            diag = csr.diagonal()
            d = np.abs(diag) + (absrow - np.abs(diag))
            d[d == 0] = 1.0
            if self.Ad.fmt == "sharded-ell":
                from ..distributed.matrix import shard_vector
                self.dinv = shard_vector(self.Ad, 1.0 / d)
            else:
                self.dinv = jnp.asarray(1.0 / d, dtype=self.Ad.dtype)
        else:
            # device-only block fallback: |diag|-block row sums
            d = jnp.abs(self.Ad.diag).sum(axis=-1).reshape(-1)
            self.dinv = 1.0 / jnp.where(d == 0, 1.0, d)

    def solve_iteration(self, b, x, state, iter_idx):
        r = b - spmv(self.Ad, x)
        x = x + self.relaxation_factor * self.dinv * r
        return x, state


@register_solver("CF_JACOBI")
class CFJacobiSolver(Solver):
    """C/F-split Jacobi for classical AMG (reference ``cf_jacobi_solver.cu``):
    one sweep updates C points then F points (or the reverse), using the
    C/F splitting attached to the matrix by the classical selector."""

    is_smoother = True

    def solver_setup(self):
        self.dinv = setup_dinv(self)
        self.cf_mode = int(self.cfg.get("cf_smoothing_mode", self.scope))
        cf = getattr(self.A, "cf_map", None) if self.A is not None else None
        if cf is None:
            cf = np.zeros(self.Ad.n_rows, dtype=bool)  # all C
        self.c_mask = jnp.asarray(np.asarray(cf, dtype=bool))

    def _masked_sweep(self, b, x, mask):
        r = b - spmv(self.Ad, x)
        dx = self.relaxation_factor * _apply_dinv(self.dinv, r)
        if self.Ad.block_dim > 1:
            mask = jnp.repeat(mask, self.Ad.block_dim)
        return x + jnp.where(mask, dx, 0.0)

    def solve_iteration(self, b, x, state, iter_idx):
        first_c = self.cf_mode in (0, 2)
        m1 = self.c_mask if first_c else ~self.c_mask
        x = self._masked_sweep(b, x, m1)
        x = self._masked_sweep(b, x, ~m1)
        return x, state
