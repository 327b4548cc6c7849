"""Gauss-Seidel family + Kaczmarz smoothers (color-parallel).

Reference: ``core/src/solvers/multicolor_gauss_seidel_solver.cu``,
``fixcolor_gauss_seidel_solver.cu``, ``gauss_seidel_solver.cu``,
``kaczmarz_solver.cu``; params ``symmetric_GS``, ``GS_L1_variant``
(core.cu:425-427), ``kaczmarz_coloring_needed``.

TPU design: rows of one color are independent.  Each color's rows are
gathered at setup into a compact ELL slab (rows, padded cols, values), so
one sweep costs O(nnz) total — the per-color update reads only that
color's slab and scatters only that color's rows, exactly like the
reference's per-color kernels (``multicolor_dilu_solver.cu``) and unlike
a masked full-width relaxation, which would pay O(num_colors · nnz).
The serial "GS" solver maps onto the same color-ordered sweep.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..coloring import color_matrix
from ..ops.spmv import spmv
from ..utils.jaxcompat import shard_map as _shard_map
from .base import Solver, register_solver
from .colorsweep import (ColorSlab, build_color_slabs, stack_color_slabs,
                         sweep)
from .jacobi import _apply_dinv, setup_dinv


class _ColoredSmootherBase(Solver):
    """Shared setup: coloring + per-color packed slabs (or masks for the
    sharded fallback) + block-diag inverse."""

    def _setup_colors(self, build_slabs: bool = True,
                      dist_slabs: bool = True):
        if self.A is not None:
            coloring = color_matrix(self.A, self.cfg, self.scope)
            colors = coloring.colors
            self.num_colors = coloring.num_colors
        else:
            # device-only fallback: single color (degenerates to Jacobi)
            colors = np.zeros(self.Ad.n_rows, dtype=np.int32)
            self.num_colors = 1
        b = self.Ad.block_dim
        self.color_slabs = None
        self.color_masks = None
        self.dist_slab_rows = None
        if build_slabs and self.Ad.fmt != "sharded-ell" \
                and self.A is not None:
            self.color_slabs = build_color_slabs(
                self.A.host, colors, self.num_colors, self.Ad.dtype, b)
        elif build_slabs and dist_slabs \
                and self.Ad.fmt == "sharded-ell" and b == 1 \
                and self.A is not None:
            # distributed per-color slabs: the shard pack's columns are
            # already in [local | halo] coordinates, so each color's
            # slab is a row-selection of the shard ELL; the sweep pays
            # ONE halo exchange and O(nnz_shard) per pass (reference
            # per-color kernels, multicolor_dilu_solver.cu) instead of
            # the masked O(num_colors·nnz) with per-color exchanges
            self.dist_slab_rows = self._stack_dist_color_rows(colors)
        else:
            # device-only (or block-sharded) fallback: masked full-width
            masks = []
            for c in range(self.num_colors):
                m = colors == c
                if b > 1:
                    m = np.repeat(m, b)
                if self.Ad.fmt == "sharded-ell":
                    from ..distributed.matrix import shard_vector
                    masks.append(shard_vector(
                        self.Ad, m.astype(self.Ad.dtype)) > 0.5)
                else:
                    masks.append(jnp.asarray(m))
            self.color_masks = masks
        self.dinv = setup_dinv(self)

    def _stack_dist_color_rows(self, colors):
        """Per color, each rank's local row ids as row-only slabs, padded
        with the trash slot ``n_loc + H`` of the distributed sweep's
        ``[local | frozen halo | trash]`` vector."""
        Ad = self.Ad
        offs = np.asarray(Ad.offsets)
        per_rank = [[ColorSlab(np.flatnonzero(cp == c).astype(np.int32),
                               None, None)
                     for c in range(self.num_colors)]
                    for cp in np.split(colors, offs[1:-1])]
        trash = Ad.n_loc + Ad.halo_src.shape[1]
        return stack_color_slabs(per_rank, trash, Ad.mesh, Ad.axis)


def _shard_transpose(A, Ad):
    """Sharded pack of Aᵀ from per-rank row blocks of A: each rank's
    entries route to their COLUMN owners (send-side, neighbour-wise —
    the Pᵀ collection pattern of the classical distributed path).
    Row partition of Aᵀ = column partition of A = the same offsets."""
    import scipy.sparse as sp

    from ..distributed.matrix import shard_matrix_from_blocks
    offs = np.asarray(Ad.offsets)
    n_parts = Ad.n_parts
    n = int(offs[-1])
    if A.host is None and A.blocks is not None:
        blocks = A.blocks
    else:
        from ..distributed.partition import split_row_blocks
        blocks = split_row_blocks(A.scalar_csr(), offs)
    tri = [([], [], []) for _ in range(n_parts)]
    for p, blk in enumerate(blocks):
        coo = sp.coo_matrix(blk)
        gl_rows = coo.row.astype(np.int64) + offs[p]
        owner = np.searchsorted(offs, coo.col, side="right") - 1
        for q in np.unique(owner) if len(coo.col) else []:
            m = owner == q
            tri[q][0].append(coo.col[m] - offs[q])   # Aᵀ local rows
            tri[q][1].append(gl_rows[m])             # Aᵀ global cols
            tri[q][2].append(coo.data[m])
    t_blocks = []
    for q in range(n_parts):
        rr, cc, vv = tri[q]
        t_blocks.append(sp.csr_matrix(
            (np.concatenate(vv) if vv else [],
             (np.concatenate(rr) if rr else [],
              np.concatenate(cc) if cc else [])),
            shape=(int(offs[q + 1] - offs[q]), n)))
    return shard_matrix_from_blocks(t_blocks, offs, Ad.mesh,
                                    axis=Ad.axis, dtype=Ad.dtype,
                                    n_loc=Ad.n_loc)


def _structurally_symmetric(A) -> bool:
    """Pattern symmetry of a host Matrix (global or per-rank blocks);
    True when unknown (no host data) — the caller only warns."""
    import scipy.sparse as sp
    if A is None or (A.host is None and A.blocks is None):
        return True          # no host data: unknown — don't warn
    if A.blocks is None:
        csr = sp.csr_matrix(A.host)
        pat = sp.csr_matrix(
            (np.ones(csr.nnz, np.int8), csr.indices, csr.indptr),
            shape=csr.shape)
        return (pat != pat.T).nnz == 0
    # blocks mode: compare the sorted (i, j) and (j, i) key sets from
    # per-rank COO indices (index arrays only — no global matrix)
    n = int(A.block_offsets[-1])
    keys, rkeys = [], []
    for p, b in enumerate(A.blocks):
        coo = b.tocoo()
        rows = coo.row.astype(np.int64) + int(A.block_offsets[p])
        cols = coo.col.astype(np.int64)
        keys.append(rows * n + cols)
        rkeys.append(cols * n + rows)
    return bool(np.array_equal(np.sort(np.concatenate(keys)),
                               np.sort(np.concatenate(rkeys))))


def _abs_row_sums_and_diag(A):
    """(Σ_j |a_ij|, |a_ii|) per scalar row — per-rank in block mode."""
    if A.host is None and A.blocks is not None:
        offs = A.block_offsets
        absrow = np.concatenate([
            np.asarray(np.abs(b).sum(axis=1)).ravel() for b in A.blocks])
        d = np.concatenate([
            np.abs(np.asarray(b[:, offs[p]:offs[p + 1]].diagonal()))
            for p, b in enumerate(A.blocks)])
        return absrow, d
    csr = A.scalar_csr()
    return (np.asarray(np.abs(csr).sum(axis=1)).ravel(),
            np.abs(csr.diagonal()))


@register_solver("MULTICOLOR_GS")
class MulticolorGSSolver(_ColoredSmootherBase):
    is_smoother = True

    def __init__(self, cfg, scope="default"):
        super().__init__(cfg, scope)
        self.symmetric = bool(cfg.get("symmetric_GS", scope))
        self.l1_variant = bool(cfg.get("GS_L1_variant", scope))

    def solver_setup(self):
        self._setup_colors()
        if self.l1_variant and self.A is not None:
            # L1 damping: d_i ← d_i + Σ_{j∉color(i)}|a_ij| (jacobi_l1-style)
            absrow, d = _abs_row_sums_and_diag(self.A)
            dl1 = d + 0.5 * (absrow - d)
            dl1[dl1 == 0] = 1.0
            vec = (1.0 / dl1).astype(self.Ad.dtype)
            if self.Ad.fmt == "sharded-ell":
                from ..distributed.matrix import shard_vector
                self.dinv = shard_vector(self.Ad, vec)
            else:
                self.dinv = jnp.asarray(vec)

    def _relax(self, b, dinv):
        """The row update ω·D⁻¹(b − A·x) of the rows one color writes,
        from their row dot ``t`` = (A·x)_i."""
        relax = self.relaxation_factor

        def update(i, t):
            r_c = b[i] - t
            if dinv.ndim > r_c.ndim:
                return relax * jnp.einsum("nab,nb->na", dinv[i], r_c)
            return relax * dinv[i] * r_c
        return update

    def _color_sweep(self, b, x, order):
        if getattr(self, "dist_slab_rows", None) is not None:
            return self._dist_color_sweep(b, x, order)
        if self.color_slabs is None:
            # masked fallback (device-only packs)
            for c in order:
                r = b - spmv(self.Ad, x)
                dx = self.relaxation_factor * _apply_dinv(self.dinv, r)
                x = jnp.where(self.color_masks[c], x + dx, x)
            return x
        bd = self.Ad.block_dim
        rows = (lambda v: v) if bd == 1 else (lambda v: v.reshape(-1, bd))
        # a block smoother's L1 variant keeps a scalar damped diagonal
        dinv = rows(self.dinv) if self.dinv.ndim == 1 else self.dinv
        return sweep(rows(x), self.color_slabs, order,
                     self._relax(rows(b), dinv), add=True).reshape(-1)

    def _dist_color_sweep(self, b, x, order):
        """Distributed color-ordered sweep: ONE halo exchange at sweep
        start (halo values frozen, local updates visible — the
        reference's exchange-once-then-per-color-kernels pattern,
        multicolor_dilu_solver.cu:4167-4209), O(nnz_shard) total."""
        import jax
        from jax.sharding import PartitionSpec as P

        from ..distributed.matrix import _exchange
        A = self.Ad
        axis, n_parts, n_loc = A.axis, A.n_parts, A.n_loc
        order = list(order)

        def local(cols, vals, send_idx, halo_src, slabs, dinv, bl, xl):
            cols, vals, send_idx, halo_src, slabs = jax.tree_util.tree_map(
                lambda a: a[0], (cols, vals, send_idx, halo_src, slabs))
            got = _exchange(xl[send_idx], A.dists, axis, n_parts)
            # [local | frozen halo | trash]
            xe = jnp.concatenate([xl, got[halo_src],
                                  jnp.zeros((1,), xl.dtype)])
            xe = sweep(xe, slabs, order, self._relax(bl, dinv), add=True,
                       n_loc=n_loc, pack=(cols, vals))
            return xe[:n_loc]

        spec = lambda a: P(axis, *[None] * (a.ndim - 1))
        args = (A.cols, A.vals, A.send_idx, A.halo_src, self.dist_slab_rows)
        return _shard_map(
            local, mesh=A.mesh,
            in_specs=(*jax.tree_util.tree_map(spec, args), P(axis), P(axis),
                      P(axis)),
            out_specs=P(axis), check_vma=False,
        )(*args, self.dinv, b, x)

    def solve_iteration(self, b, x, state, iter_idx):
        x = self._color_sweep(b, x, range(self.num_colors))
        if self.symmetric:
            x = self._color_sweep(b, x, range(self.num_colors - 1, -1, -1))
        return x, state


@register_solver("GS")
class GSSolver(MulticolorGSSolver):
    """Serial GS (reference ``gauss_seidel_solver.cu``) — realised as the
    color-ordered sweep, which performs the identical relaxation for
    properly colored matrices."""


@register_solver("FIXCOLOR_GS")
class FixcolorGSSolver(MulticolorGSSolver):
    """GS with a fixed color count (``fixcolor_gauss_seidel_solver.cu``):
    forces the ROUND_ROBIN coloring with ``num_colors`` stripes."""

    def solver_setup(self):
        if self.A is not None and getattr(self.A, "coloring", None) is None:
            cfg2 = self.cfg.clone()
            cfg2.set("matrix_coloring_scheme", "ROUND_ROBIN", "default")
            from ..coloring import color_matrix as cm
            self.A.coloring = cm(self.A, cfg2, self.scope)
        super().solver_setup()


@register_solver("KACZMARZ")
class KaczmarzSolver(_ColoredSmootherBase):
    """Multicolor Kaczmarz (reference ``kaczmarz_solver.cu``): row
    projections x += a_i (b_i − a_i·x)/‖a_i‖², one color at a time."""

    is_smoother = True

    def solver_setup(self):
        # Kaczmarz colors the A·Aᵀ graph: same-color rows must not share
        # ANY column, so simultaneous projections are orthogonal
        # (reference ``kaczmarz_coloring_needed``, core.cu:437)
        if self.A is not None and self.Ad.block_dim == 1 and \
                (self.Ad.fmt != "sharded-ell" or self.A.blocks is None):
            # the scalar A·Aᵀ coloring (kaczmarz_coloring_needed) also
            # serves the sharded path whenever a host view exists (or is
            # dia-derivable), so the distributed sweep order matches the
            # single-device one; blocks-mode keeps the default
            # distance-1 coloring, and BLOCK matrices use the default
            # block-row coloring (the scalar-row A·Aᵀ colors would not
            # align with the b×b mask layout)
            import scipy.sparse as sp
            from ..coloring import MatrixColoring, create_coloring
            csr = self.A.scalar_csr()
            pat = sp.csr_matrix(
                (np.ones(len(csr.data), dtype=np.int8),
                 csr.indices.copy(), csr.indptr.copy()), shape=csr.shape)
            G = sp.csr_matrix(pat @ pat.T)
            algo = create_coloring("MIN_MAX", self.cfg, self.scope)
            coloring = algo.color(G)
            self.A.coloring = coloring
        # slab projections are scalar-row based; block packs use masks
        # Kaczmarz's scatter projection keeps the masked sharded path
        self._setup_colors(build_slabs=(self.Ad.block_dim == 1),
                           dist_slabs=False)
        # row squared norms + explicit transpose pack for the projections
        if self.A is not None:
            if self.A.host is None and self.A.blocks is not None:
                csr = None
                rn = np.concatenate([
                    np.asarray(b.multiply(b).sum(axis=1)).ravel()
                    for b in self.A.blocks])
            else:
                csr = self.A.scalar_csr()
                rn = np.asarray(csr.multiply(csr).sum(axis=1)).ravel()
            rn[rn == 0] = 1.0
            vec = (1.0 / rn).astype(self.Ad.dtype)
            if self.Ad.fmt == "sharded-ell":
                from ..distributed.matrix import shard_vector
                self.rowinv = shard_vector(self.Ad, vec)
                if self.Ad.block_dim == 1:
                    # TRUE distributed transpose pack (kaczmarz_solver.cu
                    # builds Aᵀ): per-rank Aᵀ row blocks are collected
                    # send-side — each rank routes its entries to their
                    # column owners (the same neighbour-wise collection
                    # as classical R), then pack as a ShardedMatrix
                    self.AdT = _shard_transpose(self.A, self.Ad)
                else:
                    # block transpose pack not built yet: reuse A, exact
                    # only under structural symmetry — warn when false
                    self.AdT = self.Ad
                    if not _structurally_symmetric(self.A):
                        import logging
                        logging.getLogger("amgx_tpu").warning(
                            "distributed block KACZMARZ substitutes A "
                            "for A^T but this matrix is NOT structurally"
                            " symmetric — projections use wrong "
                            "couplings and convergence will degrade")
            else:
                self.rowinv = jnp.asarray(vec)
                from ..core.matrix import Matrix as _M
                self.AdT = _M(csr.T.tocsr().astype(
                    self.Ad.dtype)).device()
        else:
            self.rowinv = jnp.ones((self.Ad.n,), self.Ad.dtype)
            self.AdT = self.Ad

    def solve_iteration(self, b, x, state, iter_idx):
        # colorwise projection: for rows i of color c,
        # x += a_i (b_i − a_i·x)/‖a_i‖² — per-color slab form reads and
        # scatters only that color's rows/columns (O(nnz) per sweep)
        if self.color_slabs is not None and self.Ad.block_dim == 1:
            for c in range(self.num_colors):
                s = self.color_slabs[c]
                r_c = b[s.rows] - jnp.sum(s.vals * x[s.cols], axis=1)
                w = self.relaxation_factor * r_c * self.rowinv[s.rows]
                # same-color rows share no column (AᵀA coloring), and
                # padded slots carry zero values — scatter-add is exact
                x = x.at[s.cols.ravel()].add((s.vals * w[:, None]).ravel())
            return x, state
        for c in range(self.num_colors):
            r = b - spmv(self.Ad, x)
            w = jnp.where(self.color_masks[c], r * self.rowinv, 0.0)
            x = x + self.relaxation_factor * spmv(self.AdT, w)
        return x, state
