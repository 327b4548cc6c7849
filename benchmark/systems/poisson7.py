"""Plain f64 references for the 7-point Poisson family, and the seeded
inputs every cell of that family draws from.

Nothing here imports the solver package: these operators are what the
benchmark's answers are checked against.

Grid numbering is lexicographic with x fastest: row ``i = (z*n + y)*n +
x`` on an n^3 grid, the ordering of AmgX's
``AMGX_generate_distributed_poisson_7pt`` and of the Kronecker sum
``kron(kron(T, I), I) + kron(kron(I, T), I) + kron(kron(I, I), T)``.
Boundaries are Dirichlet: a boundary cell keeps the face to the wall in
its diagonal, so the constant operator has 6 on the diagonal and -1 off
it everywhere.

The variable-coefficient operator discretises -div(k grad p) with one
coefficient k per cell: an interior face takes the harmonic mean of its
two cells, a wall face the cell's own k.  With k = 1 it is the constant
operator exactly.
"""
from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp

#: ELL slot order; ascending column order, so the masked slots are the
#: CSR rows as scipy stores them (sorted indices)
_SLOTS = ("-z", "-y", "-x", "0", "+x", "+y", "+z")


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one run's seed.  Any whole
    number is a seed, negative or beyond 64 bits included."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream]))


def kron_csr(n: int) -> sp.csr_matrix:
    """The constant operator as a Kronecker sum of the 1-D second
    difference (a second, independent construction of ``operator(n)``;
    the tests hold the two equal)."""
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    i = sp.identity(n)
    return (sp.kron(sp.kron(t, i), i) + sp.kron(sp.kron(i, t), i)
            + sp.kron(sp.kron(i, i), t)).tocsr()


class Pattern:
    """The sparsity pattern of the 7-point stencil on n^3, built once;
    ``csr(data)`` puts a value set on it."""

    def __init__(self, n: int):
        self.n = n
        N = n ** 3
        g = np.arange(N, dtype=np.int64)
        x, y, z = g % n, (g // n) % n, g // (n * n)
        step = {"x": 1, "y": n, "z": n * n}
        coord = {"x": x, "y": y, "z": z}
        cols, mask = [], []
        for s in _SLOTS:
            if s == "0":
                cols.append(g)
                mask.append(np.ones(N, bool))
                continue
            sign, ax = (-1 if s[0] == "-" else 1), s[1]
            cols.append(g + sign * step[ax])
            mask.append(coord[ax] > 0 if sign < 0 else coord[ax] < n - 1)
        self.mask = np.stack(mask, axis=1)
        self.indices = np.stack(cols, axis=1)[self.mask].astype(np.int32)
        self.indptr = np.concatenate(
            [[0], np.cumsum(self.mask.sum(axis=1))]).astype(np.int32)
        self.shape = (N, N)

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def csr(self, data: np.ndarray) -> sp.csr_matrix:
        return sp.csr_matrix((data, self.indices, self.indptr),
                             shape=self.shape)

    def values(self, k: "np.ndarray | None") -> np.ndarray:
        """The CSR data of -div(k grad) for a per-cell field ``k`` of
        shape (n, n, n) indexed [z, y, x]; ``None`` is k = 1."""
        n = self.n
        if k is None:
            k = np.ones((n, n, n))
        faces = {}
        for axis, ax in ((2, "x"), (1, "y"), (0, "z")):
            a = np.moveaxis(k, axis, -1)
            inner = 2.0 * a[..., :-1] * a[..., 1:] / (a[..., :-1]
                                                      + a[..., 1:])
            f = np.concatenate([a[..., :1], inner, a[..., -1:]], axis=-1)
            faces[ax] = np.moveaxis(f, -1, axis)
        lo = {"x": faces["x"][:, :, :-1], "y": faces["y"][:, :-1, :],
              "z": faces["z"][:-1, :, :]}
        hi = {"x": faces["x"][:, :, 1:], "y": faces["y"][:, 1:, :],
              "z": faces["z"][1:, :, :]}
        ell = np.empty((n ** 3, len(_SLOTS)))
        for j, s in enumerate(_SLOTS):
            if s == "0":
                ell[:, j] = sum(lo[a] + hi[a] for a in "xyz").ravel()
            else:
                ell[:, j] = -(lo if s[0] == "-" else hi)[s[1]].ravel()
        return ell[self.mask]


def operator(n: int) -> sp.csr_matrix:
    """The constant-coefficient f64 operator on n^3."""
    p = Pattern(n)
    return p.csr(p.values(None))


def lognormal_field(n: int, sigma: float, seed: int, index: int):
    """Per-cell coefficients exp(sigma * N(0, 1)), one draw per cell,
    from stream (seed, 1, index)."""
    g = rng(seed, 1, index)
    return np.exp(sigma * g.standard_normal((n, n, n)))


def rhs_pool(n_rows: int, size: int, seed: int) -> list:
    """``size`` distinct standard-normal right-hand sides, from stream
    (seed, 0, j)."""
    return [rng(seed, 0, j).standard_normal(n_rows) for j in range(size)]


def relres(A: sp.csr_matrix, x, b: np.ndarray) -> float:
    """||b - A x|| / ||b|| in f64."""
    x = np.asarray(x, dtype=np.float64).ravel()
    return float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))


class System:
    """One run's inputs, drawn from its seed, and the operators its
    answers are checked against.

    ``rhs`` is the pool of right-hand sides the loop uses round-robin.
    ``values`` is None for the constant operator, or the pool of value
    sets (CSR data on ``pattern``) that a re-setup mix cycles through.
    """

    def __init__(self, n: int, seed: int, rhs_pool_size: int,
                 field: "dict | None" = None, value_pool: int = 0):
        self.n = n
        self.n_rows = n ** 3
        self.rhs = rhs_pool(self.n_rows, rhs_pool_size, seed)
        self.values = None
        if field is not None:
            if field.get("kind") != "lognormal":
                raise ValueError(f"unknown coefficient field {field!r}")
            self.values = [
                self.pattern.values(lognormal_field(n, field["sigma"],
                                                    seed, j))
                for j in range(value_pool)]

    @functools.cached_property
    def pattern(self) -> Pattern:
        return Pattern(self.n)

    def host_csr(self, index: "int | None" = None) -> sp.csr_matrix:
        """The f64 operator of value set ``index`` (None: constant)."""
        data = (self.pattern.values(None) if index is None
                else self.values[index].copy())
        return self.pattern.csr(data)

    def reference(self, index: "int | None" = None) -> sp.csr_matrix:
        """The operator an answer of value set ``index`` is checked
        against; built anew from the seed's values, never from what the
        program holds."""
        return self.host_csr(index)


def build(conf: dict, traffic: dict, seed: int, n: int) -> System:
    return System(n, seed, int(traffic["rhs_pool"]),
                  field=traffic.get("coefficients"),
                  value_pool=int(traffic.get("value_pool", 0)))
