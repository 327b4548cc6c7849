"""Coarse aggregate numbering (amg/hierarchy.py
``_relabel_aggregation_levels``): each single-device aggregation level's
coarse unknowns are numbered by their first fine row, after the chain is
built with the selectors' own numbering — the same partitions, renumbered
operators with a narrow band."""
import numpy as np
import pytest
import scipy.sparse as sp
from scipy.spatial import cKDTree

import amgx_tpu as amgx
from amgx_tpu import telemetry
from amgx_tpu.amg.aggregation.galerkin import galerkin_coarse
from amgx_tpu.amg.aggregation.selectors import create_selector
from amgx_tpu.config import AMGConfig
from amgx_tpu.io import poisson7pt

#: the benchmark cell's stack (benchmark/configs/poisson7-agg.json)
CELL_CFG = (
    "config_version=2, solver(main)=FGMRES, main:max_iters=100, "
    "main:monitor_residual=1, main:tolerance=1e-10, "
    "main:convergence=RELATIVE_INI, main:norm=L2, main:use_scalar_norm=1, "
    "main:gmres_n_restart=10, main:preconditioner(amg)=AMG, "
    "amg:algorithm=AGGREGATION, amg:selector=SIZE_2, amg:max_iters=1, "
    "amg:cycle=V, amg:max_levels=50, amg:min_coarse_rows=32, "
    "amg:smoother=MULTICOLOR_DILU, amg:presweeps=0, amg:postsweeps=3, "
    "amg:relaxation_factor=0.75, "
    "amg:matrix_coloring_scheme=PARALLEL_GREEDY, amg:error_scaling=0, "
    "amg:coarse_solver=DENSE_LU_SOLVER")


def _scattered_mesh(n_pts=6000, seed=3):
    """An irregular SPD operator with mesh-like locality: a graph
    Laplacian (+ a small shift) over random points in the unit square,
    joined within a radius, rows in x-major order of 40 y-strips."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n_pts, 2))
    pts = pts[np.lexsort((pts[:, 0], np.floor(pts[:, 1] * 40)))]
    pairs = cKDTree(pts).query_pairs(0.022, output_type="ndarray")
    w = rng.uniform(0.5, 2.0, len(pairs))
    i, j = pairs[:, 0], pairs[:, 1]
    W = sp.csr_matrix((np.r_[w, w], (np.r_[i, j], np.r_[j, i])),
                      shape=(n_pts, n_pts))
    L = sp.diags(np.asarray(W.sum(axis=1)).ravel() + 1e-2) - W
    return sp.csr_matrix(L)


def _bandwidth(M):
    M = sp.csr_matrix(M)
    rows = np.repeat(np.arange(M.shape[0]), np.diff(M.indptr))
    return int(np.abs(rows - M.indices).max())


def _same_partition(p, q):
    """Two labelings of one index set give the same partition."""
    pairs = np.unique(np.stack([p, q]), axis=1).shape[1]
    return pairs == len(np.unique(p)) == len(np.unique(q))


def _setup(A, cfg=CELL_CFG):
    slv = amgx.create_solver(AMGConfig(cfg))
    slv.setup(amgx.Matrix(A))
    return slv, slv.preconditioner.hierarchy


@pytest.mark.parametrize("name", ["poisson7_24", "scattered_mesh"])
def test_relabel_keeps_partitions_and_narrows_bands(name):
    A = sp.csr_matrix(poisson7pt(24, 24, 24)) if name == "poisson7_24" \
        else _scattered_mesh()
    slv, h = _setup(A)
    assert len(h.levels) >= 4
    assert all(lvl.kind == "aggregation" for lvl in h.levels)
    # the selector's raw chain, built with its own numbering
    sel = create_selector("SIZE_2", AMGConfig(CELL_CFG), "amg")
    raw = sp.csr_matrix(A)
    fine_of_raw = np.arange(A.shape[0])   # level-0 row → raw level id
    fine_of_new = np.arange(A.shape[0])   # level-0 row → renumbered id
    for i, lvl in enumerate(h.levels):
        agg_raw = sel.select(raw)
        agg = np.asarray(lvl.aggregates)
        # (1) the same partition, composed down to the fine grid
        fine_of_raw = agg_raw[fine_of_raw]
        fine_of_new = agg[fine_of_new]
        assert _same_partition(fine_of_raw, fine_of_new), i
        # (2) coarse ids in the order of each aggregate's first member
        ids, first = np.unique(agg, return_index=True)
        np.testing.assert_array_equal(ids, np.arange(lvl.n_coarse))
        assert (np.diff(first) > 0).all(), i
        raw = galerkin_coarse(raw, agg_raw)
        nxt = h.levels[i + 1].A if i + 1 < len(h.levels) else h.coarsest
        # the renumbered operator is Π A Πᵀ of the selector's one
        order = np.empty(raw.shape[0], dtype=np.int64)
        order[fine_of_new] = fine_of_raw
        want = raw[order][:, order]
        assert abs(sp.csr_matrix(nxt.host) - want).max() <= \
            1e-12 * abs(want).max()
    # (3) levels 1-3 are banded: max |i - j| within half the rows
    for lvl in h.levels[1:4]:
        n = lvl.A.n_block_rows
        assert _bandwidth(lvl.A.host) <= n // 2, (n, _bandwidth(lvl.A.host))


@pytest.mark.parametrize("reuse", [0, 100])
def test_relabelled_solve_and_resetup(reuse):
    A = sp.csr_matrix(poisson7pt(24, 24, 24))
    cfg = CELL_CFG + f", amg:structure_reuse_levels={reuse}"
    slv, h = _setup(A, cfg)
    b = np.random.default_rng(7).standard_normal(A.shape[0])

    def solve():
        res = slv.solve(b, zero_initial_guess=True)
        x = np.asarray(res.x)
        assert res.status == amgx.SolveStatus.SUCCESS
        assert np.linalg.norm(b - A @ x) / np.linalg.norm(b) <= 1e-10
        return res.iterations

    it1 = solve()
    ops = [sp.csr_matrix(lvl.A.host) for lvl in h.levels[1:]]
    aggs = [np.asarray(lvl.aggregates) for lvl in h.levels]
    slv.resetup(amgx.Matrix(A))
    h = slv.preconditioner.hierarchy
    # a re-setup rebuilds the renumbered operators, reused or fresh
    for lvl, agg, op in zip(h.levels, aggs, [None] + ops):
        np.testing.assert_array_equal(np.asarray(lvl.aggregates), agg)
        if op is not None:
            assert abs(sp.csr_matrix(lvl.A.host) - op).max() <= \
                1e-12 * abs(op).max()
    assert solve() == it1


def test_level_bandwidth_gauge():
    A = sp.csr_matrix(poisson7pt(16, 16, 16))
    with telemetry.capture() as cap:
        _, h = _setup(A)
    bw = {r["labels"]["level"]: r["value"]
          for r in cap.metric_records("amgx_level_bandwidth")}
    mats = [lvl.A for lvl in h.levels] + [h.coarsest]
    assert set(bw) == set(range(len(mats)))
    assert bw[0] == 16 * 16
    for i, m in enumerate(mats[1:], 1):
        assert bw[i] == _bandwidth(m.host), i
