"""The ``poisson7-agg-dd4`` deployment on the CPU: AmgX's MPI Poisson
example split into z-slabs, one rank a device, solved with the
FGMRES_AGGREGATION stack of ``benchmark/configs/poisson7-agg-dd4.json``,
on 4 of the 8 virtual devices and against a plain f64 reference.

The operator goes the normal path of the benchmark's cell: the host CSR,
``Matrix.set_distribution(make_mesh(4))``, then ``create_solver(...)
.setup/solve``.  At 16³ the four slabs are whole planes; at 17³ (4,913
rows) the equal row split cuts planes, so the slabs are uneven.
"""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import amgx_tpu as amgx
from amgx_tpu.distributed.matrix import (halo_stats, make_mesh,
                                         shard_matrix, shard_vector,
                                         uses_all_gather)
from amgx_tpu.errors import SolveStatus
from amgx_tpu.ops.spmv import spmv
from amgx_tpu.telemetry.costmodel import halo_wire_bytes

CONF = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                   / "configs" / "poisson7-agg-dd4.json").read_text())
TOL = float(CONF["tolerance"])


def kron_poisson(n: int) -> sp.csr_matrix:
    """The 7-point Dirichlet Poisson on n³ as a Kronecker sum of the 1-D
    second difference: a construction independent of the package's
    generators."""
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    i = sp.identity(n)
    return (sp.kron(sp.kron(t, i), i) + sp.kron(sp.kron(i, t), i)
            + sp.kron(sp.kron(i, i), t)).tocsr()


def condition_number(n: int) -> float:
    """κ₂ of ``kron_poisson(n)``: its eigenvalues are sums of three
    2 - 2cos(kπ/(n+1)), k = 1..n."""
    lo = 2.0 - 2.0 * np.cos(np.pi / (n + 1))
    hi = 2.0 - 2.0 * np.cos(n * np.pi / (n + 1))
    return hi / lo


def relres(A, x, b) -> float:
    x = np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))


def solve(A, b, mesh=None):
    m = amgx.Matrix(A)
    m.device_dtype = np.dtype(CONF["dtype"])
    if mesh is not None:
        m.set_distribution(mesh)
    slv = amgx.create_solver(amgx.AMGConfig(CONF["solver_config"]))
    slv.setup(m)
    res = slv.solve(b, zero_initial_guess=True)
    return slv, res, np.asarray(res.x, dtype=np.float64)


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


@pytest.mark.parametrize("n,seed", [(16, 2718282811), (17, 3000000019)])
def test_dd4_solve_meets_tolerance_and_agrees_with_one_device(mesh4, n,
                                                              seed):
    A = kron_poisson(n)
    b = np.random.default_rng(seed).standard_normal(n ** 3)
    before = halo_stats()
    slv, res, x = solve(A, b, mesh4)
    assert res.status == SolveStatus.SUCCESS
    r_dist = relres(A, x, b)
    assert r_dist <= TOL
    assert halo_stats()["exchanges"] > before["exchanges"]
    # every level of the per-rank hierarchy is sharded over the 4 chips
    # and names the interior path its SpMV takes
    lay = slv.preconditioner.hierarchy.dist_levels()
    assert lay and all(d["n_parts"] == 4 and d["interior"] in
                       ("window", "gather") for d in lay.values())
    assert "sharded/" in slv.preconditioner.grid_stats()

    # the same configuration on one device exchanges no halo
    before = halo_stats()
    _, res1, x1 = solve(A, b)
    assert halo_stats() == before
    assert res1.status == SolveStatus.SUCCESS
    r_one = relres(A, x1, b)
    assert r_one <= TOL
    # Both answers solve A x = b to a relative residual r, so each lies
    # within κ(A)·r of the exact solution (relative forward error); the
    # two differ by at most κ(A)·(r_dist + r_one).  The hierarchies
    # differ (per-rank aggregation and local-block DILU against global
    # ones), so nothing tighter than what the tolerance buys holds.
    err = np.linalg.norm(x - x1) / np.linalg.norm(x1)
    assert err <= condition_number(n) * (r_dist + r_one)


def _dense_links(n_rows: int) -> sp.csr_matrix:
    """A random sparse matrix whose ranks all couple to each other: the
    exchange takes the all-gather fallback."""
    A = sp.random(n_rows, n_rows, density=0.05,
                  random_state=np.random.RandomState(3), format="csr")
    return sp.csr_matrix(A + sp.identity(n_rows) * 5)


def _collectives(hlo: str):
    """(op, metadata op_name) of each halo collective in compiled HLO;
    an asynchronous one counts once, by its ``-start``."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= [^=]*? (collective-permute|all-gather)"
                      r"(?:-start)?\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("graph", ["slabs", "dense"])
def test_halo_stats_count_the_traced_exchanges(mesh4, graph):
    A = kron_poisson(12) if graph == "slabs" else _dense_links(96)
    sm = shard_matrix(A, mesh4, dtype=np.float32)
    gather = uses_all_gather(sm.dists, sm.n_parts)
    assert gather == (graph == "dense")
    x = shard_vector(sm, np.ones(A.shape[0]))
    f = jax.jit(lambda v: spmv(sm, v))
    before = halo_stats()
    compiled = f.lower(x).compile()
    traced = halo_stats()
    hops = 1 if gather else len(sm.dists)
    assert traced["exchanges"] - before["exchanges"] == 1
    assert traced["hops"] - before["hops"] == hops
    assert traced["wire_bytes"] - before["wire_bytes"] == \
        halo_wire_bytes(sm, 1)
    assert traced["by_op"]["dist_spmv/ring1"]["exchanges"] >= 1
    # the compiled program holds the counted collectives, each under the
    # SpMV's halo-exchange scope
    coll = _collectives(compiled.as_text())
    assert [c[0] for c in coll] == \
        ["all-gather" if gather else "collective-permute"] * hops
    assert all("amgx/dist/halo_exchange" in c[1] for c in coll)
    # running the program counts nothing: the count is the trace's
    jax.block_until_ready(compiled(x))
    jax.block_until_ready(f(x))
    assert halo_stats() == traced


def test_lowered_dd4_solve_scopes_its_halo_exchange(mesh4):
    """The whole refined solve program of the cell's configuration (the
    f64 rung over the f32 sharded pack) carries the SpMV's halo scope
    in the op metadata of each of its ``ppermute``s, and the boundary
    correction's scope beside it."""
    A = kron_poisson(12)
    b = np.random.default_rng(5).standard_normal(A.shape[0])
    slv, res, x = solve(A, b, mesh4)
    assert relres(A, x, b) <= TOL
    wide, fn = slv._refined_fn
    Ad = slv.Ad
    b_hi = b.astype(np.float32)
    b_lo = (b - b_hi.astype(np.float64)).astype(np.float32)
    hlo = fn.lower(slv._bindings.collect(), shard_vector(Ad, b_hi),
                   shard_vector(Ad, b_lo), None, None,
                   jnp.asarray(slv.tolerance, jnp.dtype(wide.name)),
                   jnp.asarray(slv.max_iters, jnp.int32)
                   ).compile().as_text()
    perms = [c for c in _collectives(hlo) if c[0] == "collective-permute"]
    assert perms
    assert all("amgx/dist/halo_exchange" in name for _, name in perms)
    assert "amgx/dist/boundary" in hlo


def _rank_rows(h, m):
    """(row offsets, per-rank row blocks with global columns) of one
    distributed level operator."""
    offsets = np.asarray(m.device().offsets)
    return offsets, h._rank_blocks(m, offsets)


@pytest.mark.parametrize("reuse", [0, 100])
@pytest.mark.parametrize("n", [16, 17])
def test_dd4_coarse_ids_in_first_member_order(mesh4, n, reuse):
    """Each rank's coarse aggregates are numbered by their first fine
    row inside the rank's own coarse range: the selector's partition and
    count, and coarse blocks that are the per-rank Galerkin product of
    the renumbered aggregates; the solve meets the tolerance before and
    after a re-setup."""
    from amgx_tpu.amg.aggregation.galerkin import galerkin_coarse
    from amgx_tpu.amg.aggregation.selectors import create_selector
    A = kron_poisson(n)
    b = np.random.default_rng(n).standard_normal(n ** 3)
    m = amgx.Matrix(A)
    m.device_dtype = np.dtype(CONF["dtype"])
    m.set_distribution(mesh4)
    cfg = amgx.AMGConfig(CONF["solver_config"]
                         + f", amg:structure_reuse_levels={reuse}")
    slv = amgx.create_solver(cfg)
    slv.setup(m)

    def solve():
        res = slv.solve(b, zero_initial_guess=True)
        assert res.status == SolveStatus.SUCCESS
        assert relres(A, np.asarray(res.x), b) <= TOL

    solve()
    h = slv.preconditioner.hierarchy
    sel = create_selector("SIZE_2", cfg, "amg")
    dist = [i for i, s in enumerate(h._structure)
            if s[0] == "aggregation-dist"]
    assert dist == list(range(len(h.levels)))
    aggs = []
    for i in dist:
        agg, nc = h._structure[i][1]
        aggs.append(agg.copy())
        offsets, blocks = _rank_rows(h, h.levels[i].A)
        base = 0
        for p in range(4):
            lo, hi = offsets[p], offsets[p + 1]
            raw = sel.select(sp.csr_matrix(blocks[p][:, lo:hi]))
            cnt = int(raw.max()) + 1
            ids = agg[lo:hi] - base
            # the rank's own range, the selector's count and partition
            assert ids.min() == 0 and ids.max() == cnt - 1, (i, p)
            assert len(np.unique(np.stack([raw, ids]), axis=1).T) == cnt
            base += cnt
        assert base == nc
        # coarse ids in the order of each aggregate's first fine row
        ids, first = np.unique(agg, return_index=True)
        np.testing.assert_array_equal(ids, np.arange(nc))
        assert (np.diff(first) > 0).all(), i
        nxt = h.levels[i + 1].A if i + 1 < len(h.levels) else h.coarsest
        want = galerkin_coarse(sp.csr_matrix(sp.vstack(blocks)), agg)
        got = sp.csr_matrix(sp.vstack(_rank_rows(h, nxt)[1]))
        assert abs(got - want).max() <= 1e-12 * abs(want).max(), i

    slv.resetup(m)
    h = slv.preconditioner.hierarchy
    for i, agg in zip(dist, aggs):
        np.testing.assert_array_equal(h._structure[i][1][0], agg)
    solve()
