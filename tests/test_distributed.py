"""Distributed-layer tests on an 8-device virtual CPU mesh.

Reference analog: in-process partition simulation
(``base/tests/generated_matrix_distributed_io.cu:58-97``) + the MPI example
flows (``examples/amgx_mpi_poisson7.c``) — SURVEY §4.4.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from jax.sharding import PartitionSpec as P

import amgx_tpu as amgx
from amgx_tpu.distributed.matrix import (dist_spmv, shard_matrix,
                                         shard_vector, unshard_vector,
                                         embed_padded, pad_map)
from amgx_tpu.distributed.partition import (build_partition,
                                            partition_offsets_from_vector)
from amgx_tpu.io import generate_distributed_poisson_7pt, poisson5pt, poisson7pt


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((8,), ("p",))


def test_partition_halo_maps():
    A = sp.csr_matrix(poisson5pt(8, 8))
    part = build_partition(A, 4)
    assert part.n_parts == 4
    assert part.n_loc == 16
    # 1D split of a 2D grid: stencil partition → ring neighbours
    assert part.ring_neighbors_only
    for p in range(4):
        nb = part.neighbors[p]
        assert all(abs(q - p) == 1 for q in nb)
    # halo of rank 1 = last row of rank 0's grid + first row of rank 2's
    assert part.halo_count[1] == 16


def test_partition_vector_offsets():
    pv = np.repeat([0, 1, 2], 5)
    off = partition_offsets_from_vector(pv, 3)
    np.testing.assert_array_equal(off, [0, 5, 10, 15])
    with pytest.raises(Exception):
        partition_offsets_from_vector(np.array([1, 0, 1]), 2)


def test_dist_spmv_matches_serial(mesh, rng):
    A = sp.csr_matrix(poisson7pt(8, 8, 8))
    sm = shard_matrix(A, mesh)
    x = rng.standard_normal(A.shape[0])
    xs = shard_vector(sm, x)
    y = jax.jit(lambda v: dist_spmv(sm, v))(xs)
    y_real = unshard_vector(sm, y)
    np.testing.assert_allclose(y_real, A @ x, rtol=1e-12)


def test_dist_spmv_nonuniform_offsets(mesh, rng):
    A = sp.csr_matrix(poisson5pt(10, 10))
    offsets = np.array([0, 13, 26, 39, 52, 65, 78, 91, 100])
    sm = shard_matrix(A, mesh, offsets=offsets)
    x = rng.standard_normal(100)
    y = unshard_vector(sm, jax.jit(lambda v: dist_spmv(sm, v))(
        shard_vector(sm, x)))
    np.testing.assert_allclose(y, A @ x, rtol=1e-12)


def test_dist_spmv_general_graph(mesh, rng):
    # random sparse matrix → non-ring neighbours → all_gather path
    A = sp.random(96, 96, density=0.05,
                  random_state=np.random.RandomState(3), format="csr")
    A = sp.csr_matrix(A + sp.identity(96) * 5)
    sm = shard_matrix(A, mesh)
    # dense link graph → the exchange falls back to one all_gather
    assert len(sm.dists) >= sm.n_parts - 1
    x = rng.standard_normal(96)
    y = unshard_vector(sm, jax.jit(lambda v: dist_spmv(sm, v))(
        shard_vector(sm, x)))
    np.testing.assert_allclose(y, A @ x, rtol=1e-12)


def test_embed_padded_roundtrip(rng):
    M = sp.random(10, 6, density=0.4, random_state=np.random.RandomState(5),
                  format="csr")
    r_off = np.array([0, 3, 7, 10])
    c_off = np.array([0, 2, 4, 6])
    Mp = embed_padded(M, r_off, 5, c_off, 3)
    assert Mp.shape == (15, 9)
    rm, cm = pad_map(r_off, 5), pad_map(c_off, 3)
    np.testing.assert_allclose(Mp[np.ix_(rm, cm)].toarray(), M.toarray())


def test_distributed_pcg(mesh):
    A = poisson7pt(12, 12, 12)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(s)=PCG, s:preconditioner(p)=BLOCK_JACOBI, "
        "p:max_iters=3, s:max_iters=300, s:monitor_residual=1, "
        "s:tolerance=1e-8, s:convergence=RELATIVE_INI")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    res = slv.solve(b)
    x = np.asarray(res.x)
    assert x.shape[0] == A.shape[0]
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7
    assert res.status == amgx.SolveStatus.SUCCESS


def test_distributed_matches_single_device(mesh):
    # equivalence oracle (reference style): distributed result ≡ serial
    A = poisson5pt(16, 16)
    b = np.sin(np.arange(A.shape[0]))
    cfgs = ("config_version=2, solver(s)=PCG, s:max_iters=50, "
            "s:monitor_residual=1, s:tolerance=1e-10, "
            "s:convergence=RELATIVE_INI")
    slv1 = amgx.create_solver(amgx.AMGConfig(cfgs))
    slv1.setup(amgx.Matrix(A))
    x1 = np.asarray(slv1.solve(b).x)
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    slv2 = amgx.create_solver(amgx.AMGConfig(cfgs))
    slv2.setup(m)
    x2 = np.asarray(slv2.solve(b).x)
    np.testing.assert_allclose(x1, x2, rtol=1e-8, atol=1e-10)


def test_distributed_fgmres_agg_amg(mesh):
    # the headline distributed config: FGMRES + aggregation AMG over the
    # mesh (amgx_mpi_poisson7 analog, BASELINE config 3)
    A, pv = generate_distributed_poisson_7pt(6, 6, 6, px=2, py=2, pz=2)
    offsets = partition_offsets_from_vector(pv, 8)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.set_distribution(mesh, offsets=offsets)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=FGMRES, out:max_iters=100, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:gmres_n_restart=20, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=12, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, amg:presweeps=1, "
        "amg:postsweeps=2, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    res = slv.solve(b)
    x = np.asarray(res.x)
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)
    # hierarchy levels (above coarsest) carry sharded matrices
    assert slv.preconditioner.hierarchy.levels[0].Ad.fmt == "sharded-ell"


def test_distributed_classical_amg(mesh):
    A = poisson7pt(10, 10, 10)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=60, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=CLASSICAL, amg:selector=PMIS, amg:interpolator=D1, "
        "amg:max_iters=1, amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:presweeps=2, amg:postsweeps=2, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    res = slv.solve(b)
    x = np.asarray(res.x)
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)


def test_consolidation_threshold(mesh):
    # glue analog: small coarse grids migrate off the mesh
    A = poisson7pt(8, 8, 8)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, matrix_consolidation_lower_threshold=16, "
        "solver(out)=PCG, out:max_iters=60, out:monitor_residual=1, "
        "out:tolerance=1e-8, out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, amg:presweeps=2, "
        "amg:postsweeps=2, amg:min_coarse_rows=8, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    levels = slv.preconditioner.hierarchy.levels
    fmts = [lvl.Ad.fmt for lvl in levels]
    assert fmts[0] == "sharded-ell"
    assert any(f != "sharded-ell" for f in fmts[1:]), fmts  # consolidated
    res = slv.solve(b)
    x = np.asarray(res.x)
    assert np.linalg.norm(b - A @ x) / np.linalg.norm(b) < 1e-7


def test_two_ring_halo_maps_and_exchange(mesh, rng):
    """Ring-2 maps deliver next-nearest-neighbour values exactly
    (reference HALO2 / B2L_rings, distributed_manager.h:284-305)."""
    from amgx_tpu.distributed.matrix import exchange_halo
    A = sp.csr_matrix(poisson7pt(8, 8, 8))       # 8 z-planes → 8 shards
    part = build_partition(A, 8, n_rings=2)
    # ring 2 of an interior rank is the z±2 planes
    r2 = part.rings[1]
    assert r2.halo_count[3] == 128               # two 64-row planes
    sm = shard_matrix(A, mesh)
    x = rng.standard_normal(512)
    xs = shard_vector(sm, x)
    for ring in (1, 2):
        got = np.asarray(jax.jit(
            lambda v: exchange_halo(sm, v, ring=ring))(xs))
        ringmaps = part.rings[ring - 1]
        for p in range(8):
            cnt = int(ringmaps.halo_count[p])
            want = x[ringmaps.halo_global[p]]
            np.testing.assert_allclose(got[p, :cnt], want, rtol=1e-12)


def test_dist_spmv_multi_distance_schedule(mesh, rng):
    """Long-range couplings exercise the distance-wise ppermute schedule
    (more than one distance, fewer than an all-gather)."""
    n = 512
    diag = sp.diags([np.full(n, 8.0)], [0])
    near = sp.diags([np.ones(n - 1), np.ones(n - 1)], [-1, 1])
    far = sp.diags([np.ones(n - 192), np.ones(n - 192)], [-192, 192])
    A = sp.csr_matrix(diag + near + far)         # n_loc=64 → dist 3 links
    sm = shard_matrix(A, mesh)
    assert 1 < len(sm.dists) < sm.n_parts - 1, sm.dists
    x = rng.standard_normal(n)
    y = unshard_vector(sm, jax.jit(lambda v: dist_spmv(sm, v))(
        shard_vector(sm, x)))
    np.testing.assert_allclose(y, A @ x, rtol=1e-12)


def _poisson_blocks(nx, ny, nz, n_parts):
    """Per-rank row blocks of a global Poisson WITHOUT keeping the global
    (test builds it once as the oracle only)."""
    A = sp.csr_matrix(poisson7pt(nx, ny, nz))
    n = A.shape[0]
    nl = -(-n // n_parts)
    offsets = np.minimum(np.arange(n_parts + 1) * nl, n)
    blocks = [sp.csr_matrix(A[offsets[p]:offsets[p + 1]])
              for p in range(n_parts)]
    return A, blocks, offsets


def test_block_upload_solve_matches_global(mesh):
    """set_distributed_blocks: scalable upload (no global CSR) solves the
    same system to the same answer as the global-upload path."""
    A, blocks, offsets = _poisson_blocks(16, 16, 16, 8)
    b = np.sin(np.arange(A.shape[0]))
    cfgs = ("config_version=2, solver(out)=FGMRES, out:max_iters=100, "
            "out:monitor_residual=1, out:tolerance=1e-8, "
            "out:convergence=RELATIVE_INI, out:gmres_n_restart=20, "
            "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
            "amg:selector=SIZE_2, amg:max_iters=1, amg:max_levels=12, "
            "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
            "amg:presweeps=1, amg:postsweeps=2, amg:min_coarse_rows=16, "
            "amg:coarse_solver=DENSE_LU_SOLVER")
    m = amgx.Matrix()
    m.set_distributed_blocks(blocks, offsets, mesh)
    assert m.host is None
    with pytest.raises(Exception):
        m.scalar_csr()          # the scalable contract, enforced
    slv = amgx.create_solver(amgx.AMGConfig(cfgs))
    slv.setup(m)
    # hierarchy coarse levels stay block-distributed (no global assembly)
    lvl1_A = slv.preconditioner.hierarchy.levels[1].A
    assert lvl1_A.blocks is not None and lvl1_A.host is None
    res = slv.solve(b)
    x = np.asarray(res.x)
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)


def test_block_setup_never_assembles_large(mesh, monkeypatch):
    """The scalable-setup memory contract: nothing bigger than a
    consolidated coarse grid is ever assembled globally."""
    A, blocks, offsets = _poisson_blocks(12, 12, 12, 8)
    assembled = []
    orig = amgx.Matrix.assemble_global

    def spy(self):
        assembled.append(self.shape[0])
        return orig(self)

    monkeypatch.setattr(amgx.Matrix, "assemble_global", spy)
    m = amgx.Matrix()
    m.set_distributed_blocks(blocks, offsets, mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=100, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=AGGREGATION, amg:selector=SIZE_2, amg:max_iters=1, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, amg:presweeps=1, "
        "amg:postsweeps=2, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    n = A.shape[0]
    assert assembled, "coarsest-level consolidation expected"
    assert max(assembled) <= n // 4, assembled


def test_submesh_consolidation(mesh):
    """Glue analog: a too-small coarse grid migrates onto a sub-mesh
    (fewer active ranks) before full replication (glue.h:73-263)."""
    A, blocks, offsets = _poisson_blocks(12, 12, 12, 8)
    b = np.ones(A.shape[0])
    m = amgx.Matrix()
    m.set_distributed_blocks(blocks, offsets, mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, matrix_consolidation_lower_threshold=200, "
        "matrix_consolidation_upper_threshold=300, "
        "solver(out)=PCG, out:max_iters=100, out:monitor_residual=1, "
        "out:tolerance=1e-8, out:convergence=RELATIVE_INI, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=SIZE_2, amg:max_iters=1, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, amg:presweeps=1, "
        "amg:postsweeps=2, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    # level-1 coarse (1728 → ~864 rows, 108/rank < 200) must sit on a
    # sub-mesh: ceil(864/300) = 3 active ranks
    lvls = slv.preconditioner.hierarchy.levels
    c_off = np.asarray(lvls[1].A.dist[2])
    active = int(np.sum(np.diff(c_off) > 0))
    assert 1 < active < 8, c_off
    res = slv.solve(b)
    x = np.asarray(res.x)
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)


@pytest.mark.parametrize("smoother", ["MULTICOLOR_DILU", "MULTICOLOR_GS"])
def test_distributed_amg_with_colored_smoothers(mesh, smoother):
    """Colored smoothers must work on block-distributed coarse levels
    (regression: scalar_csr raise propagated into smoother setup)."""
    A = poisson7pt(10, 10, 10)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=FGMRES, out:max_iters=100, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=AGGREGATION, amg:selector=SIZE_2, amg:max_iters=1, "
        f"amg:smoother(sm)={smoother}, sm:max_iters=1, amg:presweeps=1, "
        "amg:postsweeps=1, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    res = slv.solve(b)
    x = np.asarray(res.x)
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)


# ---------------------------------------------------------------------------
# per-rank distributed classical AMG (classical_amg_level.cu:240-340)
# ---------------------------------------------------------------------------
_CLA_DIST_CFG = (
    "config_version=2, solver(out)=PCG, out:max_iters=60, "
    "out:monitor_residual=1, out:tolerance=1e-10, "
    "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
    "amg:algorithm=CLASSICAL, amg:selector=PMIS, amg:interpolator={interp}, "
    "amg:max_iters=1, amg:interp_max_elements=4, amg:max_row_sum=0.9, "
    "amg:max_levels=6, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1, "
    "amg:presweeps=1, amg:postsweeps=1, amg:min_coarse_rows=8, "
    "amg:coarse_solver=DENSE_LU_SOLVER, determinism_flag=1")


@pytest.mark.parametrize("interp", ["D1", "D2"])
def test_distributed_classical_per_rank_matches_single(mesh, interp):
    """Per-rank classical setup (strength/PMIS/interp/RAP from rank
    blocks + halo rows only) reproduces the single-device hierarchy and
    solve trajectory."""
    A = poisson7pt(12, 12, 12)
    n = A.shape[0]
    b = np.ones(n)
    cfg = _CLA_DIST_CFG.format(interp=interp)

    slv1 = amgx.create_solver(amgx.AMGConfig(cfg))
    slv1.setup(amgx.Matrix(A))
    res1 = slv1.solve(b)
    x1 = np.asarray(res1.x)

    m2 = amgx.Matrix(A)
    m2.set_distribution(mesh)
    slv2 = amgx.create_solver(amgx.AMGConfig(cfg))
    slv2.setup(m2)
    kinds = [s[0] for s in slv2.preconditioner.hierarchy._structure]
    assert all(k == "classical-dist" for k in kinds), kinds
    bd = shard_vector(m2.device(), b)
    res2 = slv2.solve(bd)
    x2 = unshard_vector(m2.device(), np.asarray(res2.x))
    assert int(res2.iterations) == int(res1.iterations)
    assert np.allclose(x1, x2, rtol=1e-8, atol=1e-8)


def test_distributed_classical_never_assembles_global(mesh, monkeypatch):
    """Scalable contract for the classical path: setup from per-rank
    blocks touches no global matrix (the aggregation path's guarantee,
    now extended to classical — distributed_arranger.h:223-231)."""
    A, blocks, offsets = _poisson_blocks(12, 12, 12, 8)
    n = A.shape[0]
    assembled = []
    orig = amgx.Matrix.assemble_global

    def spy(self):
        assembled.append(self.shape[0])
        return orig(self)

    monkeypatch.setattr(amgx.Matrix, "assemble_global", spy)
    m = amgx.Matrix()
    m.set_distributed_blocks(blocks, offsets, mesh)
    slv = amgx.create_solver(amgx.AMGConfig(_CLA_DIST_CFG.format(
        interp="D2")))
    slv.setup(m)   # would raise via scalar_csr() on a global view
    b = np.ones(n)
    bd = shard_vector(m.device(), b)
    res = slv.solve(bd)
    x = unshard_vector(m.device(), np.asarray(res.x))
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-8, (relres, res.iterations)
    # only coarsest-level consolidation (dense LU) may assemble, and
    # only at a fraction of the fine size
    assert not assembled or max(assembled) <= n // 4, assembled


def test_ring2_feeds_distance2_interpolation(mesh):
    """The ring-2 maps have a real consumer: each rank's extended block
    spans [local | ring1 | ring2], and D2 interpolation reads ring-2
    columns through it."""
    from amgx_tpu.amg.classical.distributed import RankExtended
    from amgx_tpu.distributed.partition import (
        build_partition_from_blocks, split_row_blocks)
    A = sp.csr_matrix(poisson7pt(10, 10, 10))
    offsets = np.linspace(0, A.shape[0], 9).astype(np.int64)
    blocks = split_row_blocks(A, offsets)
    part = build_partition_from_blocks(blocks, offsets, n_rings=2)
    e = RankExtended(3, blocks, part)
    r1 = part.rings[0].halo_global[3]
    r2 = part.rings[1].halo_global[3]
    assert len(r2) > 0
    nU = e.n_local + len(r1) + len(r2)
    assert e.nU == nU
    # ring-1 halo ROWS are present and reach ring-2 columns
    row_counts = np.diff(e.A_U.indptr)
    assert row_counts[e.n_local:e.n_local + len(r1)].min() > 0
    ring2_slots = np.arange(e.n_local + len(r1), nU)
    assert np.isin(e.A_U.indices, ring2_slots).any()


def test_interior_spmv_independent_of_collective(mesh, rng):
    """Structural latency-hiding check (multiply.cu:113-196 analog): in
    the traced dist_spmv, the interior contraction (the reduce over the
    (n_loc, K) gather/multiply) has NO data dependence on the ppermute
    collectives — XLA is therefore free to overlap the exchange with the
    interior compute.  This is the evidence behind the README's overlap
    claim (checkable single-host; real-ICI profiles need >1 chip)."""
    A = sp.csr_matrix(poisson7pt(8, 8, 8))
    Ad = shard_matrix(A, mesh)
    x = shard_vector(Ad, rng.standard_normal(A.shape[0]))
    jaxpr = jax.make_jaxpr(lambda v: dist_spmv(Ad, v))(x)

    tainted = set()
    n_ppermute = 0
    interior_reduces = []

    def walk(jx):
        nonlocal n_ppermute
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            in_tainted = any(
                not isinstance(v, jax.extend.core.Literal)
                and v in tainted for v in eqn.invars)
            if prim == "ppermute" or prim == "all_gather":
                n_ppermute += 1
                in_tainted = True
            if in_tainted:
                tainted.update(eqn.outvars)
            for p in eqn.params.values():
                if hasattr(p, "jaxpr"):
                    pass  # nested jaxprs handled below
            for name in ("jaxpr", "call_jaxpr"):
                sub = eqn.params.get(name)
                if sub is not None:
                    inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                    # propagate taint through the call boundary
                    for iv, inner_v in zip(eqn.invars, inner.invars):
                        if not isinstance(iv, jax.extend.core.Literal) \
                                and iv in tainted:
                            tainted.add(inner_v)
                    walk(inner)
                    for ov, inner_ov in zip(eqn.outvars, inner.outvars):
                        if not isinstance(
                                inner_ov, jax.extend.core.Literal) \
                                and inner_ov in tainted:
                            tainted.add(ov)
            if prim == "reduce_sum" and \
                    eqn.invars[0].aval.ndim == 2 and \
                    eqn.invars[0].aval.shape[1] == Ad.ell_width:
                interior_reduces.append(
                    not isinstance(eqn.invars[0],
                                   jax.extend.core.Literal)
                    and eqn.invars[0] in tainted)

    walk(jaxpr.jaxpr)
    assert n_ppermute > 0, "no collective found in dist_spmv trace"
    assert interior_reduces, "interior (n_loc, K) reduction not found"
    # canary that taint propagation works at all: the boundary
    # correction's reduce DOES depend on the exchange
    assert any(interior_reduces), "taint propagation found nothing"
    assert not all(interior_reduces), \
        "every (n_loc, K) reduction depends on the collective — " \
        "interior/boundary overlap is structurally impossible"


def test_distributed_kaczmarz_true_transpose_unsymmetric(mesh):
    """Distributed KACZMARZ builds the TRUE per-rank transpose pack
    (kaczmarz_solver.cu builds A^T) — on a structurally unsymmetric
    matrix the row projections must match the single-device solver,
    which a substitute-A-for-A^T shortcut would get wrong."""
    A = sp.csr_matrix(poisson5pt(8, 8)).tolil()
    A[0, 5] = 0.3          # break structural symmetry
    A[5, 0] = 0.0
    A = sp.csr_matrix(A)
    b = np.sin(np.arange(A.shape[0]))
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=KACZMARZ, out:max_iters=8, "
        "out:monitor_residual=1")
    slv1 = amgx.create_solver(cfg)
    slv1.setup(amgx.Matrix(A))
    x1 = np.asarray(slv1.solve(b).x)

    m2 = amgx.Matrix(A)
    m2.set_distribution(mesh)
    slv2 = amgx.create_solver(amgx.AMGConfig(
        "config_version=2, solver(out)=KACZMARZ, out:max_iters=8, "
        "out:monitor_residual=1"))
    slv2.setup(m2)
    assert slv2.AdT is not slv2.Ad       # a real transpose pack
    bd = shard_vector(m2.device(), b)
    x2 = unshard_vector(m2.device(), np.asarray(slv2.solve(bd).x))
    np.testing.assert_allclose(x1, x2, rtol=1e-8, atol=1e-10)


# ---------------------------------------------------------------------------
# per-color packed distributed smoothers (multicolor_dilu_solver.cu)
# ---------------------------------------------------------------------------
def _count_collectives(jaxpr) -> int:
    n = 0
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            if eqn.primitive.name in ("ppermute", "all_gather",
                                      "all_to_all", "psum"):
                n += 1
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    stack.append(sub.jaxpr if hasattr(sub.jaxpr, "eqns")
                                 else sub)
                elif hasattr(sub, "eqns"):
                    stack.append(sub)
    return n


def _sweep_ops(jaxpr):
    """Counts of the sweep's gather/scatter equations and of shard_maps,
    and the gathers and scatters that lie inside a shard_map."""
    counts = {"gather": 0, "scatter": 0, "scatter-add": 0, "shard_map": 0}
    inside = 0
    stack = [(jaxpr.jaxpr, False)]
    while stack:
        jx, in_map = stack.pop()
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in counts:
                counts[name] += 1
                inside += in_map and name != "shard_map"
            for sub in eqn.params.values():
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    stack.append((sub, in_map or name == "shard_map"))
    return counts, inside


def _dilu_apply_jaxpr(A, mesh=None):
    m = amgx.Matrix(A)
    if mesh is not None:
        m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=MULTICOLOR_DILU, out:max_iters=2, "
        "out:monitor_residual=1")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    r = np.ones(A.shape[0])
    r = shard_vector(m.device(), r) if mesh is not None else jnp.asarray(r)
    return slv, jax.make_jaxpr(slv._apply_dilu)(r)


def test_dist_dilu_slab_sweeps_no_collectives(mesh):
    """Distributed DILU sweeps are per-rank slab kernels with ZERO
    collectives (halo values are frozen at sweep start, exchanged once
    by the outer residual — multicolor_dilu_solver.cu:4167-4209); cost
    is O(nnz_shard), not O(num_colors·nnz).  Per color, each of the two
    sweeps gathers three times (the vector at the slab's columns, E⁻¹
    and the right-hand side at its rows) and writes its rows with one
    plain scatter, all inside one shard_map."""
    A = poisson7pt(8, 8, 8)
    slv, jaxpr = _dilu_apply_jaxpr(A, mesh)
    C = slv.num_colors
    assert C > 1
    assert _count_collectives(jaxpr) == 0, jaxpr
    counts, inside = _sweep_ops(jaxpr)
    assert counts == {"gather": 6 * C, "scatter": 2 * C, "scatter-add": 0,
                      "shard_map": 1}, (counts, C)
    assert inside == 8 * C, (inside, C)
    # slab storage is O(nnz_shard): total slab entries ≤ nnz + padding
    tot = sum(int(np.prod(s.vals.shape)) for s in slv.L_slabs) + \
        sum(int(np.prod(s.vals.shape)) for s in slv.U_slabs)
    assert tot <= 2 * A.nnz, (tot, A.nnz)


def test_dilu_slab_sweeps_op_counts_one_device():
    """On one device the DILU apply is the same sweep with no shard_map:
    6 gathers and 2 plain scatters per color, no scatter-add."""
    slv, jaxpr = _dilu_apply_jaxpr(poisson7pt(8, 8, 8))
    C = slv.num_colors
    assert C > 1
    counts, _ = _sweep_ops(jaxpr)
    assert counts == {"gather": 6 * C, "scatter": 2 * C, "scatter-add": 0,
                      "shard_map": 0}, (counts, C)


def test_dist_gs_one_exchange_per_sweep(mesh):
    """Distributed multicolor GS pays ONE halo exchange per sweep (not
    one per color): the traced sweep contains at most len(dists)
    ppermutes regardless of color count."""
    A = poisson7pt(8, 8, 8)
    m = amgx.Matrix(A)
    m.set_distribution(mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=MULTICOLOR_GS, out:max_iters=2, "
        "out:monitor_residual=1")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    assert slv.num_colors > 1
    assert slv.dist_slab_rows is not None
    r = shard_vector(m.device(), np.ones(A.shape[0]))
    jaxpr = jax.make_jaxpr(
        lambda b, x: slv._color_sweep(b, x, range(slv.num_colors)))(r, r)
    n_coll = _count_collectives(jaxpr)
    assert 0 < n_coll <= len(m.device().dists), (
        n_coll, slv.num_colors, m.device().dists)


@pytest.mark.parametrize("smoother", ["MULTICOLOR_DILU", "MULTICOLOR_GS"])
def test_dist_smoother_setup_from_blocks_only(mesh, monkeypatch,
                                              smoother):
    """Host-matrix-free distributed smoother setup: coloring,
    factorisation, and slabs come from per-rank blocks (no global
    assembly — distributed_manager.cu setup-from-local contract)."""
    A, blocks, offsets = _poisson_blocks(12, 12, 12, 8)
    n = A.shape[0]
    assembled = []
    orig = amgx.Matrix.assemble_global

    def spy(self):
        assembled.append(self.shape[0])
        return orig(self)

    monkeypatch.setattr(amgx.Matrix, "assemble_global", spy)
    m = amgx.Matrix()
    m.set_distributed_blocks(blocks, offsets, mesh)
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=100, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=AGGREGATION, amg:selector=SIZE_2, amg:max_iters=1, "
        f"amg:smoother(sm)={smoother}, sm:max_iters=1, amg:presweeps=1, "
        "amg:postsweeps=2, amg:min_coarse_rows=16, "
        "amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    slv.setup(m)
    b = np.ones(n)
    bd = shard_vector(m.device(), b)
    res = slv.solve(bd)
    x = unshard_vector(m.device(), np.asarray(res.x))
    relres = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
    assert relres < 1e-7, (relres, res.iterations)
    assert not assembled or max(assembled) <= n // 4, assembled


def test_distributed_block_dilu_4x4(mesh):
    """BASELINE config 4 on the mesh: 4×4 block system, BiCGStab +
    multicolor DILU — block-CSR distribution (matrix.h:87-220) with
    per-rank local-block factorisation (multicolor_dilu_solver.cu:48-112)
    and zero-collective block slab sweeps."""
    A4 = sp.kron(poisson7pt(10, 10, 10), sp.identity(4)).tocsr()
    n = A4.shape[0]
    b = np.ones(n)
    cfgs = ("config_version=2, solver(out)=PBICGSTAB, out:max_iters=200, "
            "out:monitor_residual=1, out:tolerance=1e-8, "
            "out:convergence=RELATIVE_INI, "
            "out:preconditioner(pre)=MULTICOLOR_DILU, pre:max_iters=1")
    slv1 = amgx.create_solver(amgx.AMGConfig(cfgs))
    slv1.setup(amgx.Matrix(A4, block_dim=4))
    res1 = slv1.solve(b)
    x1 = np.asarray(res1.x)
    relres1 = np.linalg.norm(b - A4 @ x1) / np.linalg.norm(b)
    assert relres1 < 1e-7

    m2 = amgx.Matrix(A4, block_dim=4)
    m2.set_distribution(mesh)
    slv2 = amgx.create_solver(amgx.AMGConfig(cfgs))
    slv2.setup(m2)
    Ad = m2.device()
    assert Ad.block_dim == 4 and Ad.fmt == "sharded-ell"
    bd_ = shard_vector(Ad, b)
    res2 = slv2.solve(bd_)
    x2 = unshard_vector(Ad, np.asarray(res2.x))
    relres2 = np.linalg.norm(b - A4 @ x2) / np.linalg.norm(b)
    assert relres2 < 1e-7, (relres2, res2.iterations)
    # local-block DILU may take a couple extra iterations vs the global
    # factorisation (the reference's distributed smoother differs the
    # same way) but must stay in the same ballpark
    assert int(res2.iterations) <= int(res1.iterations) + 8
    # sweeps stay collective-free
    pre = slv2.preconditioner
    r = shard_vector(Ad, np.ones(n))
    assert _count_collectives(jax.make_jaxpr(pre._apply_dilu)(r)) == 0


def test_distributed_block_spmv_matches_serial(mesh, rng):
    A0 = sp.csr_matrix(poisson7pt(6, 6, 6))
    bsr0 = sp.kron(A0, np.ones((4, 4))).tobsr(blocksize=(4, 4))
    bsr0.data[:] = rng.standard_normal(bsr0.data.shape)
    from amgx_tpu.distributed.matrix import shard_block_matrix
    Ad = shard_block_matrix(bsr0, 4, mesh)
    x = rng.standard_normal(bsr0.shape[0])
    y = unshard_vector(Ad, jax.jit(lambda v: dist_spmv(Ad, v))(
        shard_vector(Ad, x)))
    np.testing.assert_allclose(y, bsr0 @ x, rtol=1e-12)


def test_distributed_block_spmv_all_gather_path(rng):
    """2-rank chain: the dense-link all_gather fallback must keep the
    (B, b) block components of the exchange buffers."""
    A0 = sp.csr_matrix(poisson7pt(6, 6, 6))
    bsr0 = sp.kron(A0, np.ones((4, 4))).tobsr(blocksize=(4, 4))
    bsr0.data[:] = rng.standard_normal(bsr0.data.shape)
    from amgx_tpu.distributed.matrix import shard_block_matrix
    mesh2 = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("p",))
    Ad = shard_block_matrix(bsr0, 4, mesh2)
    assert len(Ad.dists) >= Ad.n_parts - 1    # all_gather fallback
    x = rng.standard_normal(bsr0.shape[0])
    y = unshard_vector(Ad, jax.jit(lambda v: dist_spmv(Ad, v))(
        shard_vector(Ad, x)))
    np.testing.assert_allclose(y, bsr0 @ x, rtol=1e-12)


def test_distributed_setup_memory_is_rank_local():
    """VERDICT r3 criterion: during an 8-rank classical setup, the
    distributed setup math (amg/classical/distributed.py) never
    allocates an array of global length — every buffer is sized by a
    rank's [local | ring1 | ring2] universe, and PMIS rounds exchange
    only boundary states through the HaloExchange schedule."""
    import amgx_tpu.amg.classical.distributed as dmod
    from amgx_tpu.io import poisson7pt

    A = sp.csr_matrix(poisson7pt(24, 24, 24))
    n = A.shape[0]

    class GuardedNumpy:
        """numpy proxy that rejects creations of global-length arrays."""

        _create = {"zeros", "full", "empty", "ones", "arange",
                   "where", "asarray", "repeat"}

        def __getattr__(self, name):
            real = getattr(np, name)
            if name not in self._create:
                return real

            def guard(*a, **k):
                out = real(*a, **k)
                # exact global length — the signature of the old
                # lam/state/colmap bugs; rank-local buffers (universe,
                # per-rank nnz) have different sizes by construction
                if isinstance(out, np.ndarray) and out.ndim >= 1 and \
                        len(out) in (n, n + 1):
                    raise AssertionError(
                        f"np.{name} allocated length {len(out)} ~ "
                        f"n_global={n} inside distributed setup")
                return out

            return guard

    mesh = jax.make_mesh((8,), ("p",))
    m = amgx.Matrix(A)
    m.set_distribution(mesh, "p")
    cfg = amgx.AMGConfig(
        "config_version=2, solver(out)=PCG, out:max_iters=40, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, out:preconditioner(amg)=AMG, "
        "amg:algorithm=CLASSICAL, amg:selector=PMIS, "
        "amg:interpolator=D2, amg:max_iters=1, amg:max_levels=3, "
        "amg:smoother(sm)=JACOBI_L1, sm:max_iters=1, "
        "amg:min_coarse_rows=64, amg:coarse_solver=DENSE_LU_SOLVER")
    slv = amgx.create_solver(cfg)
    real_np = dmod.np
    dmod.np = GuardedNumpy()
    try:
        slv.setup(m)
    finally:
        dmod.np = real_np
    res = slv.solve(np.ones(n))
    x = np.asarray(res.x)
    assert np.linalg.norm(np.ones(n) - A @ x) / np.sqrt(n) < 1e-7


def test_distributed_io_partition_vector_roundtrip(tmp_path):
    """VERDICT r3 Missing #6: partition-vector-driven distributed IO
    (distributed_io.cu:182-278 parity).  A NON-contiguous partition
    vector renumbers rows rank-major on read; each rank holds its own
    row block; a distributed write inverts the renumbering so the file
    round-trips in the original global ordering."""
    from amgx_tpu import capi
    from amgx_tpu.io import poisson5pt

    A = sp.csr_matrix(poisson5pt(16, 16))
    n = A.shape[0]
    rng = np.random.default_rng(9)
    b = rng.standard_normal(n)
    src = tmp_path / "sys.mtx"
    import amgx_tpu.io as aio
    aio.write_matrix_market(str(src), A, rhs=b)

    # scrambled (non-contiguous) partition vector over 8 ranks
    pv = rng.integers(0, 8, size=n)
    rc, cfg = capi.AMGX_config_create(
        "config_version=2, solver(out)=PCG, out:max_iters=200, "
        "out:monitor_residual=1, out:tolerance=1e-8, "
        "out:convergence=RELATIVE_INI, "
        "out:preconditioner(pre)=BLOCK_JACOBI, pre:max_iters=1")
    assert rc == 0
    rc, rsrc = capi.AMGX_resources_create_simple(cfg)
    rc, mtx = capi.AMGX_matrix_create(rsrc, "dDDI")
    rc, vb = capi.AMGX_vector_create(rsrc, "dDDI")
    rc, vx = capi.AMGX_vector_create(rsrc, "dDDI")
    rc = capi.AMGX_read_system_distributed(
        mtx, vb, vx, str(src), 1, 8, None, pv)
    assert rc == 0
    # each rank owns exactly its partition-vector rows
    m = mtx.matrix
    assert m.blocks is not None and len(m.blocks) == 8
    counts = np.bincount(pv, minlength=8)
    assert np.array_equal(np.diff(m.block_offsets), counts)
    order = np.argsort(pv, kind="stable")
    A_ren = A[order][:, order].tocsr()
    assert abs(m.assemble_global() - A_ren).max() < 1e-14
    np.testing.assert_allclose(np.asarray(vb.data), b[order])

    # the distributed system solves (8-rank mesh)
    rc, slv = capi.AMGX_solver_create(rsrc, "dDDI", cfg)
    assert capi.AMGX_solver_setup(slv, mtx) == 0
    assert capi.AMGX_solver_solve(slv, vb, vx) == 0
    rc, x = capi.AMGX_vector_download(vx)
    assert rc == 0
    rr = np.linalg.norm(b[order] - A_ren @ x) / np.linalg.norm(b[order])
    assert rr < 1e-7

    # write-back inverts the renumbering: original ordering on disk
    dst = tmp_path / "back.mtx"
    rc = capi.AMGX_write_system_distributed(mtx, vb, None, str(dst), 1, 8,
                                            None, n, pv)
    assert rc == 0
    back = aio.read_matrix_market(str(dst))
    assert abs(sp.csr_matrix(back.A) - A).max() < 1e-12
    np.testing.assert_allclose(back.rhs, b, rtol=1e-12)


def test_distributed_io_partition_sizes_contiguous(tmp_path):
    """Round-4 advisor: ``partition_sizes`` without a partition vector
    is the reference's contiguous-size partitioning — each rank gets a
    contiguous block of the given size (was silently ignored)."""
    from amgx_tpu import capi
    from amgx_tpu.io import poisson5pt

    A = sp.csr_matrix(poisson5pt(12, 12))
    n = A.shape[0]
    src = tmp_path / "sys.mtx"
    import amgx_tpu.io as aio
    aio.write_matrix_market(str(src), A, rhs=np.ones(n))

    sizes = [40, 40, 40, 24]
    rc, cfg = capi.AMGX_config_create("config_version=2, solver(out)=PCG")
    rc, rsrc = capi.AMGX_resources_create_simple(cfg)
    rc, mtx = capi.AMGX_matrix_create(rsrc, "dDDI")
    rc = capi.AMGX_read_system_distributed(
        mtx, None, None, str(src), 1, 4, sizes, None)
    assert rc == 0
    m = mtx.matrix
    assert m.blocks is not None and len(m.blocks) == 4
    assert np.array_equal(np.diff(m.block_offsets), sizes)
    assert abs(m.assemble_global() - A).max() < 1e-14

    # inconsistent sizes must be rejected, not ignored
    rc, mtx2 = capi.AMGX_matrix_create(rsrc, "dDDI")
    rc = capi.AMGX_read_system_distributed(
        mtx2, None, None, str(src), 1, 4, [40, 40, 40, 23], None)
    assert rc != 0


def test_make_mesh_refuses_missing_devices():
    """A mesh over more devices than exist raises — it never silently
    shrinks to the devices that are there."""
    from amgx_tpu.distributed import make_mesh
    from amgx_tpu.errors import BadParametersError
    n = len(jax.devices())
    assert make_mesh(n).devices.size == n
    with pytest.raises(BadParametersError, match="devices are visible"):
        make_mesh(n + 1)


def _geo_fgmres_cfg(tol):
    return amgx.AMGConfig(
        "config_version=2, solver(out)=FGMRES, out:max_iters=100, "
        f"out:monitor_residual=1, out:tolerance={tol}, "
        "out:convergence=RELATIVE_INI, out:gmres_n_restart=6, "
        "out:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION, "
        "amg:selector=GEO, amg:max_iters=1, amg:max_levels=20, "
        "amg:smoother(sm)=BLOCK_JACOBI, sm:max_iters=1, "
        "amg:presweeps=2, amg:postsweeps=2, amg:min_coarse_rows=32, "
        "amg:coarse_solver=DENSE_LU_SOLVER")


def test_distributed_geo_coarsens_rank_slabs_by_cells():
    """GEO on a row-partitioned stencil grid aggregates each rank's
    slab in 2×2×2 cells, so the hierarchy has the one-device GEO level
    sizes — consecutive-row pairs halved it per level instead."""
    from amgx_tpu.distributed import make_mesh
    A = poisson7pt(16, 16, 16)
    sizes = []
    for mesh4 in (None, make_mesh(4)):
        m = amgx.Matrix(A)
        if mesh4 is not None:
            m.set_distribution(mesh4)
        slv = amgx.create_solver(_geo_fgmres_cfg(1e-6))
        slv.setup(m)
        sizes.append([int(lv.A.n_block_rows)
                      for lv in slv.preconditioner.hierarchy.levels])
    assert sizes[0] == sizes[1] == [4096, 512, 64]


def test_cell_aggregates_refuse_wrapped_stencils():
    """A periodic coupling decodes as a long grid move: no cells."""
    from amgx_tpu.amg.structured import cell_aggregates
    A = sp.csr_matrix(poisson7pt(4, 4, 4))
    agg = cell_aggregates(A)
    assert agg is not None and agg.max() + 1 == 8
    assert np.bincount(agg).tolist() == [8] * 8
    W = A.tolil()
    W[0, 3] = W[3, 0] = -1.0           # x wraps around the first row
    assert cell_aggregates(sp.csr_matrix(W)) is None


@pytest.mark.parametrize("values", ["integer", "fractional"])
def test_sharded_f32_pack_refines_to_f64_tolerance(mesh, values):
    """A row-partitioned f32 pack reaches a tolerance below the f32
    floor through the f64 refinement rung, as one device does; the
    fractional operator carries a rounding residue plane."""
    A = sp.csr_matrix(poisson7pt(8, 8, 8))
    if values == "fractional":
        A = sp.csr_matrix(A / 3.0 + sp.identity(A.shape[0]) * 0.1)
    b = np.ones(A.shape[0])
    m = amgx.Matrix(A)
    m.device_dtype = np.float32
    m.set_distribution(mesh)
    slv = amgx.create_solver(_geo_fgmres_cfg(1e-9))
    slv.setup(m)
    res = slv.solve(b)
    x = np.asarray(res.x, dtype=np.float64)
    assert res.status == amgx.SolveStatus.SUCCESS
    assert np.linalg.norm(b - A @ x) / np.linalg.norm(b) < 1e-9
    assert (slv._refine_lo is None) == (values == "integer")
