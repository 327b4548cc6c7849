"""Compile the Pallas SpMV kernels of the main path for a described TPU
v5e, at the sizes ``chip_smoke.py`` runs them — no chip needed.

The interpreter tier (test_pallas_*.py) cannot see what the chip's
compiler refuses: VMEM over budget, blocks not aligned to the (8, 128)
tiling.  Here every kernel is lowered for one chip of a ``v5e:2x2``
topology described on the CPU (section 2 of the on-chip-measurement
guide) and must come out as a Mosaic ``tpu_custom_call``.  Packs are
built on the host exactly as setup builds them; the kernels are then
compiled from shapes only.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""
import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from amgx_tpu.core.matrix import (DeviceMatrix, assemble_device_matrix,
                                  pack_host_arrays, pack_kind)
from amgx_tpu.ops import pallas_csr, pallas_ell, pallas_shift, pallas_spmv

KERNEL_MODULES = (pallas_spmv, pallas_csr, pallas_ell, pallas_shift)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def poisson7(n):
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    i = sp.identity(n)
    return (sp.kron(sp.kron(t, i), i) + sp.kron(sp.kron(i, t), i)
            + sp.kron(sp.kron(i, i), t)).tocsr()


def shuffled(A, rng, window=1024):
    """Renumber rows/cols at random inside windows of consecutive
    indices: local disorder, as a mesh generator leaves it — no banded
    pack applies, the binned kernel does."""
    n = A.shape[0]
    perm = np.concatenate([lo + rng.permutation(min(window, n - lo))
                           for lo in range(0, n, window)])
    return A[perm][:, perm].tocsr()


def host_pack(monkeypatch, A, block_dim=1, **kw):
    """The host-side pack setup builds for the chip (kernel layouts
    attach only where the kernels can run, so the packing side takes
    the interpreter switch)."""
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, "_INTERPRET", True)
    arrays, meta = pack_host_arrays(A.astype(np.float32), block_dim,
                                    np.float32, **kw)
    return assemble_device_matrix(dict(arrays), dict(meta))


def compile_for_chip(monkeypatch, one_chip, kernel, Ad):
    """Lower ``kernel(Ad, x)`` from shapes for one v5e chip and compile
    it with the persistent cache off (a compile for a described device
    cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, "_INTERPRET", False)
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), Ad)
    x = jax.ShapeDtypeStruct((Ad.n_cols * Ad.block_dim,), jnp.float32,
                             sharding=one_chip)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(kernel).lower(sds, x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_dia_kernel_compiles_128(monkeypatch, one_chip):
    n = 128 ** 3
    offs = (-128 * 128, -128, -1, 0, 1, 128, 128 * 128)
    Ad = DeviceMatrix(
        cols=None, vals=np.zeros((len(offs), n), np.float32),
        diag=np.zeros(n, np.float32), row_ids=None, n_rows=n, n_cols=n,
        block_dim=1, fmt="dia", ell_width=len(offs), dia_offsets=offs)
    assert pallas_spmv.dia_spmv_supported(n, offs, np.float32)
    compile_for_chip(monkeypatch, one_chip, pallas_spmv.dia_spmv, Ad)


@pytest.mark.parametrize("kind", ["ell/shift", "ell/window"])
def test_banded_ell_kernels_compile_128(monkeypatch, one_chip, kind):
    Ad = host_pack(monkeypatch, poisson7(128), dia_max_diags=0,
                   use_shift=(kind == "ell/shift"))
    assert pack_kind(Ad) == kind
    kernel = pallas_shift.shift_spmv if kind == "ell/shift" \
        else pallas_ell.ell_window_spmv
    compile_for_chip(monkeypatch, one_chip, kernel, Ad)


def test_binned_kernel_compiles_coarse_operator(monkeypatch, one_chip):
    # a classical-coarse-like operator: the 25-point A² of a 32³
    # Laplacian (AMG coarse grids carry 20-60 entries per row),
    # renumbered inside 4,096-row windows — too scattered for the
    # windowed kernel, within the binned kernel's padding budget
    L = poisson7(32)
    A = shuffled((L @ L).tocsr(), np.random.default_rng(0), window=4096)
    Ad = host_pack(monkeypatch, A, dia_max_diags=0)
    assert pack_kind(Ad) == "ell/binned"
    compile_for_chip(monkeypatch, one_chip, pallas_csr.binned_spmv, Ad)


def test_window_kernel_compiles_aggregation_level(monkeypatch, one_chip):
    # the benchmark cell's largest coarse operator: SIZE_2 level 1 of a
    # 64³ Laplacian (162,468 rows, up to 73 entries a row), its coarse
    # unknowns numbered by first fine row as the hierarchy numbers them
    from amgx_tpu.amg.aggregation.galerkin import galerkin_coarse
    from amgx_tpu.amg.aggregation.selectors import create_selector
    from amgx_tpu.amg.hierarchy import _first_member_order, _permute_host
    from amgx_tpu.config import AMGConfig
    A = poisson7(64)
    agg = create_selector("SIZE_2", AMGConfig(), "default").select(A)
    order = _first_member_order(agg, int(agg.max()) + 1)
    Ad = host_pack(monkeypatch,
                   _permute_host(galerkin_coarse(A, agg), order, 1))
    assert Ad.n_rows == 162468
    assert pack_kind(Ad) == "ell/window"
    compile_for_chip(monkeypatch, one_chip, pallas_ell.ell_window_spmv, Ad)


def test_window_kernel_compiles_dd4_rank_level(monkeypatch, one_chip):
    # one rank of the 4-chip cell: the SIZE_2 level 1 of its 100×100×25
    # slab of the 7-point Laplacian (the rank's diagonal block), coarse
    # unknowns numbered by first fine row as the distributed coarsening
    # numbers them — every row tile within the window kernel's budget
    from amgx_tpu.amg.aggregation.galerkin import galerkin_coarse
    from amgx_tpu.amg.aggregation.selectors import create_selector
    from amgx_tpu.amg.hierarchy import _first_member_order, _permute_host
    from amgx_tpu.config import AMGConfig
    from amgx_tpu.ops.pallas_ell import _MAX_BLOCKS, ell_window_pack
    line = [sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(m, m))
            for m in (25, 100, 100)]
    eye = [sp.identity(m) for m in (25, 100, 100)]
    A = (sp.kron(sp.kron(line[0], eye[1]), eye[2])
         + sp.kron(sp.kron(eye[0], line[1]), eye[2])
         + sp.kron(sp.kron(eye[0], eye[1]), line[2])).tocsr()
    agg = create_selector("SIZE_2", AMGConfig(), "default").select(A)
    order = _first_member_order(agg, int(agg.max()) + 1)
    assert order is not None
    Ac = _permute_host(galerkin_coarse(A, agg), order, 1)
    Ac.sort_indices()
    deg = np.diff(Ac.indptr)
    cols = np.zeros((Ac.shape[0], int(deg.max())), dtype=np.int64)
    cols[np.repeat(np.arange(Ac.shape[0]), deg),
         np.arange(Ac.nnz) - np.repeat(Ac.indptr[:-1], deg)] = Ac.indices
    pack = ell_window_pack(cols)
    assert pack is not None and pack[0].shape[1] <= _MAX_BLOCKS
    Ad = host_pack(monkeypatch, Ac)
    assert pack_kind(Ad) == "ell/window"
    compile_for_chip(monkeypatch, one_chip, pallas_ell.ell_window_spmv, Ad)


def test_binned_block_kernel_compiles_b4(monkeypatch, one_chip):
    # chip_smoke's block phase: a 4×4-block system on a 32³ grid
    rng = np.random.default_rng(0)
    r = rng.uniform(-1.0, 1.0, size=(4, 4))
    A = (sp.kron(shuffled(poisson7(32), rng), sp.identity(4))
         + sp.kron(sp.identity(32 ** 3), 0.5 * (r - r.T))).tocsr()
    Ad = host_pack(monkeypatch, A, block_dim=4)
    assert pack_kind(Ad) == "ell/binned-block"
    compile_for_chip(monkeypatch, one_chip, pallas_csr.binned_spmv, Ad)


@pytest.mark.parametrize("path", ["window", "wide"])
def test_sharded_spmv_compiles_on_2x2(monkeypatch, topo, path):
    """The row-partitioned SpMV of ``chip_smoke.py --chips 4`` over the
    four chips of the described 2x2: the f32 windowed-kernel interior,
    and the f64 gather apply the refinement rung's true residual takes
    (``SolverBase._wide_pack``), with its all-reduced norm."""
    import dataclasses
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from amgx_tpu.distributed.matrix import (dist_spmv, interior_path,
                                             make_mesh, shard_matrix)
    monkeypatch.setattr(pallas_ell, "_INTERPRET", True)
    sm = shard_matrix(poisson7(32), make_mesh(4), dtype=np.float32)
    monkeypatch.setattr(pallas_ell, "_INTERPRET", False)
    chips = Mesh(np.array(topo.devices[:4]), ("p",))
    wide = path == "wide"
    sm = dataclasses.replace(sm, mesh=chips)
    if wide:
        sm = dataclasses.replace(sm, win_blocks=None, win_codes=None,
                                 win_vals=None)
    assert interior_path(sm) == ("gather" if wide else "window")
    dt = jnp.float64 if wide else jnp.float32

    def sds(a, dtype=None):
        spec = P("p", *([None] * (a.ndim - 1)))
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=NamedSharding(chips, spec))
    sm = jax.tree_util.tree_map(sds, sm)
    if wide:
        sm = dataclasses.replace(sm, vals=sds(sm.vals, dt),
                                 diag=sds(sm.diag, dt))
    x = jax.ShapeDtypeStruct((sm.n,), dt,
                             sharding=NamedSharding(chips, P("p")))

    def residual(A, x, b):
        r = b - dist_spmv(A, x)
        return r, jnp.sqrt(jnp.sum(r * r))

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(residual).lower(sm, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    assert ("tpu_custom_call" in text) != wide
    assert ("f64" in text) == wide


def test_dd4_sharded_levels_take_the_window_interior(monkeypatch):
    """The 4-chip cell's stack at 16³ on 4 virtual devices, the window
    packs built as on a chip (the interpreter switch): every sharded
    scalar level's interior SpMV is the windowed kernel."""
    import json
    from pathlib import Path

    import amgx_tpu as amgx
    from amgx_tpu.distributed.matrix import make_mesh
    conf = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                       / "configs" / "poisson7-agg-dd4.json").read_text())
    monkeypatch.setattr(pallas_ell, "_INTERPRET", True)
    m = amgx.Matrix(poisson7(16))
    m.device_dtype = np.dtype(conf["dtype"])
    m.set_distribution(make_mesh(4))
    slv = amgx.create_solver(amgx.AMGConfig(conf["solver_config"]))
    slv.setup(m)
    lay = slv.preconditioner.hierarchy.dist_levels()
    assert len(lay) >= 5
    assert all(d["interior"] == "window" for d in lay.values()), lay
