"""Sharded matrix pack + distributed SpMV.

TPU-native equivalent of the reference's distributed SpMV with latency
hiding (``base/src/multiply.cu:75-196``, SURVEY §3.4):

    exchange_halo_async → SpMV on INTERIOR rows → wait → SpMV on BOUNDARY

Here the halo exchange is a mesh collective inside ``jax.shard_map``:
``all_gather`` of the fixed-size B2L send buffers (general partitions) or a
``ppermute`` neighbour schedule (1D stencil partitions).  XLA overlaps the
collective with the interior gather/multiply the way the reference overlaps
MPI with the interior kernel — without hand-rolled streams.

Vectors are flat (P·n_loc,) arrays sharded over mesh axis ``p`` with a
``NamedSharding``; everything outside SpMV (dots, axpys, Krylov updates) is
plain jnp code that GSPMD partitions automatically, inserting ``psum`` for
reductions — the TPU analog of the reference's MPI all-reduce dots
(SURVEY §3.3 "Every dot product in Krylov is an MPI all-reduce").

Padding invariant: shards are equal-sized; padding rows are identity rows
whose rhs/solution entries are exactly zero through every cycle operation,
so padded entries never pollute dots or norms.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..errors import BadParametersError
from ..telemetry import scopes as _tscopes
from ..utils.jaxcompat import shard_map as _shard_map
from .partition import Partition, build_partition


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["cols", "vals", "diag", "send_idx", "halo_src",
                 "bnd_rows", "send_idx2", "halo_src2", "win_blocks",
                 "win_codes", "win_vals"],
    meta_fields=["n_global", "n_parts", "n_loc", "ell_width", "block_dim",
                 "axis", "dists", "dists2", "offsets", "win_tile",
                 "mesh", "n_loc_cols", "col_offsets", "send_counts",
                 "halo_counts", "halo_counts2", "bnd_counts"],
)
@dataclasses.dataclass(frozen=True)
class ShardedMatrix:
    """Frozen sharded ELL pack (leading axis = mesh axis ``p``).

    ``cols`` index into the per-shard extended vector
    ``[x_local (n_loc) | halo (H)]``.  ``bnd_rows`` lists each shard's
    boundary rows (padded with the trash slot ``n_loc``) so SpMV can
    overlap the halo exchange with the interior compute; ``send_idx2`` /
    ``halo_src2`` are the ring-2 B2L maps (``distributed_manager.h:
    284-305`` per-ring maps).
    """

    cols: jax.Array       # (P, n_loc, K) int32
    vals: jax.Array       # (P, n_loc, K)
    diag: jax.Array       # (P·n_loc,) flat, sharded like vectors
    send_idx: jax.Array   # (P, B) int32 — ring-1 B2L gather map
    halo_src: jax.Array   # (P, H) int32 — d_slot·B + pos into recv bufs
    bnd_rows: jax.Array   # (P, Bd) int32 — boundary rows, pad → n_loc
    send_idx2: jax.Array  # (P, B2) int32 — ring-2 B2L gather map
    halo_src2: jax.Array  # (P, H2) int32
    n_global: int
    n_parts: int
    n_loc: int
    ell_width: int
    block_dim: int
    axis: str             # mesh axis name
    dists: tuple          # ring-1 rank distances (owner − p) mod P
    dists2: tuple         # ring-2 rank distances
    offsets: tuple        # (P+1,) real row offsets per rank
    #: per-shard windowed-ELL pack (ops/pallas_ell.py) for the interior
    #: SpMV on TPU backends; None when some shard exceeds the window
    #: budget (local compute then falls back to the XLA gather)
    win_blocks: Optional[jax.Array] = None   # (P, n_tiles·B) int32
    #: int16 (codes < 5120 by construction — halves transfer bytes);
    #: _ell_window_call widens to int32 at trace time for the kernel
    win_codes: Optional[jax.Array] = None    # (P, n_pad·K) int16
    win_vals: Optional[jax.Array] = None     # (P, n_pad·K)
    win_tile: int = 0
    #: static (meta) so traced packs keep it — tracers have no .sharding
    mesh: Mesh = None
    #: rectangular operators (classical P/R): the COLUMN space has its
    #: own partition — halo exchange runs in that space; None ⇒ square
    n_loc_cols: Optional[int] = None
    col_offsets: Optional[tuple] = None
    #: per-rank UNPADDED map sizes (static, from the Partition) — the
    #: telemetry cost model reads these so halo byte counters report
    #: both wire bytes (padded) and useful entries (analytic boundary
    #: sizes); None on packs built before instrumentation cared
    send_counts: Optional[tuple] = None
    halo_counts: Optional[tuple] = None
    halo_counts2: Optional[tuple] = None
    bnd_counts: Optional[tuple] = None

    @property
    def n(self) -> int:
        """Padded global scalar size (P · n_loc · b) — vector length."""
        return self.n_parts * self.n_loc * self.block_dim

    @property
    def n_rows(self) -> int:
        """Padded global (block-)row count."""
        return self.n_parts * self.n_loc

    @property
    def n_cols(self) -> int:
        """Padded global COLUMN size."""
        return self.n_parts * (self.n_loc_cols
                               if self.n_loc_cols is not None
                               else self.n_loc)

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def fmt(self):
        return "sharded-ell"


def pad_map(offsets: np.ndarray, n_loc: int) -> np.ndarray:
    """real global row id → padded id (rank p, local l → p·n_loc + l)."""
    n_parts = len(offsets) - 1
    out = np.empty(offsets[-1], dtype=np.int64)
    for p in range(n_parts):
        lo, hi = offsets[p], offsets[p + 1]
        out[lo:hi] = p * n_loc + np.arange(hi - lo)
    return out


def embed_padded(M: sp.csr_matrix, row_offsets, row_nloc,
                 col_offsets, col_nloc) -> sp.csr_matrix:
    """Re-index a real-sized sparse matrix into padded coordinates (pad
    rows/cols stay empty).  Used to embed classical P/R into the padded
    vector spaces."""
    M = sp.coo_matrix(M)
    rmap = pad_map(np.asarray(row_offsets), row_nloc)
    cmap = pad_map(np.asarray(col_offsets), col_nloc)
    n_parts = len(row_offsets) - 1
    shape = (n_parts * row_nloc, (len(col_offsets) - 1) * col_nloc)
    return sp.csr_matrix((M.data, (rmap[M.row], cmap[M.col])), shape=shape)


def make_mesh(n_devices: Optional[int] = None, axis: str = "p") -> Mesh:
    """Build a 1D device mesh in Auto (GSPMD) mode — collectives for the
    Krylov-level algebra are inserted by the partitioner; only the SpMV
    halo exchange is hand-scheduled via shard_map."""
    from ..utils.jaxcompat import axis_type_auto
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise BadParametersError(
            f"make_mesh({n}): only {len(devs)} devices are visible; a "
            "smaller mesh would silently change the partition")
    return Mesh(np.array(devs[:n]), (axis,),
                axis_types=(axis_type_auto(),))


def _auto_mesh(mesh: Mesh) -> Mesh:
    """Coerce a mesh to Auto axis types (GSPMD) — explicit sharding-in-types
    meshes would demand out_sharding annotations on every contraction."""
    from ..utils.jaxcompat import axis_type_auto
    auto = axis_type_auto()
    if all(t == auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(auto,) * len(mesh.axis_names))


def _ml_register_pack(pack: "ShardedMatrix", kind: str) -> "ShardedMatrix":
    """HBM-ledger registration of a freshly built sharded pack (owner
    ``amgx/dist/<kind>`` — device values plus halo/B2L exchange maps).
    A weakref finalizer releases the ledger entry when the pack dies,
    so the builders need no explicit teardown hook; never raises."""
    from .. import telemetry
    ml = telemetry.memledger
    if not ml.is_enabled():
        return pack
    tok = None
    try:
        tok = ml.register(ml.owner_name("dist", kind), pack)
        if tok is not None:
            weakref.finalize(pack, ml.release, tok)
    except Exception:
        ml.release(tok)
    return pack


def shard_matrix(A: sp.csr_matrix, mesh: Mesh, axis: str = "p",
                 dtype=None, offsets=None, n_loc: Optional[int] = None,
                 partition: Optional[Partition] = None) -> ShardedMatrix:
    """Pack a global CSR matrix into a ShardedMatrix laid out over ``mesh``
    (convenience wrapper: splits into per-rank row blocks first)."""
    A = sp.csr_matrix(A)
    mesh = _auto_mesh(mesh)
    n_parts = mesh.shape[axis]
    if partition is not None:
        offsets = np.asarray(partition.offsets)
    elif offsets is None:
        n = A.shape[0]
        nl = -(-n // n_parts)
        offsets = np.minimum(np.arange(n_parts + 1) * nl, n)
    else:
        offsets = np.asarray(offsets)
    from .partition import split_row_blocks
    return shard_matrix_from_blocks(split_row_blocks(A, offsets), offsets,
                                    mesh, axis=axis, dtype=dtype,
                                    n_loc=n_loc, partition=partition)


def shard_matrix_from_blocks(blocks, offsets, mesh: Mesh, axis: str = "p",
                             dtype=None, n_loc: Optional[int] = None,
                             partition: Optional[Partition] = None,
                             col_offsets=None,
                             n_loc_cols: Optional[int] = None
                             ) -> ShardedMatrix:
    """Pack per-rank row blocks (global column ids) into a ShardedMatrix.

    The scalable-setup entry point — no step materialises a global matrix
    (``AMGX_matrix_upload_distributed`` semantics).  Mirrors
    ``DistributedManager::loadDistributedMatrix``
    (``distributed_manager.h:1815``): build B2L maps from per-rank data
    (``distributed_arranger.h:85-140``), renumber columns to
    [local | halo] slots, pad shards to equal size with identity rows.

    ``col_offsets``/``n_loc_cols``: rectangular operators (classical P/R)
    whose column space is partitioned differently — halo maps then live
    in the column space, padding rows are zero rows, and the diagonal is
    meaningless (zeros).
    """
    from .partition import build_partition_from_blocks
    blocks = [sp.csr_matrix(b) for b in blocks]
    offsets = np.asarray(offsets)
    rect = col_offsets is not None
    dtype = np.dtype(dtype or blocks[0].dtype)
    mesh = _auto_mesh(mesh)
    n_parts = mesh.shape[axis]
    if len(blocks) != n_parts:
        raise BadParametersError(
            f"{len(blocks)} row blocks for a {n_parts}-way mesh axis")
    part = partition or build_partition_from_blocks(
        blocks, offsets, n_rings=1 if rect else 2,
        col_offsets=col_offsets)
    if n_loc is not None and n_loc > part.n_loc:
        part = dataclasses.replace(part, n_loc=n_loc)
    n_loc = part.n_loc
    if rect:
        col_offsets = np.asarray(col_offsets)
        nlc = n_loc_cols or int(np.max(np.diff(col_offsets)))
    else:
        col_offsets = part.offsets
        nlc = n_loc
    K = max((int(np.diff(b.indptr).max()) if b.nnz else 1
             for b in blocks), default=1)

    cols = np.zeros((n_parts, n_loc, K), dtype=np.int32)
    vals = np.zeros((n_parts, n_loc, K), dtype=dtype)
    diag = np.zeros((n_parts, n_loc), dtype=dtype)
    for p in range(n_parts):
        lo, hi = part.offsets[p], part.offsets[p + 1]
        clo, chi = col_offsets[p], col_offsets[p + 1]
        nl = hi - lo
        sub = blocks[p]
        sub.sort_indices()
        ext = part.halo_global[p]
        gcols = sub.indices.astype(np.int64)
        local = (gcols >= clo) & (gcols < chi)
        lcols = np.where(local, gcols - clo, 0)
        if len(ext):
            halo_slot = np.searchsorted(ext, gcols)
            halo_slot = np.minimum(halo_slot, len(ext) - 1)
            lcols = np.where(local, lcols, nlc + halo_slot)
        deg = np.diff(sub.indptr)
        rr = np.repeat(np.arange(nl), deg)
        pos = np.arange(len(gcols)) - np.repeat(sub.indptr[:-1], deg)
        cols[p, rr, pos] = lcols
        vals[p, rr, pos] = sub.data
        if not rect:
            on_diag = gcols == rr + lo
            # add (not assign): duplicate diagonal entries are legal CSR
            # input and the ELL pack sums them too
            np.add.at(diag[p], rr[on_diag], sub.data[on_diag])
            # identity padding rows (zero rows in rectangular packs: a
            # padded output entry must stay exactly zero)
            r = np.arange(nl, n_loc)
            cols[p, r, 0] = r
            vals[p, r, 0] = 1.0
            diag[p, r] = 1.0

    # per-shard windowed-ELL pack for the TPU interior SpMV (columns
    # index the [local | halo] extended space — rectangular is fine);
    # all shards must fit the window budget or none carry it
    win_blocks = win_codes = win_vals = None
    win_tile = 0
    from ..ops.pallas_ell import _INTERPRET
    mesh_is_tpu = mesh.devices.flat[0].platform == "tpu"
    if np.dtype(dtype) == np.float32 and K <= 160 and \
            (mesh_is_tpu or _INTERPRET):
        from ..ops.pallas_ell import ell_window_pack, win_vals_pack
        packs = [ell_window_pack(cols[p]) for p in range(n_parts)]
        if all(pk is not None for pk in packs):
            win_tile = packs[0][2]
            Bmax = max(pk[0].shape[1] for pk in packs)
            nt = packs[0][0].shape[0]
            wb = np.zeros((n_parts, nt * Bmax), dtype=np.int32)
            for p, (bids, _, _) in enumerate(packs):
                padded = np.zeros((nt, Bmax), dtype=np.int32)
                padded[:, : bids.shape[1]] = bids
                wb[p] = padded.reshape(-1)
            win_blocks = wb
            win_codes = np.stack([pk[1][0] for pk in packs])
            win_vals = np.stack(
                [win_vals_pack(vals[p], win_tile)[0]
                 for p in range(n_parts)])

    spec3 = NamedSharding(mesh, P(axis, None, None))
    spec2 = NamedSharding(mesh, P(axis, None))
    spec1 = NamedSharding(mesh, P(axis))
    if len(part.rings) > 1:
        r2 = part.rings[1]
    else:                     # rectangular packs carry no ring 2
        from .partition import Ring
        r2 = Ring(dists=(1,),
                  send_idx=np.zeros((n_parts, 1), np.int32),
                  send_count=np.zeros(n_parts, np.int32),
                  halo_src=np.zeros((n_parts, 1), np.int32),
                  halo_count=np.zeros(n_parts, np.int32),
                  halo_global=[np.zeros(0, np.int64)] * n_parts)
    return _ml_register_pack(ShardedMatrix(
        cols=jax.device_put(cols, spec3),
        vals=jax.device_put(vals, spec3),
        diag=jax.device_put(diag.reshape(-1), spec1),
        send_idx=jax.device_put(part.send_idx, spec2),
        halo_src=jax.device_put(part.halo_src, spec2),
        bnd_rows=jax.device_put(part.bnd_rows, spec2),
        send_idx2=jax.device_put(r2.send_idx, spec2),
        halo_src2=jax.device_put(r2.halo_src, spec2),
        win_blocks=None if win_blocks is None else
        jax.device_put(win_blocks, spec2),
        win_codes=None if win_codes is None else
        jax.device_put(win_codes, spec2),
        win_vals=None if win_vals is None else
        jax.device_put(win_vals, spec2),
        win_tile=win_tile,
        n_global=part.n_global, n_parts=n_parts, n_loc=n_loc,
        ell_width=K, block_dim=1, axis=axis,
        dists=part.dists, dists2=r2.dists,
        offsets=tuple(int(o) for o in part.offsets), mesh=mesh,
        n_loc_cols=nlc if rect else None,
        col_offsets=tuple(int(o) for o in col_offsets) if rect else None,
        send_counts=tuple(int(c) for c in part.send_count),
        halo_counts=tuple(int(c) for c in part.halo_count),
        halo_counts2=tuple(int(c) for c in r2.halo_count),
        bnd_counts=tuple(int(c) for c in part.bnd_count)),
        "rect_pack" if rect else "shard_pack")


def shard_block_matrix(host, block_dim: int, mesh: Mesh, axis: str = "p",
                       dtype=None, offsets=None,
                       n_loc: Optional[int] = None) -> ShardedMatrix:
    """Pack a BLOCK (b×b) matrix into a ShardedMatrix: vals
    (P, n_loc, K, b, b), cols over the [local | halo] BLOCK space, flat
    (P·n_loc·b) vectors — the reference's uniform block-CSR distribution
    (``matrix.h:87-220``).  The halo machinery runs unchanged on the
    BLOCK graph (an index CSR whose data point into the bsr blocks)."""
    from .partition import build_partition_from_blocks, split_row_blocks
    b = int(block_dim)
    bsr = host if isinstance(host, sp.bsr_matrix) else sp.bsr_matrix(
        host, blocksize=(b, b))
    bsr.sort_indices()
    dtype = np.dtype(dtype or bsr.dtype)
    mesh = _auto_mesh(mesh)
    n_parts = mesh.shape[axis]
    nb = bsr.shape[0] // b
    # block-graph index CSR: entry (I, J) stores its block id
    ind = sp.csr_matrix(
        (np.arange(len(bsr.indices), dtype=np.int64) + 1, bsr.indices,
         bsr.indptr), shape=(nb, bsr.shape[1] // b))
    if offsets is None:
        nl = -(-nb // n_parts)
        offsets = np.minimum(np.arange(n_parts + 1) * nl, nb)
    else:
        offsets = np.asarray(offsets)
    ind_blocks = split_row_blocks(ind, offsets)
    part = build_partition_from_blocks(ind_blocks, offsets, n_rings=2)
    if n_loc is not None and n_loc > part.n_loc:
        part = dataclasses.replace(part, n_loc=n_loc)
    n_loc = part.n_loc
    K = max((int(np.diff(blk.indptr).max()) if blk.nnz else 1
             for blk in ind_blocks), default=1)

    cols = np.zeros((n_parts, n_loc, K), dtype=np.int32)
    vals = np.zeros((n_parts, n_loc, K, b, b), dtype=dtype)
    diag = np.zeros((n_parts, n_loc, b, b), dtype=dtype)
    eye = np.eye(b, dtype=dtype)
    for p in range(n_parts):
        lo, hi = part.offsets[p], part.offsets[p + 1]
        nl = hi - lo
        sub = ind_blocks[p]
        sub.sort_indices()
        ext = part.halo_global[p]
        gcols = sub.indices.astype(np.int64)
        local = (gcols >= lo) & (gcols < hi)
        lcols = np.where(local, gcols - lo, 0)
        if len(ext):
            halo_slot = np.searchsorted(ext, gcols)
            halo_slot = np.minimum(halo_slot, len(ext) - 1)
            lcols = np.where(local, lcols, n_loc + halo_slot)
        deg = np.diff(sub.indptr)
        rr = np.repeat(np.arange(nl), deg)
        pos = np.arange(len(gcols)) - np.repeat(sub.indptr[:-1], deg)
        cols[p, rr, pos] = lcols
        vals[p, rr, pos] = bsr.data[sub.data - 1]
        on_diag = gcols == rr + lo
        diag[p, rr[on_diag]] += bsr.data[sub.data[on_diag] - 1]
        # identity padding rows
        r = np.arange(nl, n_loc)
        cols[p, r, 0] = r
        vals[p, r, 0] = eye
        diag[p, r] = eye

    spec5 = NamedSharding(mesh, P(axis, None, None, None, None))
    spec3 = NamedSharding(mesh, P(axis, None, None))
    spec2 = NamedSharding(mesh, P(axis, None))
    spec1 = NamedSharding(mesh, P(axis))
    r2 = part.rings[1]
    return _ml_register_pack(ShardedMatrix(
        cols=jax.device_put(cols, spec3),
        vals=jax.device_put(vals, spec5),
        diag=jax.device_put(diag.reshape(-1, b, b), spec1),
        send_idx=jax.device_put(part.send_idx, spec2),
        halo_src=jax.device_put(part.halo_src, spec2),
        bnd_rows=jax.device_put(part.bnd_rows, spec2),
        send_idx2=jax.device_put(r2.send_idx, spec2),
        halo_src2=jax.device_put(r2.halo_src, spec2),
        n_global=part.n_global, n_parts=n_parts, n_loc=n_loc,
        ell_width=K, block_dim=b, axis=axis,
        dists=part.dists, dists2=r2.dists,
        offsets=tuple(int(o) for o in part.offsets), mesh=mesh,
        send_counts=tuple(int(c) for c in part.send_count),
        halo_counts=tuple(int(c) for c in part.halo_count),
        halo_counts2=tuple(int(c) for c in r2.halo_count),
        bnd_counts=tuple(int(c) for c in part.bnd_count)),
        "block_pack")


# --------------------------------------------------------------------------
# distributed SpMV
# --------------------------------------------------------------------------
def uses_all_gather(dists: tuple, n_parts: int) -> bool:
    """THE exchange-path predicate: dense link sets fall back from the
    per-distance ppermute schedule to one all_gather.  Single authority
    shared by the real exchange (:func:`_exchange`), the telemetry path
    label (:func:`_tel_exchange`) and the cost model
    (``telemetry.costmodel.halo_wire_bytes``) — three copies would
    silently drift."""
    return n_parts > 1 and len(dists) >= n_parts - 1


_halo_lock = threading.Lock()
#: (op, ring) -> [exchanges, hops, wire bytes], counted as traced
_halo_counts: dict = {}


def _hops(A: "ShardedMatrix", ring: int):
    """(path, collectives) of one ring-``ring`` exchange: the ACTUAL
    collective count XLA executes — the all_gather fallback collapses
    the whole distance schedule into ONE collective (its wire BYTES still
    count every (P-1)-buffer the gather moves)."""
    dists = A.dists if ring == 1 else A.dists2
    if uses_all_gather(dists, A.n_parts):
        return "all_gather", 1
    return "ppermute", len(dists)


def halo_stats() -> dict:
    """Process totals of the halo exchanges traced so far, counted
    whether telemetry is on or not: ``{"exchanges": n, "hops": n,
    "wire_bytes": n, "by_op": {"<op>/ring<r>": {...}}}``.

    An exchange counts when the Python body that emits it runs: once per
    trace under ``jax.jit`` (the compiled program is unchanged and pays
    nothing), once per call when eager.  ``hops`` are the collectives it
    puts in the program (one ``ppermute`` per ring distance, or one
    ``all_gather`` on the dense fallback); ``wire_bytes`` the padded
    send buffers all shards put on the ICI, as
    ``telemetry.costmodel.halo_wire_bytes`` models one exchange."""
    with _halo_lock:
        by = {f"{op}/ring{ring}": {"exchanges": c[0], "hops": c[1],
                                   "wire_bytes": c[2]}
              for (op, ring), c in sorted(_halo_counts.items())}
    return {k: sum(d[k] for d in by.values())
            for k in ("exchanges", "hops", "wire_bytes")} | {"by_op": by}


def _count_halo(A: "ShardedMatrix", ring: int, op: str):
    """Add one traced exchange to :func:`halo_stats` (none on one
    part, whose exchange is the identity)."""
    if A.n_parts == 1:
        return
    from ..telemetry import costmodel as _tcost
    wire = int(_tcost.halo_wire_bytes(A, ring))
    hops = _hops(A, ring)[1]
    with _halo_lock:
        c = _halo_counts.setdefault((op, ring), [0, 0, 0])
        c[0] += 1
        c[1] += hops
        c[2] += wire


def _tel_exchange(A: "ShardedMatrix", ring: int, op: str):
    """Halo-exchange telemetry (one attribute check when off).

    Like the SpMV dispatch counters (ops/spmv.py), this fires HOST-side
    at dispatch/trace time — the compiled program is unchanged; under
    ``jax.jit`` one traced exchange counts once per compilation, which
    is exactly the static cost the comms PRs are judged by.  Wire bytes
    count the PADDED send buffers every shard actually puts on the ICI
    (one per ppermute hop, or P−1 under the all_gather fallback);
    entries count the useful (analytic-boundary-size) halo values.
    """
    from ..telemetry import recorder as _trecorder
    if not _trecorder.is_enabled():
        return
    from ..telemetry import costmodel as _tcost
    from ..telemetry import metrics as _tmetrics
    path, n_coll = _hops(A, ring)
    wire = _tcost.halo_wire_bytes(A, ring)
    entries = _tcost.halo_entries(A, ring)
    send_idx = A.send_idx if ring == 1 else A.send_idx2
    _tmetrics.counter_inc("amgx_halo_exchange_total", ring=ring, op=op,
                          path=path)
    _tmetrics.counter_inc("amgx_halo_bytes_total", wire, ring=ring,
                          op=op)
    _tmetrics.counter_inc("amgx_halo_entries_total", entries, ring=ring,
                          op=op)
    _tmetrics.gauge_set("amgx_dist_ring_hops", n_coll, ring=ring)
    counts = A.halo_counts if ring == 1 else A.halo_counts2
    _trecorder.event(
        "halo_exchange", op=op, ring=ring, path=path,
        n_parts=A.n_parts, hops=n_coll,
        send_buf=int(send_idx.shape[1]),
        wire_bytes=int(wire), entries=int(entries),
        per_rank_entries=None if counts is None else list(counts))


def _tel_dist_spmv(A: "ShardedMatrix"):
    """dist_spmv dispatch telemetry: the halo-exchange counters plus
    per-device boundary/halo gauges (label ``device`` = shard index —
    the SPMD program is identical per device; the per-rank numbers come
    from the partition's static counts)."""
    from ..telemetry import recorder as _trecorder
    if not _trecorder.is_enabled():
        return
    from ..telemetry import metrics as _tmetrics
    # NOTE: the dispatch counter (pack="sharded/<interior>") is
    # ops/spmv.py's job — incrementing it again here would double-count
    # every distributed SpMV
    _tel_exchange(A, 1, "dist_spmv")
    if A.bnd_counts is None:
        return
    offs = A.offsets
    for p in range(A.n_parts):
        rows = max((offs[p + 1] - offs[p]) if offs is not None
                   else A.n_loc, 1)
        _tmetrics.gauge_set("amgx_dist_boundary_fraction",
                            A.bnd_counts[p] / rows, device=p)
        if A.halo_counts is not None:
            _tmetrics.gauge_set("amgx_dist_halo_entries",
                                A.halo_counts[p], device=p)


def _exchange(buf: jax.Array, dists: tuple, axis: str,
              n_parts: int) -> jax.Array:
    """Distance-wise neighbour exchange: rank p receives, for each d in
    ``dists``, rank (p+d) mod P's send buffer — one ``ppermute`` per
    distance (neighbour-wise like ``comms_mpi_hostbuffer_stream.cu:
    354-523``, O(D·B) instead of the all-gather's O(P·B)).  Falls back to
    one all_gather when the link set is dense."""
    if n_parts == 1:
        return buf
    if uses_all_gather(dists, n_parts):
        all_bufs = jax.lax.all_gather(buf, axis)        # (P, B[, b])
        i = jax.lax.axis_index(axis)
        order = (i + jnp.asarray(dists, jnp.int32)) % n_parts
        # keep trailing block components (b×b packs send (B, b) bufs)
        return all_bufs[order].reshape((-1,) + buf.shape[1:])
    parts = []
    for d in dists:
        # source s delivers to (s − d) mod P ⇒ rank p receives from p+d
        perm = [(s, (s - d) % n_parts) for s in range(n_parts)]
        parts.append(jax.lax.ppermute(buf, axis, perm))
    return jnp.concatenate(parts)


def exchange_halo(A: ShardedMatrix, x: jax.Array, ring: int = 1
                  ) -> jax.Array:
    """Gather the ring-``ring`` halo values of sharded ``x``: returns a
    (P, H_ring) array whose row p holds the values of
    ``partition.rings[ring-1].halo_global[p]`` (reference
    ``exchange_halo``, rings machinery of ``vector.h:38-51``)."""
    if ring not in (1, 2):
        raise BadParametersError(f"halo ring must be 1 or 2, got {ring}")
    from ..telemetry import recorder as _trecorder
    _count_halo(A, ring, "exchange_halo")
    _tel_exchange(A, ring, "exchange_halo")
    # span over the host-level call: real wall time when eager, the
    # dispatch/trace cost under jit (the executed collective shows up in
    # the device profile, not the host ring)
    sid = _trecorder.span_begin("exchange_halo",
                                {"ring": ring, "n_parts": A.n_parts})
    try:
        axis = A.axis
        send_idx = A.send_idx if ring == 1 else A.send_idx2
        halo_src = A.halo_src if ring == 1 else A.halo_src2
        dists = A.dists if ring == 1 else A.dists2

        def local(si, hs, xl):
            buf = xl[si[0]]
            got = _exchange(buf, dists, axis, A.n_parts)
            return got[hs[0]][None]

        with _tscopes.scope("dist", "halo_exchange"):
            return _shard_map(
                local, mesh=A.mesh,
                in_specs=(P(axis, None), P(axis, None), P(axis)),
                out_specs=P(axis, None),
            )(send_idx, halo_src, x)
    finally:
        _trecorder.span_end(sid, "exchange_halo")


def interior_path(A: ShardedMatrix) -> str:
    """The interior SpMV a sharded apply takes: ``"window"`` (the
    windowed one-hot Pallas kernel of ops/pallas_ell.py), ``"gather"``
    (an XLA ``x[cols]`` gather) or ``"block"`` (b×b einsums over a
    gather).  ops/spmv.py labels the dispatch with it."""
    if A.block_dim > 1:
        return "block"
    from ..ops.pallas_ell import _INTERPRET
    # gate on the MESH's platform, not the process default backend — a
    # CPU debug mesh on a TPU host must take the gather path
    if A.win_blocks is not None and (
            A.mesh.devices.flat[0].platform == "tpu" or _INTERPRET):
        return "window"
    return "gather"


def dist_spmv(A: ShardedMatrix, x: jax.Array) -> jax.Array:
    """y = A·x for a flat sharded x of length P·n_loc (call under jit).

    Interior/boundary latency hiding (``multiply.cu:113-196``): the
    interior term reads only local x (halo slots as zeros) and carries no
    data dependency on the exchange, so XLA's async collectives overlap
    the ppermutes with the bulk gather/multiply; boundary rows then get a
    small gathered correction scattered back through a trash slot.
    """
    axis = A.axis
    n_parts = A.n_parts
    if A.block_dim > 1:
        return _dist_spmv_block(A, x)
    use_win = interior_path(A) == "window"
    from ..telemetry import recorder as _trecorder
    _count_halo(A, 1, "dist_spmv")
    _tel_dist_spmv(A)
    sid = _trecorder.span_begin(
        "dist_spmv", {"n_parts": n_parts, "n_loc": A.n_loc,
                      "interior": "win" if use_win else "gather"})

    def interior_gather(cols, vals, xfull0, _wb, _wc, _wv):
        return jnp.sum(vals * xfull0[cols], axis=1)

    def interior_win(cols, vals, xfull0, wb, wc, wv):
        # windowed one-hot Pallas kernel — a per-chip gather would
        # otherwise throttle every shard (ops/pallas_ell.py)
        from ..ops.pallas_ell import _ell_window_call
        n_loc = cols.shape[0]
        T, K = A.win_tile, A.ell_width
        n_pad = wc.shape[0] // K
        n_tiles = n_pad // T
        B = wb.shape[0] // n_tiles
        m_pad = -(-xfull0.shape[0] // 128) * 128
        x2 = jnp.pad(xfull0, (0, m_pad - xfull0.shape[0])) \
            .reshape(-1, 128)
        return _ell_window_call(wb, wc[None, :], wv[None, :], x2, T,
                                (n_tiles, B, K)).reshape(-1)[:n_loc]

    interior = interior_win if use_win else interior_gather

    def local(cols, vals, send_idx, halo_src, bnd_rows, wb, wc, wv, xl):
        cols, vals = cols[0], vals[0]
        send_idx, halo_src, bnd = send_idx[0], halo_src[0], bnd_rows[0]
        n_loc_r = cols.shape[0]       # output (row) shard size
        n_loc_c = xl.shape[0]         # input (column) shard size
        H = halo_src.shape[0]
        with _tscopes.scope("dist", "halo_exchange"):
            buf = xl[send_idx]                              # B2L gather
            got = _exchange(buf, A.dists, axis, n_parts)
            hvals = got[halo_src]                           # (H,)
        # interior: halo slots read zero — independent of the exchange
        xfull0 = jnp.concatenate([xl, jnp.zeros((H,), xl.dtype)])
        y0 = interior(cols, vals, xfull0, wb[0], wc[0], wv[0])
        # boundary rows get a small gathered correction scattered back
        # through a trash slot
        with _tscopes.scope("dist", "boundary"):
            rows = jnp.minimum(bnd, n_loc_r - 1)
            cb = cols[rows]                                 # (Bd, K)
            vb = vals[rows]
            hb = jnp.where(cb >= n_loc_c,
                           vb * hvals[jnp.clip(cb - n_loc_c, 0, H - 1)],
                           0.0)
            corr = jnp.sum(hb, axis=1)                      # (Bd,)
            yext = jnp.zeros((n_loc_r + 1,), xl.dtype).at[bnd].add(corr)
        return y0 + yext[:n_loc_r]

    # the win arrays always ride the shard_map signature (dummy scalars
    # when absent) so both paths share one body
    zeros = jnp.zeros((n_parts, 1), jnp.int32)
    wb = A.win_blocks if A.win_blocks is not None else zeros
    wc = A.win_codes if A.win_codes is not None else zeros
    wv = A.win_vals if A.win_vals is not None else \
        jnp.zeros((n_parts, 1), A.vals.dtype)
    try:
        return _shard_map(
            local, mesh=A.mesh,
            in_specs=(P(axis, None, None), P(axis, None, None),
                      P(axis, None), P(axis, None), P(axis, None),
                      P(axis, None), P(axis, None), P(axis, None),
                      P(axis)),
            out_specs=P(axis),
            # the pallas_call's out_shape carries no varying-mesh-axes
            # annotation — skip the vma check
            check_vma=False,
        )(A.cols, A.vals, A.send_idx, A.halo_src, A.bnd_rows, wb, wc, wv,
          x)
    finally:
        _trecorder.span_end(sid, "dist_spmv")


def _dist_spmv_block(A: ShardedMatrix, x: jax.Array) -> jax.Array:
    """Block (b×b) distributed SpMV: same interior/boundary split, halo
    exchange carries (B, b) block values, contractions are batched
    einsums (the b×b MXU path)."""
    axis, n_parts, b = A.axis, A.n_parts, A.block_dim
    from ..telemetry import recorder as _trecorder
    _count_halo(A, 1, "dist_spmv")
    _tel_dist_spmv(A)
    sid = _trecorder.span_begin(
        "dist_spmv", {"n_parts": n_parts, "n_loc": A.n_loc,
                      "interior": "block", "block_dim": b})

    def local(cols, vals, send_idx, halo_src, bnd_rows, xl):
        cols, vals = cols[0], vals[0]
        send_idx, halo_src, bnd = send_idx[0], halo_src[0], bnd_rows[0]
        n_loc = cols.shape[0]
        H = halo_src.shape[0]
        xb = xl.reshape(n_loc, b)
        with _tscopes.scope("dist", "halo_exchange"):
            buf = xb[send_idx]                              # (B, b)
            got = _exchange(buf, A.dists, axis, n_parts)    # (D·B, b)
            hvals = got[halo_src]                           # (H, b)
        xfull0 = jnp.concatenate([xb, jnp.zeros((H, b), xl.dtype)])
        xg = xfull0[cols]                                   # (n,K,b)
        y0 = jnp.einsum("nkab,nkb->na", vals, xg,
                        preferred_element_type=vals.dtype)
        with _tscopes.scope("dist", "boundary"):
            rows = jnp.minimum(bnd, n_loc - 1)
            cb = cols[rows]                                 # (Bd, K)
            vb = vals[rows]                                 # (Bd,K,b,b)
            hg = hvals[jnp.clip(cb - n_loc, 0, H - 1)]      # (Bd,K,b)
            hb = jnp.einsum("nkab,nkb->na", vb,
                            jnp.where((cb >= n_loc)[..., None], hg, 0.0),
                            preferred_element_type=vals.dtype)
            yext = jnp.zeros((n_loc + 1, b), xl.dtype).at[bnd].add(hb)
        return (y0 + yext[:n_loc]).reshape(-1)

    try:
        return _shard_map(
            local, mesh=A.mesh,
            in_specs=(P(axis, None, None),
                      P(axis, None, None, None, None),
                      P(axis, None), P(axis, None), P(axis, None),
                      P(axis)),
            out_specs=P(axis),
        )(A.cols, A.vals, A.send_idx, A.halo_src, A.bnd_rows, x)
    finally:
        _trecorder.span_end(sid, "dist_spmv")


def vector_sharding(A: ShardedMatrix) -> NamedSharding:
    return NamedSharding(A.mesh, P(A.axis))


def shard_vector(A: ShardedMatrix, v) -> jax.Array:
    """Pad a real-sized global vector to P·n_loc·b and place it sharded.

    The padded layout is rank-major: rank p's real (block) rows land at
    [p·n_loc, p·n_loc + count_p), ×b scalar entries each.
    """
    # chaos harness (utils/faultinject.py): the halo_exchange point
    # fails the distributed solve at its host seam — the sharded
    # placement every halo'd SpMV depends on — with the device-error RC
    # the reference's communicator failures map to
    from ..utils import faultinject
    if faultinject.active():
        from ..errors import RC, AMGXError
        faultinject.maybe_raise(
            "halo_exchange",
            AMGXError("injected halo-exchange failure", RC.CUDA_FAILURE))
    v = np.asarray(v)
    n = A.n_parts * A.n_loc * A.block_dim
    if v.shape[0] == n:
        return jax.device_put(v.astype(A.dtype), vector_sharding(A))
    out = np.zeros(n, dtype=A.dtype)
    out[_pad_map_cached(A)] = v
    return jax.device_put(out, vector_sharding(A))


def unshard_vector(A: ShardedMatrix, v: jax.Array) -> np.ndarray:
    """Gather a padded sharded vector back to real global ordering."""
    return np.asarray(v)[_pad_map_cached(A)]


_padmap_cache = {}


def _pad_map_cached(A: ShardedMatrix) -> np.ndarray:
    key = (A.offsets, A.n_loc, A.block_dim)
    if key not in _padmap_cache:
        pm = pad_map(np.asarray(A.offsets), A.n_loc)
        b = A.block_dim
        if b > 1:
            # block pad map → scalar entries
            pm = (pm[:, None] * b + np.arange(b)[None, :]).reshape(-1)
        _padmap_cache[key] = pm
    return _padmap_cache[key]
