"""The colour-slab sweep shared by the coloured smoothers (DILU, ILU, GS).

Reference: the per-colour kernels of ``multicolor_dilu_solver.cu``,
``multicolor_ilu_solver.cu`` and ``multicolor_gauss_seidel_solver.cu``.

Rows of one colour are independent, so each colour's rows are packed at
setup into a compact ELL slab (rows, padded columns, values).  A sweep
visits the colours in order; for each it takes the row dot of the slab
with the vector and writes the colour's rows — O(nnz) per sweep, whatever
the colour count.  The same function runs on one device and, on a mesh,
on each rank's local rows inside the caller's ``shard_map``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.matrix import ell_layout
from ..core.precision import compute_dtype


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["rows", "cols", "vals"], meta_fields=[])
@dataclasses.dataclass
class ColorSlab:
    """One colour's compact row slab: ELL rows with (block) column ids.

    Stacked per rank on a mesh, each array gains a leading rank axis.  A
    slab whose ``cols``/``vals`` are None selects them from a whole ELL
    pack by its rows (see :func:`sweep`)."""

    rows: Any       # (nc,) int32 — this colour's (block) rows
    cols: Any       # (nc, K) int32
    vals: Any       # (nc, K[, b, b])


def split_lower_upper(mat, colors):
    """(L, U): the strict lower and upper parts of a CSR or BSR matrix in
    colour-rank order — (i, j) lies in L where colors[j] < colors[i] and
    in U where colors[j] > colors[i].  A scalar part drops the entries of
    the other parts; a block part keeps the whole pattern, with zero
    blocks."""
    rows = np.repeat(np.arange(len(mat.indptr) - 1), np.diff(mat.indptr))
    trail = (1,) * (mat.data.ndim - 1)
    parts = []
    for keep in (colors[mat.indices] < colors[rows],
                 colors[mat.indices] > colors[rows]):
        part = type(mat)((np.where(keep.reshape(keep.shape + trail),
                                   mat.data, 0.0),
                          mat.indices.copy(), mat.indptr.copy()),
                         shape=mat.shape)
        if mat.data.ndim == 1:
            part.eliminate_zeros()
        parts.append(part)
    return parts


def build_color_slabs(mat, colors, num_colors, dtype, block_dim=1,
                      device=True):
    """Per-colour packed ELL slabs of a sparse matrix, read as scalar CSR
    (``block_dim`` 1) or as b×b BSR: cols are (block) column ids, vals
    (nc, K[, b, b]); ``device=False`` keeps host arrays (a sharded setup
    stacks them per rank with :func:`stack_color_slabs`)."""
    b = block_dim
    mat = sp.csr_matrix(mat) if b == 1 else \
        sp.bsr_matrix(mat, blocksize=(b, b))
    wrap = jnp.asarray if device else (lambda x: x)
    # each stored (block) entry's position in mat.data, on its pattern
    ids = sp.csr_matrix(
        (np.arange(len(mat.indices)), mat.indices, mat.indptr),
        shape=(mat.shape[0] // b, mat.shape[1] // b))
    slabs = []
    for c in range(num_colors):
        rows = np.flatnonzero(colors == c)
        sub = ids[rows]
        sub.sort_indices()
        for_rows, pos, k = ell_layout(sub.indptr, sub.indices)
        cols = np.zeros((len(rows), k), dtype=np.int32)
        vals = np.zeros((len(rows), k) + mat.data.shape[1:], dtype=dtype)
        cols[for_rows, pos] = sub.indices
        vals[for_rows, pos] = mat.data[sub.data]
        slabs.append(ColorSlab(wrap(rows.astype(np.int32)), wrap(cols),
                               wrap(vals)))
    return slabs


def shard_leading(a, mesh, axis):
    """Place a host array with a leading rank axis sharded over ``axis``."""
    return jax.device_put(a, NamedSharding(mesh,
                                           P(axis, *[None] * (a.ndim - 1))))


def _stack(arrays, fill):
    """(P, ...) stack of per-rank arrays, each padded with ``fill`` to the
    largest rank's shape (at least 1 along every axis)."""
    shape = np.maximum(np.max([a.shape for a in arrays], axis=0), 1)
    out = np.full((len(arrays), *shape), fill, dtype=arrays[0].dtype)
    for p, a in enumerate(arrays):
        out[(p, *map(slice, a.shape))] = a
    return out


def stack_color_slabs(per_rank, pad_row, mesh, axis):
    """Each colour's per-rank host slabs as one :class:`ColorSlab` of
    (P, R[, K[, b, b]]) arrays sharded over the mesh axis.  Pad rows name
    ``pad_row`` — the trash slot of the sweep's vector — with zero
    columns and values."""
    out = []
    for slabs in zip(*per_rank):          # one colour, every rank
        rows = shard_leading(_stack([s.rows for s in slabs], pad_row),
                             mesh, axis)
        if slabs[0].cols is None:
            out.append(ColorSlab(rows, None, None))
            continue
        out.append(ColorSlab(
            rows,
            shard_leading(_stack([s.cols for s in slabs], 0), mesh, axis),
            shard_leading(_stack([s.vals for s in slabs], 0), mesh, axis)))
    return out


def _rowdot(vals, g):
    """Σ_k vals[i, k]·g[i, k] per slab row, scalar or b×b: sub-f32 slab
    values (a bf16 hierarchy) accumulate in f32 — the floor every SpMV
    path applies (core/precision.py)."""
    if vals.ndim == 2:
        return jnp.sum(vals * g, axis=1)
    pet = jnp.promote_types(compute_dtype(vals.dtype), g.dtype)
    return jnp.einsum("nkab,nkb->na", vals, g, preferred_element_type=pet)


def sweep(v, slabs, order, update, *, add=False, n_loc=None, pack=None):
    """One colour-ordered sweep over ``v`` ((n,) scalar or (n, b) block
    rows).  For each colour ``c`` in ``order``, with ``i`` its rows:

        t = Σ_k vals[i, k]·v[cols[i, k]]
        v[i] = update(i, t)        (v[i] += update(i, t) with ``add``)

    so later colours read the rows earlier ones wrote.  ``n_loc`` marks
    one rank's local rows on a mesh: slab rows past it are pads, which
    read the last local row and write the trash slot they name.  With
    ``pack=(cols, vals)`` — a rank's whole ELL pack — the slabs hold rows
    only and each colour gathers its columns and values from the pack."""
    for c in order:
        s = slabs[c]
        rows = s.rows if n_loc is None else jnp.minimum(s.rows, n_loc - 1)
        cols, vals = (s.cols, s.vals) if pack is None else \
            (pack[0][rows], pack[1][rows])
        new = update(rows, _rowdot(vals, v[cols]))
        v = v.at[s.rows].add(new) if add else v.at[s.rows].set(new)
    return v
