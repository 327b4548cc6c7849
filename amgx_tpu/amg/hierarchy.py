"""AMG hierarchy: the setup loop.

Reference: ``base/src/amg.cu`` — the ``AMG`` class (level list, coarse
solver, setup-loop parameters, ``amg.cu:69-82``) and the hot setup loop
``AMG_Setup::setup`` (``amg.cu:177-450``): per level —

1. termination checks (``max_levels``, ``min_coarse_rows``),
2. createCoarseVertices (selector),
3. coarsening-rate guard (``coarsen_threshold``, ``amg.cu:394``),
4. createCoarseMatrices (interpolation + Galerkin RAP),
5. setup_smoother.

Setup runs on host (irregular graph work); every produced level is a frozen
device pack.  Structure reuse across re-setups (``structure_reuse_levels``,
``amg.cu:260-290``) keeps selector/interpolation structure and refreshes
numeric values only.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np
import scipy.sparse as sp

from .. import telemetry
from ..telemetry import setup_profile
from ..config import AMGConfig
from ..core.matrix import Matrix
from ..errors import BadConfigurationError
from ..solvers.base import SolverFactory
from ..ops.spgemm import pad_to_symbolic
from ..utils.logging import amgx_output
from ..utils.profiler import cpu_profiler
from .aggregation.galerkin import galerkin_coarse
from .aggregation.selectors import create_selector
from .classical.interpolators import create_interpolator
from .classical.selectors import create_cf_selector
from .classical.strength import create_strength
from .level import (AggregationLevel, AMGLevel, ClassicalLevel,
                    PairwiseLevel, StructuredLevel)
from .pairwise import dia_arrays, dia_to_scipy, pairwise_galerkin_dia
from .structured import (coarse_dims, decompose_offsets, infer_grid_dims,
                         stencil_values_consistent, structured_galerkin)


#: sentinel: the structured pairwise path declined (too irregular) and the
#: caller should retry with a graph-matching selector
_PAIRWISE_FALLBACK = object()


def _tiebreak_seed(cfg: AMGConfig) -> int:
    """THE PMIS/coarsening tie-break seed — one definition for BOTH the
    device classical pipeline and the host/fallback classical paths, so
    pipeline-on/off A/B runs select the SAME coarse grids and differ
    only in representation.

    Always the deterministic value 7, whatever ``determinism_flag``
    says: several compiled programs are keyed on the REALIZED coarse
    offset sets, which follow the PMIS outcome — a fixed seed makes
    them identical run to run, so the persistent compile cache always
    hits.  (determinism_flag=0 promises nothing about ordering; a
    deterministic select is a valid instance of it, the same reasoning
    as utils.determinism.SESSION_SEED.)  ``cfg`` is taken on purpose:
    the signature documents that the flag deliberately does NOT alter
    the value."""
    del cfg
    return 7


def _child_matrix(parent: Matrix, a, block_dim: int = 1) -> Matrix:
    """A hierarchy child matrix inheriting the parent's device dtype
    (mixed precision flows down the whole hierarchy)."""
    m = Matrix(a, block_dim=block_dim)
    m.device_dtype = parent.device_dtype
    m.placement = parent.placement
    return m


def _drop_zero_diagonals(offs, vals: np.ndarray):
    """Drop stored all-zero diagonals (the main diagonal always stays).

    They carry no numerics, but their offsets participate in the
    structured-vs-pairwise gate — a stored zero diagonal whose offset
    breaks the stencil decode would silently demote a 2×2×2-coarsenable
    operator to 1D pairing.  Returns ``(offs, vals, keep)`` with ``keep``
    None when nothing was dropped, else the kept row indices (used to
    slice the matching rows out of an already-uploaded device pack)."""
    offs = list(offs)
    nonzero = (vals != 0).any(axis=1) | (np.asarray(offs) == 0)
    if nonzero.all():
        return offs, vals, None
    keep = np.flatnonzero(nonzero)
    return [offs[int(k)] for k in keep], vals[keep], keep


def _require_dia(cur: Matrix):
    """DIA arrays for a structure-reuse refresh; a clear error when the
    refreshed matrix no longer admits the recorded DIA structure (e.g. a
    block or rectangular matrix handed to resetup)."""
    arrs = cur.dia_cache()
    if arrs is None:
        raise BadConfigurationError(
            "resetup: recorded hierarchy structure is DIA-based but the "
            "refreshed matrix has no diagonal decomposition — call "
            "setup() for a structural rebuild")
    offs, vals, _ = _drop_zero_diagonals(*arrs)
    return offs, vals


def _narrow_dia(cur: Matrix, arrs):
    """Mixed precision: coarse GRIDS live in the device dtype — they are
    preconditioner data (outer refinement owns final accuracy, the
    reference's dDFI split); narrowing before the Galerkin halves its
    bandwidth and makes every coarse pack a zero-copy view.

    The narrowing FLOORS at f32: an 8-bit-mantissa (bf16) Galerkin
    product would distort the hierarchy itself, so sub-f32 device
    dtypes keep the setup math in f32 and the values are cast at
    upload (``Matrix.device`` / the precision policy's views)."""
    dd = np.dtype(cur.device_dtype) if cur.device_dtype is not None \
        else None
    if dd is not None:
        from ..core.precision import compute_dtype
        dd = compute_dtype(dd)
    if dd is not None and dd.itemsize < arrs[1].dtype.itemsize:
        return (arrs[0], arrs[1].astype(dd))
    return arrs


def _child_matrix_dia(parent: Matrix, offsets, vals) -> Matrix:
    """DIA-native hierarchy child: the coarse operator stays in diagonal
    form end to end (device pack, further coarsening, smoother diag) and
    its scipy view assembles lazily only if a consumer asks — this is what
    keeps setup O(one pass over the fine operator)."""
    m = Matrix.from_dia(offsets, vals)
    m.device_dtype = parent.device_dtype
    m.placement = parent.placement
    return m


def _first_member_order(agg: np.ndarray, nc: int) -> Optional[np.ndarray]:
    """Coarse ids in the order of each aggregate's first fine row
    (``order[new] = old``), or None when ``agg`` is numbered so already."""
    _, first = np.unique(agg, return_index=True)
    order = np.argsort(first, kind="stable")
    if np.array_equal(order, np.arange(nc)):
        return None
    return order


def _permute_host(host, order: np.ndarray, b: int):
    """``Π host Πᵀ`` for the block-row permutation ``order`` (new → old):
    the stored entries move, their values are untouched."""
    m = host if b == 1 else sp.bsr_matrix(host, blocksize=(b, b))
    n = len(order)
    inv = np.empty_like(order)
    inv[order] = np.arange(n)
    slot = sp.csr_matrix((np.arange(1, len(m.indices) + 1), m.indices,
                          m.indptr), shape=(n, n))[order]
    cls = sp.csr_matrix if b == 1 else sp.bsr_matrix
    return cls((m.data[slot.data - 1], inv[slot.indices], slot.indptr),
               shape=m.shape)


def _bandwidth(m: Matrix) -> Optional[int]:
    """max |i − j| over the stored (block) entries of a level operator,
    from what the matrix already holds (no device download); None when
    it holds neither host rows nor diagonal offsets."""
    if m._dia is not None:
        return max(abs(int(o)) for o in m._dia[0])
    if m._host is not None:
        h = m._host
        rows = np.repeat(np.arange(len(h.indptr) - 1), np.diff(h.indptr))
        return int(np.abs(rows - h.indices).max()) if h.nnz else 0
    if getattr(m._device, "fmt", None) == "dia":
        return max(abs(int(o)) for o in m._device.dia_offsets)
    return None


class AMGHierarchy:
    def __init__(self, cfg: AMGConfig, scope: str):
        self.cfg = cfg
        self.scope = scope
        g = lambda name: cfg.get(name, scope)
        self.algorithm = str(g("algorithm"))
        self.max_levels = int(g("max_levels"))
        self.min_coarse_rows = int(g("min_coarse_rows"))
        self.min_fine_rows = int(g("min_fine_rows"))
        self.coarsen_threshold = float(g("coarsen_threshold"))
        self.cycle_type = str(g("cycle"))
        self.presweeps = int(g("presweeps"))
        self.postsweeps = int(g("postsweeps"))
        self.finest_sweeps = int(g("finest_sweeps"))
        self.coarsest_sweeps = int(g("coarsest_sweeps"))
        self.cycle_iters = int(g("cycle_iters"))
        self.structure_reuse_levels = int(g("structure_reuse_levels"))
        #: levels with ≤ this many rows compute on the HOST inside the
        #: same executable (reference amg_host_levels_rows, amg.h:169-173
        #: — coarse levels on CPU while fine levels run on the device)
        self.host_levels_rows = int(g("amg_host_levels_rows"))
        self.dense_lu_num_rows = int(g("dense_lu_num_rows"))
        self.dense_lu_max_rows = int(g("dense_lu_max_rows"))
        self.print_grid_stats = bool(g("print_grid_stats"))
        self.aggressive_levels = int(g("aggressive_levels"))
        #: coarse-correction scaling (aggregation levels; reference
        #: aggregation_amg_level.cu:740-860): 2 = minimise residual
        #: 2-norm, 3 = minimise error A-norm, 0 = off
        self.error_scaling = int(g("error_scaling"))
        self.scaling_smoother_steps = int(g("scaling_smoother_steps"))
        #: convergence forensics (telemetry/forensics.py): cycle-anatomy
        #: instrumentation in build_cycle + setup-time quality probes
        self.forensics = int(g("forensics"))
        #: mixed precision (core/precision.py): storage dtype of level
        #: operators, smoother data and transfer packs from
        #: ``mixed_precision_from_level`` down; None = inherit (a
        #: sub-f32 fine-matrix device_dtype implies the policy so the
        #: tpu_matrix_dtype=bfloat16 path narrows device-born levels
        #: too).  Setup math (strength/interp/RAP) always runs at f32+
        #: — values are narrowed at upload or by a device-side cast.
        from ..core.precision import resolve_dtype
        self.hierarchy_dtype = resolve_dtype(str(g("hierarchy_dtype")))
        self.mixed_from_level = int(g("mixed_precision_from_level"))
        #: device-side setup engine (amg/device_setup/): route the
        #: classical/aggregation Galerkin RAP through pattern-keyed
        #: device SpGEMM executables (host scipy stays the fallback)
        self.device_setup = int(g("device_setup"))
        self.device_setup_min_rows = int(g("device_setup_min_rows"))
        self.device_setup_cache_mb = int(g("device_setup_cache_mb"))
        #: coarse-level agglomeration (distributed/agglomerate.py —
        #: AmgX's shrinking-communicator consolidation, amg.cu:328-390):
        #: below this many rows per ACTIVE rank a distributed coarse
        #: level migrates onto a P/factor sub-mesh (0 disables)
        self.dist_agglomerate_min_rows = int(
            g("dist_agglomerate_min_rows"))
        self.dist_agglomerate_factor = int(g("dist_agglomerate_factor"))
        self.levels: List[AMGLevel] = []
        self.coarse_solver = None
        self.coarse_solver_is_smoother = False
        self._structure: Optional[list] = None  # for structure reuse

    # ------------------------------------------------------------------ setup
    def setup(self, A: Matrix):
        t0 = time.perf_counter()
        reuse = (self._structure is not None and
                 self.structure_reuse_levels != 0 and A.dist is None)
        try:
            with cpu_profiler("amg_setup_reuse" if reuse
                              else "amg_setup"):
                if reuse:
                    self._setup_reuse(A)
                else:
                    self._setup_fresh(A)
        except BaseException:
            # a partial structure must never feed a later reuse pass;
            # a streaming uploader must not outlive the failed setup
            st = getattr(self, "_stream_uploader", None)
            if st is not None:
                try:
                    st.join_threads()
                except Exception:
                    pass
                self._stream_uploader = None
            self._structure = None
            self.levels = []
            raise
        self.setup_time = time.perf_counter() - t0
        self._register_memledger()
        if telemetry.is_enabled():
            self._emit_telemetry()
            if self.forensics:
                # hierarchy quality probes (telemetry/forensics.py):
                # near-nullspace preservation, sampled Galerkin
                # consistency, CF/coarsening ratios, strength sample —
                # best-effort, a probe gap must never break setup
                from ..telemetry import forensics
                try:
                    with cpu_profiler("forensics_probes"), \
                            setup_profile.phase("probes"):
                        forensics.probe_hierarchy(self)
                except Exception:
                    pass
        if self.print_grid_stats:
            # informational table: verbosity level 2 (the reference
            # prints it through the same gated output stream)
            amgx_output(self.grid_stats(), level=2)
        return self

    def _setup_fresh(self, A: Matrix):
        self.levels = []
        self._structure = []
        self._cla_plans = None
        cur = self._build_levels(A)
        self._setup_smoothers_and_coarse(cur)
        if self.structure_reuse_levels != 0:
            with cpu_profiler("classical_resetup_plans"), \
                    setup_profile.phase("resetup_plan"):
                self._build_classical_plans(A, cur)

    def _build_classical_plans(self, A: Matrix, coarsest: Matrix):
        """Host-symbolic resetup schedules (classical/resetup_device.py)
        — built only when the user configured structure reuse, so a
        later ``AMGX_solver_resetup`` refreshes every Galerkin product
        ON DEVICE (csr_multiply.h:100-126 numeric-phase analog)."""
        if A.dist is not None or not self.levels or \
                not all(s[0] == "classical" for s in self._structure):
            return
        if 0 < self.structure_reuse_levels < len(self.levels):
            # partial reuse re-coarsens a suffix fresh — the device
            # refresh path can't consume these plans; don't pay the
            # symbolic build for dead weight
            return
        Ad = A.device()
        if Ad.fmt != "dia":
            return
        from .classical.resetup_device import (build_level_plan,
                                               fine_dia_to_csr_map)
        dtype = np.dtype(A.device_dtype or A.dtype)
        try:
            fine_csr = A.scalar_csr()
            fine_map = fine_dia_to_csr_map(fine_csr, Ad.dia_offsets)
        except Exception:
            return
        plans = []
        cur_csr = fine_csr
        for i, (_, data) in enumerate(self._structure):
            P_host, = data
            nxt = self.levels[i + 1].A if i + 1 < len(self.levels) \
                else coarsest
            Ac_csr = sp.csr_matrix(nxt.host)
            plan = build_level_plan(cur_csr, P_host, Ac_csr, dtype,
                                    template=nxt.device())
            if plan is None:
                return
            plans.append(plan)
            cur_csr = Ac_csr
        # boolean mask of the DIA slots the recorded CSR pattern maps —
        # a resetup value lighting up OUTSIDE it must fall back to the
        # host replay (the frozen schedule cannot carry the new entry)
        mask = np.zeros(len(Ad.dia_offsets) * A.n_block_rows, dtype=bool)
        mask[fine_map] = True
        self._cla_plans = dict(levels=plans,
                               fine_offsets=tuple(Ad.dia_offsets),
                               fine_n=A.n_block_rows,
                               fine_map=fine_map, fine_map_dev=None,
                               fine_mask=mask)

    def _level_pack_mats(self, level):
        """(matrices, lean-exception ids) of one level's packs — shared
        by the streaming uploader and the final arena upload so the two
        can never diverge: the fine level (index 0) ships NON-lean (its
        gather-form cols/vals feed mixed-precision refinement)."""
        mats = [level.A]
        if hasattr(level, "transfer_matrices"):
            mats.extend(level.transfer_matrices())
        lean_except = {id(level.A)} if self.levels and \
            self.levels[0] is level else set()
        return mats, lean_except

    def _build_levels(self, cur: Matrix) -> Matrix:
        """Run the fresh coarsening loop from ``cur``, appending to
        ``self.levels`` / ``self._structure``; returns the coarsest matrix
        (reference hot setup loop, ``amg.cu:177-450``).

        Classical serial setups STREAM each finished level's packs to the
        device on a worker thread while the next level coarsens on host:
        otherwise the hierarchy transfer serialises after all host work
        (the reference's uploads ride a CUDA stream concurrently with
        setup for the same reason).  The transfer releases the GIL, so
        host coarsening and the upload genuinely overlap;
        ``_setup_smoothers_and_coarse`` drains the stream before touching
        any pack."""
        cur = self._build_dia_device(cur)
        if self.algorithm == "CLASSICAL":
            nxt = self._build_classical_device_pipeline(cur)
            if nxt is not None:
                cur = nxt
        first_coarsened = len(self.levels)
        stream = None
        if self.algorithm == "CLASSICAL" and cur.dist is None:
            from ..utils.thread_manager import ThreadManager
            stream = ThreadManager(
                max_workers=1,
                serialize=bool(self.cfg.get("serialize_threads")))
            stream.spawn_threads()
            self._stream_uploader = stream
        while True:
            n = cur.n_block_rows
            if len(self.levels) + 1 >= self.max_levels:
                break
            if n <= self.min_coarse_rows:
                break
            with cpu_profiler(f"coarsen_level_{len(self.levels)}"), \
                    setup_profile.phase("coarsen",
                                        level=len(self.levels)):
                level, Ac, struct = self._coarsen_once(cur,
                                                       len(self.levels))
            if level is None:
                break
            nc = Ac.n_block_rows
            # coarsening-rate guard (amg.cu:394): stop if the grid stops
            # shrinking
            if nc >= self.coarsen_threshold * n or nc >= n or nc == 0:
                break
            self.levels.append(level)
            self._structure.append(struct)
            if stream is not None and getattr(level, "kind", "") == \
                    "classical":
                from ..core.matrix import batch_upload
                mats, lean_except = self._level_pack_mats(level)

                def _stream_upload(ms=mats, le=lean_except,
                                   li=len(self.levels) - 1):
                    # runs on the streaming worker thread: its upload
                    # phase OVERLAPS the main-thread coarsening (the
                    # setup-profile analyzer reports it separately and
                    # excludes it from wall-clock coverage)
                    with setup_profile.phase("upload", level=li,
                                             kind="device"):
                        batch_upload(ms, lean_except=le)

                stream.push_work(_stream_upload)
            cur = Ac
        return self._relabel_aggregation_levels(first_coarsened, cur)

    def _relabel_aggregation_levels(self, first: int,
                                    coarsest: Matrix) -> Matrix:
        """Number the coarse unknowns of the single-device aggregation
        levels from ``first`` on by each aggregate's first fine row;
        returns the coarsest matrix, renumbered with them.

        Selectors number pairs by their lower node and append leftover
        singletons, so a coarse operator's couplings land up to ~n
        columns apart, level after level, past the windowed SpMV
        kernel's gate.  The renumbering runs after the whole chain is
        built with the selectors' own numbering, so each selector saw
        the operator (and tie-breaks) it always sees: the aggregates stay
        the same partitions and each coarse operator becomes
        ``Π A Πᵀ`` of the one built.  A level's coarse side is renumbered
        only when the next level aggregates too (a DIA level pairs by
        index) or it is the coarsest.  ``_structure`` records the
        renumbered aggregates, so a re-setup's Galerkin products come
        out renumbered directly."""
        n_lv = len(self.levels)
        fine = None     # the renumbered fine matrix of level i, or None
        fine_order = None
        for i in range(first, n_lv):
            kind, data = self._structure[i]
            if kind != "aggregation":
                continue
            agg, nc = data
            if fine_order is not None:
                agg = agg[fine_order]
            order = None
            if i + 1 == n_lv or self._structure[i + 1][0] == "aggregation":
                order = _first_member_order(agg, nc)
            if order is not None:
                new_id = np.empty(nc, dtype=agg.dtype)
                new_id[order] = np.arange(nc, dtype=agg.dtype)
                agg = new_id[agg]
            if fine is not None or order is not None:
                A = fine if fine is not None else self.levels[i].A
                self.levels[i] = AggregationLevel(A, i, agg, nc)
                self._structure[i] = ("aggregation", (agg, nc))
            fine, fine_order = None, order
            if order is None:
                continue
            old = self.levels[i + 1].A if i + 1 < n_lv else coarsest
            b = old.block_dim
            fine = _child_matrix(old, _permute_host(old.host, order, b),
                                 block_dim=b)
        return fine if fine is not None else coarsest

    def _setup_reuse(self, A: Matrix):
        """Keep coarsening structure; refresh numeric values
        (``structure_reuse_levels``: N levels reuse structure; the rest of
        the hierarchy is re-coarsened fresh from the last reused level,
        reference ``amg.cu:260-290``)."""
        cur = A
        old = list(zip(self.levels, self._structure))
        self.levels = []
        self._structure = []
        if self._reuse_classical_device(cur, old):
            return
        consumed, cur = self._reuse_dia_device(cur, old)
        for i, (level, struct) in enumerate(old):
            if i < consumed:
                continue
            if 0 < self.structure_reuse_levels <= i:
                break
            kind, data = struct
            if kind == "aggregation":
                agg, nc = data
                with setup_profile.phase("rap", level=i):
                    Ac_host = self._galerkin_agg(cur, agg, i)
                lvl = AggregationLevel(cur, i, agg, nc)
                nxt = _child_matrix(cur, Ac_host, block_dim=cur.block_dim)
            elif kind == "pairwise":
                n_f, = data
                offs_c, vals_c = self._pairwise_numeric(
                    _narrow_dia(cur, _require_dia(cur)))
                lvl = PairwiseLevel(cur, i, n_f)
                nxt = _child_matrix_dia(cur, offs_c, vals_c)
            elif kind == "structured":
                dims, = data
                offs, vals = _narrow_dia(cur, _require_dia(cur))
                offs3 = decompose_offsets(offs, dims)
                if offs3 is None or \
                        not stencil_values_consistent(offs3, vals, dims):
                    # a value-only refresh can light up a previously
                    # all-zero diagonal the recorded decode never saw
                    raise BadConfigurationError(
                        "resetup: refreshed values no longer admit the "
                        "recorded structured stencil (a diagonal that was "
                        "all-zero at setup became coupled) — call setup() "
                        "for a structural rebuild")
                flat, vals_c, cdims = self._structured_numeric(
                    offs3, vals, dims)
                lvl = StructuredLevel(cur, i, dims, cdims)
                nxt = _child_matrix_dia(cur, flat, vals_c)
                nxt.grid_dims = cdims
            else:
                P_host, = data
                R_host = sp.csr_matrix(P_host.T)
                Asc_r = cur.scalar_csr()
                with setup_profile.phase("rap", level=i):
                    # CLASSICAL keeps the full symbolic pattern across
                    # resetups so recorded device plans stay applicable
                    Ac_host = self._galerkin_classical(
                        cur, Asc_r, R_host, P_host, i,
                        keep_pattern=self.algorithm == "CLASSICAL")
                lvl = ClassicalLevel(cur, i,
                                     _child_matrix(cur, P_host),
                                     _child_matrix(cur, R_host))
                nxt = _child_matrix(cur, Ac_host, block_dim=cur.block_dim)
            self.levels.append(lvl)
            self._structure.append(struct)
            cur = nxt
        # rebuild any remaining levels fresh from the reused prefix
        cur = self._build_levels(cur)
        self._setup_smoothers_and_coarse(cur)
        # a fresh-rebuilt suffix may change coarse patterns: recorded
        # device-resetup plans are only kept when the structure still
        # matches what they were built for
        plans = getattr(self, "_cla_plans", None)
        if plans is not None and (
                len(self._structure) != len(plans["levels"])
                or any(s[0] != "classical" for s in self._structure)):
            self._cla_plans = None

    def _reuse_classical_device(self, cur: Matrix, old) -> bool:
        """Value-only refresh of a fully-reused classical hierarchy ON
        DEVICE (classical/resetup_device.py): two segment-sum
        contractions per level, no host Galerkin.  False → the generic
        host replay takes over (partial reuse, changed offsets, no
        recorded plans)."""
        plans = getattr(self, "_cla_plans", None)
        if not plans or len(plans["levels"]) != len(old):
            return False
        if 0 < self.structure_reuse_levels < len(old):
            return False          # partial reuse: host replay handles it
        if any(struct[0] != "classical" for _, struct in old):
            return False
        curd = cur.device()
        if curd.fmt != "dia" or \
                tuple(curd.dia_offsets) != plans["fine_offsets"] or \
                cur.n_block_rows != plans["fine_n"]:
            # same offsets but different n would gather out of range —
            # and JAX clamps indices silently
            return False
        arrs = cur.dia_cache()
        if arrs is None or np.any(
                arrs[1].reshape(-1)[~plans["fine_mask"]]):
            # a value lit up a slot the recorded CSR pattern never
            # mapped: the frozen schedule can't represent it — the host
            # replay recomputes patterns and stays correct
            return False
        import jax
        from .classical.resetup_device import (assemble_refreshed_matrix,
                                               refresh_level)
        dtype = np.dtype(cur.device_dtype or cur.dtype)
        if plans["fine_map_dev"] is None:
            plans["fine_map_dev"] = jax.device_put(
                plans["fine_map"].astype(np.int32))
        with cpu_profiler("classical_device_resetup"), \
                setup_profile.phase("resetup_device", kind="device"):
            vA = curd.vals.reshape(-1)[plans["fine_map_dev"]]
            for i, (level, struct) in enumerate(old):
                plan = plans["levels"][i]
                vAc, fields = refresh_level(plan, vA, dtype)
                nxt = assemble_refreshed_matrix(plan, vAc, fields, dtype)
                lvl = ClassicalLevel(cur, i, level.P, level.R,
                                     getattr(level.A, "cf_map", None))
                self.levels.append(lvl)
                self._structure.append(struct)
                cur = nxt
                vA = vAc
        self._setup_smoothers_and_coarse(cur)
        return True

    def _dia_plan_inputs(self, cur: Matrix, max_diags: int = 48):
        """(offsets, host vals, dims-or-None) of a DIA-eligible matrix —
        THE single home of the structured-vs-pairwise gate (grid-dims
        attach/inference, offset decomposition, wrap-coupling value
        check); shared by the device plan, the host ``_coarsen_pairwise``
        loop, and the device reuse refresh so the three can never
        drift.  None when ``cur`` has no DIA decomposition."""
        if cur.block_dim != 1 or cur.n_block_rows < 2:
            return None
        n = cur.n_block_rows
        hint = getattr(cur, "_dia_offsets_hint", None)
        if hint is not None and getattr(cur, "_stencil_consistent", False):
            # device-GENERATED stencils (io/device_gen.py) declare their
            # offsets and consistency analytically — the plan never
            # materialises host values (vals=None; the device derive
            # consumes the on-chip pack, the host fallback re-fetches)
            offs = [int(o) for o in hint]
            if len(offs) > max_diags:
                return None
            dims = getattr(cur, "grid_dims", None)
            if dims is not None and int(np.prod(dims)) != n:
                dims = None
            if dims is None:
                dims = infer_grid_dims(offs, n)
            if dims is not None and max(dims) > 1 and \
                    decompose_offsets(offs, dims) is None:
                dims = None
            return offs, None, dims, None
        arrs = cur.dia_cache(max_diags)
        if arrs is None:
            return None
        # gate on the NARROWED diagonal set (stored all-zero diagonals
        # dropped) so the plan, the host loop, and the resetup refresh
        # (_require_dia narrows the same way) can never disagree
        offs, vals, keep = _drop_zero_diagonals(*arrs)
        dims = getattr(cur, "grid_dims", None)
        if dims is not None and int(np.prod(dims)) != n:
            dims = None
        if dims is None:
            dims = infer_grid_dims(offs, n)
        if dims is not None and max(dims) > 1:
            offs3 = decompose_offsets(offs, dims)
            if offs3 is None or \
                    not stencil_values_consistent(offs3, vals, dims):
                dims = None      # periodic/wrap stencil: decode is a lie
        return offs, vals, dims, keep

    def _dia_device_eligible(self, cur: Matrix) -> bool:
        """Device-derivation gates on top of DIA eligibility: the GEO
        aggregation path, single-device, no placement pinning (pinned
        host modes keep the host loop so the pack stays on their
        device)."""
        if self.algorithm != "AGGREGATION":
            return False
        name = str(self.cfg.get("selector", self.scope))
        if name not in ("GEO", "PAIRWISE"):
            return False
        return cur.dist is None and cur.placement is None

    def _append_dia_levels(self, cur: Matrix, steps, outs) -> Matrix:
        """Materialise planned DIA levels around the device-derived
        (vals, diag, dinv) outputs; returns the coarsest matrix."""
        cur._dinv_dev = (np.dtype(cur.device().dtype), outs[0][1])
        for st, (vals_c, diag_c, dinv_c) in zip(steps, outs[1:]):
            idx = len(self.levels)
            if st.kind == "structured":
                level = StructuredLevel(cur, idx, st.dims, st.cdims)
                struct = ("structured", (st.dims,))
            else:
                level = PairwiseLevel(cur, idx, st.n)
                struct = ("pairwise", (st.n,))
            Ac = Matrix.from_dia_device(st.c_offsets, vals_c, diag_c,
                                        dinv_c)
            Ac.placement = cur.placement
            if st.kind == "structured":
                Ac.grid_dims = st.cdims
            self.levels.append(level)
            self._structure.append(struct)
            cur = Ac
        return cur

    def _build_dia_device(self, cur: Matrix) -> Matrix:
        """Accelerated setup for the structured/pairwise DIA hierarchy:
        plan every coarsening decision statically from the stencil
        structure, then derive ALL coarse levels' values + smoother
        diagonals on the device in one jitted pass (amg/dia_device.py —
        the reference's on-accelerator setup loop, ``amg.cu:177-450``).
        Returns the coarsest planned matrix; falls through untouched (the
        generic host loop takes over) when ``cur`` is not DIA-eligible."""
        from .dia_device import derive_hierarchy_device, plan_dia_hierarchy
        if not self._dia_device_eligible(cur):
            return cur
        inputs = self._dia_plan_inputs(cur)
        if inputs is None:
            return cur
        offs, vals, dims, keep = inputs
        steps, _bailed = plan_dia_hierarchy(
            offs, cur.n_block_rows, dims, self.max_levels,
            self.min_coarse_rows, self.coarsen_threshold,
            existing_levels=len(self.levels))
        if not steps:
            return cur
        curd = cur.device()
        if curd.fmt != "dia":
            return cur
        dvals = curd.vals if keep is None else curd.vals[keep]
        with cpu_profiler("dia_device_derive"), \
                setup_profile.phase("dia_derive", kind="device"):
            outs = self._derive_dia_f32(steps, offs, dvals)
        return self._append_dia_levels(cur, steps, outs)

    @staticmethod
    def _derive_dia_f32(steps, offs, dvals):
        """Run the device hierarchy derivation with the Galerkin math in
        f32+ even when the fine pack stores bf16 (the narrowing rule:
        RAP never computes below f32); outputs are cast back to the
        storage dtype on device."""
        from ..core.precision import is_sub_f32
        from .dia_device import derive_hierarchy_device
        store_dt = dvals.dtype
        narrow = is_sub_f32(store_dt)
        if narrow:
            dvals = dvals.astype(np.float32)
        outs = derive_hierarchy_device(steps, offs, dvals)
        if narrow:
            outs = [tuple(a.astype(store_dt) for a in o) for o in outs]
        return outs

    def _reuse_dia_device(self, cur: Matrix, old) -> tuple:
        """Numeric refresh of a reused structured/pairwise prefix ON
        DEVICE (one jitted pass, amg/dia_device.py) — the resetup analog
        of the reference's device-side value-only Galerkin refresh
        (``csr_multiply.h:100-126``).  Returns (levels consumed, coarsest
        matrix); (0, cur) falls back to the per-level host refresh."""
        from .dia_device import derive_hierarchy_device, plan_dia_hierarchy
        prefix = []
        for i, (_, struct) in enumerate(old):
            if 0 < self.structure_reuse_levels <= i:
                break
            if struct[0] not in ("structured", "pairwise"):
                break
            prefix.append(struct)
        if not prefix:
            return 0, cur
        if not self._dia_device_eligible(cur):
            return 0, cur
        inputs = self._dia_plan_inputs(cur)
        if inputs is None:
            return 0, cur
        offs, _, dims, keep = inputs
        steps, _ = plan_dia_hierarchy(
            offs, cur.n_block_rows, dims, self.max_levels,
            self.min_coarse_rows, self.coarsen_threshold)
        # refresh the LONGEST matching prefix on device; a tail the
        # recorded (possibly host-built) structure disagrees on falls to
        # the per-level host refresh below
        matched = 0
        for st, (kind, data) in zip(steps, prefix):
            if st.kind != kind or \
                    (kind == "structured" and st.dims != tuple(data[0])) \
                    or (kind == "pairwise" and st.n != data[0]):
                break
            matched += 1
        if matched == 0:
            return 0, cur
        steps = steps[:matched]
        curd = cur.device()
        if curd.fmt != "dia":
            return 0, cur
        dvals = curd.vals if keep is None else curd.vals[keep]
        with cpu_profiler("dia_device_derive"), \
                setup_profile.phase("dia_derive", kind="device"):
            outs = self._derive_dia_f32(steps, offs, dvals)
        return len(steps), self._append_dia_levels(cur, steps, outs)

    #: below this logical size the device pipeline hands the tail to the
    #: host algorithms (a ≤4k-row download is ~1 MB; host finishes in ms)
    _PIPELINE_TAIL_ROWS = 4096

    def _pipeline_tail_rows(self) -> int:
        import os
        v = os.environ.get("AMGX_PIPELINE_TAIL_ROWS")
        return int(v) if v else self._PIPELINE_TAIL_ROWS

    def _classical_pipeline_eligible(self, cur: Matrix):
        """Static gates of the fully-device classical pipeline; returns
        the (offsets, keep, params) inputs or None (host path)."""
        import os
        if os.environ.get("AMGX_NO_DEVICE_PIPELINE") == "1":
            return None
        if cur.dist is not None or cur.block_dim != 1 or \
                cur.placement is not None:
            return None
        if self.structure_reuse_levels != 0 or self.aggressive_levels:
            return None
        if len(self.levels) + 1 >= self.max_levels or \
                cur.n_block_rows <= max(self.min_coarse_rows,
                                        self._pipeline_tail_rows()):
            return None
        g = lambda p: self.cfg.get(p, self.scope)
        sel = str(g("selector"))
        interp = str(g("interpolator"))
        sname = str(g("strength"))
        if sel != "PMIS" or interp not in ("D1", "D2") or \
                sname not in ("AHAT", "ALL"):
            return None
        # smoothers that set up from the device pack alone — a colored
        # smoother would download the multi-GB embedded level for its
        # host coloring pass
        smoother = str(self.cfg.get("smoother", self.scope))
        if smoother not in ("JACOBI_L1", "BLOCK_JACOBI", "JACOBI"):
            return None
        if self.cycle_type not in ("V", "W", "F"):
            return None
        if getattr(self, "host_levels_rows", -1) > 0 or \
                getattr(self, "error_scaling", 0) in (2, 3):
            return None
        inputs = self._dia_plan_inputs(cur, max_diags=16)
        if inputs is None:
            return None
        offs = inputs[0]
        if any(-o not in offs for o in offs):
            return None          # one-sided stencil: host path
        from .classical.device_fine import ahat_plan
        if interp == "D2" and len(ahat_plan(offs)[0]) > 48:
            return None
        params = dict(
            theta=float(g("strength_threshold")),
            max_row_sum=float(g("max_row_sum")),
            strength_all=sname == "ALL", interp_d2=interp == "D2",
            trunc_factor=float(g("interp_truncation_factor")),
            max_elements=int(g("interp_max_elements")))
        return offs, inputs[3], params

    def _build_classical_device_pipeline(self, cur: Matrix):
        """Fully-device classical setup (classical/device_pipeline.py +
        device_coarse.py): the fine level coarsens by shift algebra into
        an EMBEDDED coarse operator (a fine-grid DIA matrix — solve
        SpMVs ride the Pallas DIA kernel), deeper levels by the compact
        sort-algebra pipeline, until the ≤4k tail is handed back to the
        host loop.  Returns the tail matrix, or None when any gate sends
        the whole setup down the existing host path.

        Reference: the on-accelerator setup loop of
        ``classical_amg_level.cu:240-340`` + ``csr_multiply.h:100-126``
        — here the hierarchy is born on the device and only a ~1 MB tail
        ever crosses the wire."""
        elig = self._classical_pipeline_eligible(cur)
        if elig is None:
            return None
        offs, keep, params = elig
        curd = cur.device()
        if curd.fmt != "dia":
            return None
        # HBM guard: the embedded RAP materialises (candidate Δ, n) —
        # ~2.9 GB at 128³.  Past ~8 GB (256³ would need 23 GB) the host
        # path takes over rather than OOMing the chip.
        from .classical.device_pipeline import (ahat_plan,
                                                rap_candidate_offsets)
        from ..core.precision import compute_dtype
        p_offs = ahat_plan(offs)[0] if params["interp_d2"] else offs
        n_cand = len(rap_candidate_offsets(offs, p_offs))
        # the pipeline's Galerkin math runs at the COMPUTE dtype (f32
        # floor — see _narrow_dia's narrowing rule), so the HBM guard
        # sizes the f32 intermediates even for a bf16 fine pack
        itemsize = compute_dtype(
            np.dtype(cur.device_dtype or cur.dtype)).itemsize
        if n_cand * cur.n_block_rows * itemsize > (8 << 30):
            return None
        import jax.numpy as jnp

        from ..core.matrix import _dia_device_matrix
        from ..ops.device_pack import device_ell_matrix
        from .classical.device_coarse import coarsen_compact
        from .classical.device_pipeline import coarsen_fine_embedded
        # shared tie-break seed (_tiebreak_seed documents the
        # compile-cache rationale; the fallback paths read the same one)
        seed = _tiebreak_seed(self.cfg)
        n = cur.n_block_rows
        dvals = curd.vals if keep is None else curd.vals[keep]
        from ..core.precision import is_sub_f32
        if is_sub_f32(dvals.dtype):
            # strength/PMIS/interpolation/RAP never compute below f32;
            # the precision policy narrows the resulting level PACKS
            # afterwards (setup math wide, storage narrow)
            dvals = dvals.astype(jnp.float32)
        with cpu_profiler("classical_device_fine_embedded"), \
                setup_profile.phase("device_fine", level=0,
                                    kind="device"):
            res = coarsen_fine_embedded(offs, dvals, n, seed=seed,
                                        **params)
        if res is None or res.nc >= self.coarsen_threshold * n or \
                res.nc <= max(self.min_coarse_rows,
                              self._pipeline_tail_rows()):
            # too-small coarse grid: the embedded level-0 transfers
            # would feed a tail that must stay embedded-sized — at these
            # sizes the host path is already fast
            return None
        # ---- level 0: P/R as embedded DIA packs ----
        h0 = res.p_offs.index(0)
        P0 = _dia_device_matrix(res.p_offs, res.P_rows,
                                res.P_rows[h0], n_cols=n)
        r_offs = tuple(-o for o in res.p_offs[::-1])
        R0 = _dia_device_matrix(r_offs, jnp.flip(res.R_rows, axis=0),
                                res.P_rows[h0], n_cols=n)
        lvl0 = ClassicalLevel(cur, len(self.levels), P0, R0, None)
        nnz1 = int(jnp.count_nonzero(res.A_vals))
        import os as _os
        if _os.environ.get("AMGX_L1_EMBEDDED_DIRECT") == "1":
            # materialised embedded DIA (199+ offsets, ~4% fill): kept
            # behind a switch for kernel comparisons
            A1m = Matrix.from_dia_device(res.a_offs, res.A_vals,
                                         ddiag=res.diag, dinv=res.dinv)
        else:
            # solve representation = the Galerkin COMPOSITION
            # R·(A·(P·x)): three dense-fill DIA streams, ~3x the
            # apply speed and ~4x less HBM than the embedded matrix
            from ..core.matrix import ComposedDIA
            A1m = Matrix.from_device_pack(ComposedDIA(
                P=P0, A=curd, R=R0, diag=res.diag, l1row=res.l1row,
                n_rows=n, n_cols=n))
            A1m._dinv_dev = (np.dtype(A1m.device_dtype), res.dinv)
            # the materialised embedded block (~1.7 GB at 128³) has
            # served its purpose (diag/l1/compaction): free it before
            # the compact levels allocate their expansion blocks
            res.A_vals = None
        A1m.logical_rows = res.nc
        A1m._nnz_hint = nnz1
        self.levels.append(lvl0)
        self._structure.append(("classical-device", ()))
        # ---- compact continuation ----
        cur_m, cols, vals, n_log = A1m, res.cols, res.vals, res.nc
        foc = res.foc            # embedded↔compact map of level 1
        with cpu_profiler("classical_device_coarse_levels"), \
                setup_profile.phase("device_coarse", kind="device"):
            while True:
                if len(self.levels) + 1 >= self.max_levels or \
                        n_log <= max(self.min_coarse_rows,
                                     self._pipeline_tail_rows()):
                    break
                out = coarsen_compact(cols, vals, n_log, seed=seed,
                                      **params)
                if out is None or out.nc >= \
                        self.coarsen_threshold * n_log or \
                        out.nc >= n_log:
                    break
                nb, Kpx = out.P_cols.shape
                if foc is not None:
                    # embedded boundary: P rows live at the C points'
                    # fine indices; R columns address the embedded
                    # vector — pad foc entries (== n) drop on scatter
                    pce = jnp.zeros((n, Kpx), jnp.int32).at[foc].set(
                        out.P_cols, mode="drop")
                    pve = jnp.zeros((n, Kpx), vals.dtype).at[foc].set(
                        out.P_vals, mode="drop")
                    rc_src = jnp.where(
                        out.R_cols >= 0,
                        foc[jnp.maximum(out.R_cols, 0)], -1)
                    p_rows_space = n
                else:
                    pce, pve = out.P_cols, out.P_vals
                    rc_src = out.R_cols
                    p_rows_space = nb
                Pd = device_ell_matrix(pce, pve, p_rows_space,
                                       out.ncb2, square_diag=False)
                Rd = device_ell_matrix(rc_src, out.R_vals, out.ncb2,
                                       p_rows_space, square_diag=False)
                lvl = ClassicalLevel(cur_m, len(self.levels), Pd, Rd,
                                     None)
                Acd = device_ell_matrix(out.Ac_cols, out.Ac_vals,
                                        out.ncb2, out.ncb2)
                nxt = Matrix.from_device_pack(
                    Acd, nnz_hint=int(jnp.count_nonzero(out.Ac_vals)),
                    logical_rows=out.nc)
                self.levels.append(lvl)
                self._structure.append(("classical-device", ()))
                cur_m, cols, vals, n_log = nxt, out.Ac_cols, \
                    out.Ac_vals, out.nc
                foc = None
        if cur_m is A1m:
            # no compact level materialised (degenerate coarsening right
            # below the fine level): a host continuation would need the
            # multi-GB embedded matrix — unwind and let the host path
            # redo this setup from scratch
            self.levels.pop()
            self._structure.pop()
            return None
        # ---- tail: hand the (small, padded) matrix to the host loop
        with cpu_profiler("classical_device_tail_download"), \
                setup_profile.phase("tail_download", kind="device"), \
                setup_profile.transfer(int(cols.nbytes)
                                       + int(vals.nbytes), 2,
                                       "download"):
            cur_m._host = self._compact_to_host(cols, vals)
            cur_m.dtype = np.dtype(np.float64)
        return cur_m

    @staticmethod
    def _compact_to_host(cols, vals) -> sp.csr_matrix:
        """Download a compact device ELL level into host CSR (f64 — the
        host tail algorithms and the dense coarse factorisation run at
        setup precision, matching the uploaded-matrix path)."""
        cc = np.asarray(cols)
        cv = np.asarray(vals).astype(np.float64)
        nb, K = cc.shape
        rows = np.repeat(np.arange(nb), K)
        flat_c = cc.reshape(-1)
        flat_v = cv.reshape(-1)
        keepm = (flat_v != 0) | (flat_c == rows)
        M = sp.csr_matrix(
            (flat_v[keepm], (rows[keepm], flat_c[keepm])),
            shape=(nb, nb))
        M.sum_duplicates()
        M.sort_indices()
        return M

    def _coarsen_classical_device_fine(self, cur: Matrix, idx: int,
                                       strength, sel_name: str,
                                       interp_name: str):
        """Device-side classical coarsening for DIA-eligible levels
        (classical/device_fine.py); None when any gate fails — the host
        path is the fallback, not an error."""
        if sel_name != "PMIS" or interp_name not in ("D1", "D2"):
            return None
        sname = getattr(strength, "config_name", "")
        if sname not in ("AHAT", "ALL"):
            return None
        inputs = self._dia_plan_inputs(cur, max_diags=16)
        if inputs is None:
            return None
        offs, _, _, keep = inputs
        if any(-o not in offs for o in offs):
            # the device PMIS symmetrises the strength graph via the
            # opposite-offset rows; a one-sided stencil would lose its
            # reverse influence edges — host path handles it
            return None
        curd = cur.device()
        if curd.fmt != "dia":
            return None
        from .classical.device_fine import ahat_plan, classical_fine_device
        if interp_name == "D2" and len(ahat_plan(offs)[0]) > 48:
            return None
        dvals = curd.vals if keep is None else curd.vals[keep]
        # same seed as the device pipeline (_tiebreak_seed): pipeline
        # on/off A/B runs must differ only in representation
        seed = _tiebreak_seed(self.cfg)
        g = lambda p: self.cfg.get(p, self.scope)
        with cpu_profiler("classical_fine_device"), \
                setup_profile.phase("device_fine", level=idx,
                                    kind="device"):
            cf_map, P_host = classical_fine_device(
                offs, dvals, cur.n_block_rows,
                float(g("strength_threshold")), float(g("max_row_sum")),
                sname == "ALL", interp_name == "D2",
                float(g("interp_truncation_factor")),
                int(g("interp_max_elements")), seed)
        nc = int(cf_map.sum())
        if nc == 0 or nc >= cur.n_block_rows:
            return None, None, None
        Asc = cur.scalar_csr()
        P_host = P_host.astype(Asc.dtype)
        R_host = sp.csr_matrix(P_host.T)
        with setup_profile.phase("rap", level=idx):
            Ac_host = self._galerkin_classical(
                cur, Asc, R_host, P_host, idx,
                keep_pattern=self.structure_reuse_levels != 0)
        level = ClassicalLevel(cur, idx, _child_matrix(cur, P_host),
                               _child_matrix(cur, R_host), cf_map)
        return level, _child_matrix(cur, Ac_host), \
            ("classical", (P_host,))

    def _coarsen_once(self, cur: Matrix, idx: int):
        if self.algorithm == "AGGREGATION":
            name = str(self.cfg.get("selector", self.scope))
            if name == "PAIRWISE":    # alias for the structured GEO path
                name = "GEO"
            if name == "GEO" and cur.block_dim == 1 and cur.dist is None:
                out = self._coarsen_pairwise(cur, idx)
                if out is not _PAIRWISE_FALLBACK:
                    return out
                if getattr(cur, "geometry", None) is None:
                    name = "SIZE_2"  # irregular AND no coordinates
            selector = create_selector(name, self.cfg, self.scope)
            if cur.dist is not None:
                return self._coarsen_aggregation_dist(cur, idx, selector)
            Asc = cur.scalar_csr() if cur.block_dim == 1 else \
                _block_condensed(cur)
            geom = getattr(cur, "geometry", None)
            if geom is not None:
                # attached per-row coordinates feed the GEO selector
                # (AMGX_matrix_attach_geometry → geo_selector.cu)
                Asc._amgx_geometry = geom
            with setup_profile.phase("selector", level=idx):
                agg = selector.select(Asc)
            nc = int(agg.max()) + 1 if len(agg) else 0
            if nc == 0:
                return None, None, None
            with setup_profile.phase("rap", level=idx):
                Ac_host = self._galerkin_agg(cur, agg, idx)
            level = AggregationLevel(cur, idx, agg, nc)
            Ac = _child_matrix(cur, Ac_host, block_dim=cur.block_dim)
            if geom is not None:
                # coarse-level geometry = aggregate centroids, so GEO
                # keeps aggregating geometrically below the fine level
                cnt = np.bincount(agg, minlength=nc).astype(np.float64)
                Ac.geometry = tuple(
                    np.bincount(agg, weights=np.asarray(c, np.float64),
                                minlength=nc) / np.maximum(cnt, 1)
                    for c in geom)
            return level, Ac, ("aggregation", (agg, nc))
        elif self.algorithm in ("CLASSICAL", "ENERGYMIN"):
            if cur.block_dim != 1:
                raise BadConfigurationError(
                    "classical AMG requires block_dim=1 (use AGGREGATION "
                    "for block systems), as in the reference defaults")
            strength = create_strength(
                str(self.cfg.get("strength", self.scope)), self.cfg,
                self.scope)
            sel_name = str(self.cfg.get("selector", self.scope))
            interp_name = str(self.cfg.get("interpolator", self.scope))
            if self.algorithm == "ENERGYMIN":
                sel_name = str(self.cfg.get("energymin_selector", self.scope))
                interp_name = str(self.cfg.get("energymin_interpolator",
                                               self.scope))
            # aggressive coarsening on the first `aggressive_levels` levels
            # switches selector/interpolator (classical_amg_level.cu:155-201)
            if idx < self.aggressive_levels:
                asel = str(self.cfg.get("aggressive_selector", self.scope))
                if asel == "DEFAULT":
                    asel = "AGGRESSIVE_" + sel_name \
                        if not sel_name.startswith("AGGRESSIVE") else sel_name
                sel_name = asel
                interp_name = str(self.cfg.get("aggressive_interpolator",
                                               self.scope))
            if cur.dist is not None:
                # per-rank distributed classical setup — never assembles
                # a global matrix (classical_amg_level.cu:240-340)
                out = self._coarsen_classical_dist(
                    cur, idx, strength, sel_name, interp_name)
                if out is not None:
                    return out
            elif self.algorithm == "CLASSICAL":
                # DIA (stencil) fine levels run strength+PMIS+interp ON
                # DEVICE in one jitted pass (classical/device_fine.py —
                # the classical_amg_level.cu:240-340 analog); scattered
                # coarse levels fall through to the host algorithms
                out = self._coarsen_classical_device_fine(
                    cur, idx, strength, sel_name, interp_name)
                if out is not None:
                    return out
            Asc = cur.scalar_csr()
            with setup_profile.phase("strength", level=idx):
                S = strength.compute(Asc)
            selector = create_cf_selector(sel_name, self.cfg, self.scope)
            with setup_profile.phase("selector", level=idx):
                cf_map = selector.select(S)
            nc = int(cf_map.sum())
            if nc == 0 or nc >= Asc.shape[0]:
                return None, None, None
            interp = create_interpolator(interp_name, self.cfg, self.scope)
            with setup_profile.phase("interpolation", level=idx):
                P_host = interp.compute(Asc, S, cf_map).astype(Asc.dtype)
            R_host = sp.csr_matrix(P_host.T)
            with setup_profile.phase("rap", level=idx):
                if cur.dist is None:
                    Ac_host = self._galerkin_classical(
                        cur, Asc, R_host, P_host, idx,
                        keep_pattern=self.algorithm == "CLASSICAL"
                        and self.structure_reuse_levels != 0)
                else:
                    # distributed fallback: per-rank RAP owns the hot
                    # path; this global product is correctness-only
                    Ac_host = sp.csr_matrix(R_host @ Asc @ P_host) \
                        .astype(Asc.dtype)
                    Ac_host.sum_duplicates()
                    Ac_host.sort_indices()
            if cur.dist is not None:
                # fallback (non-row-local strength, HMIS/RS, MULTIPASS,
                # consolidation-small grids): embed P/R into the padded
                # vector spaces; transfer matmuls run under GSPMD
                # (correctness path — the hot per-level SpMV still uses
                # the halo pack)
                from ..distributed.matrix import embed_padded
                mesh, axis, _, _ = cur.dist
                curd = cur.device()
                f_off = np.asarray(curd.offsets)
                nc = P_host.shape[1]
                n_parts = curd.n_parts
                c_nloc = -(-nc // n_parts)
                c_off = np.minimum(np.arange(n_parts + 1) * c_nloc, nc)
                P_pad = embed_padded(P_host, f_off, curd.n_loc, c_off,
                                     c_nloc)
                R_pad = sp.csr_matrix(P_pad.T)
                Ac = _child_matrix(cur, Ac_host)
                Ac.set_distribution(mesh, axis, c_off, n_loc=c_nloc)
                level = ClassicalLevel(
                    cur, idx, _child_matrix(cur, P_pad).device(),
                    _child_matrix(cur, R_pad).device(), None)
                return level, Ac, ("classical", (P_host,))
            level = ClassicalLevel(
                cur, idx, _child_matrix(cur, P_host),
                _child_matrix(cur, R_host), cf_map)
            return level, _child_matrix(cur, Ac_host), ("classical", (P_host,))
        raise BadConfigurationError(f"unknown AMG algorithm "
                                    f"{self.algorithm!r}")

    def _coarsen_classical_dist(self, cur: Matrix, idx: int, strength,
                                sel_name: str, interp_name: str):
        """Per-rank distributed classical coarsening
        (amg/classical/distributed.py): per-rank strength + PMIS with
        exchanged halo C/F states, per-rank P rows through the ring-2
        extended blocks, per-rank RAP with owner-summed partials, and
        sharded rectangular P/R packs.  Returns None when the config
        needs the global fallback (non-row-local strength, HMIS/RS
        selectors, MULTIPASS interpolation).

        Reference: ``classical_amg_level.cu:240-340`` +
        ``distributed_arranger.h:223-231``.
        """
        if sel_name != "PMIS" or interp_name not in ("D1", "D2"):
            return None
        if getattr(type(strength), "config_name", "") not in ("AHAT",
                                                              "ALL"):
            return None
        from ..distributed.matrix import shard_matrix_from_blocks
        from ..distributed.partition import build_partition_from_blocks
        from .classical.distributed import (RankExtended,
                                            coarse_numbering_distributed,
                                            interpolate_distributed,
                                            pmis_distributed,
                                            rap_distributed,
                                            strength_distributed)
        mesh, axis, _, _ = cur.dist
        curd = cur.device()
        offsets = np.asarray(curd.offsets)
        n_parts = curd.n_parts
        n = int(offsets[-1])
        blocks = self._rank_blocks(cur, offsets)
        part = build_partition_from_blocks(blocks, offsets, n_rings=2)
        exts = [RankExtended(p, blocks, part) for p in range(n_parts)]
        # same seed as the device pipeline (_tiebreak_seed): pipeline
        # on/off A/B runs must differ only in representation
        seed = _tiebreak_seed(self.cfg)
        S_U = strength_distributed(exts, [strength] * n_parts)
        cf_loc, ex = pmis_distributed(exts, S_U, n, seed)
        nc = int(sum(int(c.sum()) for c in cf_loc))
        if nc == 0 or nc >= n:
            return None, None, None
        c_off, cf_U, cnum_U = coarse_numbering_distributed(exts, cf_loc,
                                                           n, ex)
        interp = create_interpolator(interp_name, self.cfg, self.scope)
        P_blocks = interpolate_distributed(exts, interp, cf_U, cnum_U,
                                           S_U, nc)
        dtype = np.dtype(blocks[0].dtype)
        P_blocks = [sp.csr_matrix(Pb.astype(dtype)) for Pb in P_blocks]
        # shard-local device Galerkin (amg/device_setup/): each rank's
        # RAP partial runs through the pattern-keyed engine — host scipy
        # stays the per-rank fallback
        eng = self._device_setup_engine()
        c_blocks, r_blocks = rap_distributed(
            blocks, P_blocks, part, c_off, engine=eng,
            dtype=self._galerkin_dtype(dtype), level=idx,
            min_rows=self.device_setup_min_rows,
            budget_bytes=self.device_setup_cache_mb << 20)
        c_blocks = [sp.csr_matrix(cb.astype(dtype)) for cb in c_blocks]
        # coarse-level agglomeration (distributed/agglomerate.py): below
        # dist_agglomerate_min_rows rows per active rank, migrate the
        # coarse level onto a shrinking sub-mesh — the redistribution
        # packs are cached so resetup replays them
        submesh = None
        if self.dist_agglomerate_min_rows > 0:
            from ..distributed.agglomerate import (plan_for,
                                                   redistribute_blocks)
            plan = plan_for(c_off, self.dist_agglomerate_min_rows,
                            self.dist_agglomerate_factor, level=idx)
            if plan is not None:
                c_blocks = redistribute_blocks(c_blocks, plan)
                r_blocks = redistribute_blocks(r_blocks, plan)
                c_off = np.asarray(plan.dst_offsets)
                submesh = plan.p_active
        nc_loc = max(int(np.max(np.diff(c_off))), 1)
        Ac = Matrix()
        Ac.set_distributed_blocks(c_blocks, c_off, mesh, axis=axis)
        Ac.dist = (mesh, axis, c_off, nc_loc)
        Ac.device_dtype = cur.device_dtype
        Ac.placement = cur.placement
        ddtype = np.dtype(cur.device_dtype or cur.dtype)
        Pd = shard_matrix_from_blocks(
            P_blocks, offsets, mesh, axis=axis, dtype=ddtype,
            n_loc=curd.n_loc, col_offsets=c_off, n_loc_cols=nc_loc)
        Rd = shard_matrix_from_blocks(
            r_blocks, c_off, mesh, axis=axis, dtype=ddtype,
            n_loc=nc_loc, col_offsets=offsets, n_loc_cols=curd.n_loc)
        level = ClassicalLevel(cur, idx, Pd, Rd, None)
        # the sub-mesh rides the level so cycles, doctor and grid stats
        # can see which communicator slice a level lives on
        from ..distributed.agglomerate import active_parts
        level.submesh_parts = submesh if submesh is not None else \
            active_parts(c_off)
        return level, Ac, ("classical-dist", (nc,))

    def _coarsen_pairwise(self, cur: Matrix, idx: int,
                          max_diags: int = 48):
        """Structured GEO path (amg/pairwise.py): DIA-preserving pairwise
        coarsening with reshape transfers; returns ``_PAIRWISE_FALLBACK``
        when the operator has too many distinct diagonals for the DIA
        representation (caller retries with a matching selector).
        ``max_diags`` matches ``pack_device``'s ``dia_max_diags`` so every
        level this path produces really is packed gather-free."""
        n = cur.n_block_rows
        if n < 2:
            return None, None, None   # stop coarsening here
        # shared structured-vs-pairwise gate (2×2×2 cells when the grid
        # geometry is known/inferable — geo_selector.cu analog — with
        # wrap-coupling detection; 1D index pairing otherwise)
        inputs = self._dia_plan_inputs(cur, max_diags)
        if inputs is None:
            return _PAIRWISE_FALLBACK
        offs_raw, vals_raw, dims, _keep = inputs
        if vals_raw is None:     # hint-gated plan: host path needs values
            vals_raw = cur.dia_cache(max_diags)[1]
        arrs = _narrow_dia(cur, (offs_raw, vals_raw))
        offs, vals = arrs
        if dims is not None and max(dims) > 1:
            offs3 = decompose_offsets(offs, dims)
            if offs3 is not None:
                with setup_profile.phase("rap", level=idx):
                    out = self._structured_numeric(offs3, vals, dims)
                if out is not None:
                    flat, vals_c, cdims = out
                    level = StructuredLevel(cur, idx, dims, cdims)
                    Ac = _child_matrix_dia(cur, flat, vals_c)
                    Ac.grid_dims = cdims
                    return level, Ac, ("structured", (dims,))
        with setup_profile.phase("rap", level=idx):
            offs_c, vals_c = self._pairwise_numeric(arrs)
        level = PairwiseLevel(cur, idx, n)
        Ac = _child_matrix_dia(cur, offs_c, vals_c)
        return level, Ac, ("pairwise", (n,))

    @staticmethod
    def _structured_numeric(offs3, vals, dims):
        """Numeric pipeline for the grid-structured path; None when the
        coarse grid would not shrink (all dims already 1).  Returns the
        coarse operator in DIA form (flat offsets, vals, cdims)."""
        cdims = coarse_dims(dims)
        if int(np.prod(cdims)) >= int(np.prod(dims)):
            return None
        return structured_galerkin(offs3, vals, dims)

    @staticmethod
    def _pairwise_numeric(arrs):
        """Shared numeric pipeline (fresh + structure-reuse paths):
        diagonal arrays → pairwise Galerkin, DIA in / DIA out."""
        offs, vals = arrs
        return pairwise_galerkin_dia(offs, vals)

    # ------------------------------------------- device setup engine
    def _device_setup_engine(self):
        """The process-wide device setup engine, or None when the
        ``device_setup`` knob disables it (the host paths then run
        without even consulting the engine — no fallback events)."""
        if not self.device_setup:
            return None
        from .device_setup import engine
        return engine()

    @staticmethod
    def _galerkin_dtype(host_dtype) -> np.dtype:
        """Numeric dtype of a device Galerkin pass: the HOST dtype off
        TPU (bit-comparable to the scipy product it replaces); on TPU —
        where f64 has no native lowering — always f32: coarse grids are
        preconditioner data (the same narrowing ``_narrow_dia`` applies
        to DIA hierarchies), and a bf16 device dtype still RAPs in f32
        because an 8-bit-mantissa Galerkin product would distort the
        hierarchy itself."""
        import jax
        if jax.default_backend() == "tpu":
            return np.dtype(np.float32)
        return np.dtype(host_dtype)

    def _galerkin_classical(self, cur: Matrix, Asc, R_host, P_host,
                            idx: int, keep_pattern: bool):
        """Galerkin RAP of one classical level: the device SpGEMM
        engine when enabled (pattern-keyed setup executable, numeric
        pass under jit), host scipy triple product as the fallback.
        ``keep_pattern`` returns the full symbolic pattern (the
        frozen-structure resetup contract)."""
        eng = self._device_setup_engine()
        Ac = None
        if eng is not None:
            Ac = eng.galerkin_csr(
                Asc, P_host, level=idx, keep_pattern=keep_pattern,
                dtype=self._galerkin_dtype(Asc.dtype),
                min_rows=self.device_setup_min_rows,
                budget_bytes=self.device_setup_cache_mb << 20)
        if Ac is None:
            Ac = sp.csr_matrix(R_host @ Asc @ P_host)
            if keep_pattern:
                Ac = pad_to_symbolic(Ac, Asc, P_host)
        Ac = Ac.astype(Asc.dtype)
        Ac.sum_duplicates()
        Ac.sort_indices()
        return Ac

    def _galerkin_agg(self, cur: Matrix, agg: np.ndarray, idx: int):
        """Aggregation Galerkin of one level: device segment-sum path
        (amg/device_setup/) with the host sort-based generator as the
        fallback."""
        eng = self._device_setup_engine()
        if eng is not None and cur.dist is None:
            host = cur.host
            out = eng.galerkin_agg(
                host, agg, cur.block_dim,
                dtype=self._galerkin_dtype(host.dtype),
                level=idx, min_rows=self.device_setup_min_rows,
                budget_bytes=self.device_setup_cache_mb << 20)
            if out is not None:
                return out.astype(host.dtype)
        return galerkin_coarse(cur.host, agg, cur.block_dim)

    @staticmethod
    def _rank_blocks(cur: Matrix, offsets: np.ndarray):
        """Per-rank row-block views of this level's matrix — direct in
        block mode; sliced from the global host otherwise (the legacy
        global-upload path)."""
        if cur.host is None and cur.blocks is not None:
            return cur.blocks
        from ..distributed.partition import split_row_blocks
        return split_row_blocks(cur.scalar_csr(), offsets)

    def _coarsen_aggregation_dist(self, cur: Matrix, idx: int, selector):
        """Distributed aggregation coarsening, per-rank end to end.

        Each rank aggregates its own diagonal block (the reference also
        runs selectors per-rank, ``aggregation_amg_level.cu`` distributed
        path); coarse ids are rank-contiguous so restriction/prolongation
        stay shard-local.  The Galerkin product is computed per-rank from
        the rank's row block — cross-rank couplings resolve through the
        aggregate ids of halo columns (the ``exchange_halo_rows_P`` /
        ``exchange_RAP_ext`` analog, ``distributed_arranger.h:223-231``)
        — so no step assembles a global matrix, and the coarse level is
        again a block-distributed Matrix.
        """
        mesh, axis, offsets, _ = cur.dist
        curd = cur.device()             # ShardedMatrix of this level
        offsets = np.asarray(curd.offsets)
        n_parts = curd.n_parts
        n = int(offsets[-1])
        blocks = self._rank_blocks(cur, offsets)
        agg_real = np.empty(n, dtype=np.int64)
        counts = []
        base = 0
        for p in range(n_parts):
            lo, hi = offsets[p], offsets[p + 1]
            if hi == lo:
                counts.append(0)
                continue
            sub = sp.csr_matrix(blocks[p][:, lo:hi])   # diagonal block
            agg_p = selector.select(sub)
            agg_real[lo:hi] = agg_p + base
            cnt = int(agg_p.max()) + 1 if len(agg_p) else 0
            counts.append(cnt)
            base += cnt
        nc = base
        if nc == 0 or nc >= n:
            return None, None, None
        # number the coarse ids by each aggregate's first fine row, as
        # the single-device levels are (``_relabel_aggregation_levels``):
        # rank p's fine rows and coarse ids are both contiguous ranges,
        # so the one global order only reorders within a rank and keeps
        # every count.  Each product below (Galerkin, packs, re-bucketing,
        # ``agg_pad``, the structure record) comes out renumbered, and
        # the next level's selector sees the renumbered operator
        order = _first_member_order(agg_real, nc)
        if order is not None:
            new_id = np.empty(nc, dtype=agg_real.dtype)
            new_id[order] = np.arange(nc, dtype=agg_real.dtype)
            agg_real = new_id[agg_real]
        coarse_offsets = np.concatenate([[0], np.cumsum(counts)])

        # per-rank Galerkin: rank p's coarse rows from rank p's row block;
        # agg_real[halo cols] is the halo-aggregate resolution (multi-host:
        # one neighbour-wise int exchange).  The shard-local device path
        # (engine.galerkin_agg with split row/column aggregate maps) owns
        # the hot path; the host coo remap stays the fallback
        eng = self._device_setup_engine()

        def coarse_block(p):
            lo, hi = offsets[p], offsets[p + 1]
            if eng is not None and hi > lo and blocks[p].nnz:
                C = eng.galerkin_agg(
                    blocks[p], agg_real[lo:hi] - coarse_offsets[p],
                    dtype=self._galerkin_dtype(blocks[p].dtype),
                    level=idx, min_rows=self.device_setup_min_rows,
                    budget_bytes=self.device_setup_cache_mb << 20,
                    agg_cols=agg_real, shape=(counts[p], nc))
                if C is not None:
                    return sp.csr_matrix(C.astype(blocks[p].dtype))
            coo = blocks[p].tocoo()
            rows_c = agg_real[coo.row + lo] - coarse_offsets[p]
            cols_c = agg_real[coo.col]
            C = sp.csr_matrix((coo.data, (rows_c, cols_c)),
                              shape=(counts[p], nc))
            C.sum_duplicates()
            C.sort_indices()
            return C

        c_blocks = [coarse_block(p) for p in range(n_parts)]

        # consolidation ("glue", distributed/glue.h + amg.cu:328-390):
        # when the coarse grid is too small per rank, migrate it onto a
        # SUB-mesh (fewer active ranks) or — when even one rank's worth —
        # off the mesh entirely (replicated).  Two triggers share the
        # machinery: the legacy matrix_consolidation thresholds, and the
        # dist_agglomerate_min_rows planner (factor-halving sub-meshes,
        # distributed/agglomerate.py)
        lower = int(self.cfg.get("matrix_consolidation_lower_threshold"))
        agg_min = self.dist_agglomerate_min_rows
        n_loc_f = curd.n_loc
        p_active = None
        plan = None
        if lower > 0 and nc // n_parts < lower:
            # legacy consolidation thresholds: pre-planner policy, no
            # dist_agglomerate lifecycle events
            upper = max(int(self.cfg.get(
                "matrix_consolidation_upper_threshold")), 1)
            p_active = min(n_parts, max(1, -(-nc // upper)))
        elif agg_min > 0 and nc // max(n_parts, 1) < agg_min:
            # the PR-12 planner: cached plans (a values-only resetup
            # replays the SAME packs — its dist_agglomerate event then
            # carries reused=1, exactly like the classical path)
            from ..distributed.agglomerate import plan_for
            plan = plan_for(coarse_offsets, agg_min,
                            self.dist_agglomerate_factor, level=idx)
            if plan is not None:
                p_active = plan.p_active
        if p_active is not None:
            if p_active <= 1:
                # fully consolidated: replicated coarse level
                Ac_host = sp.csr_matrix(sp.vstack(c_blocks))
                Ac = _child_matrix(cur, Ac_host)
                agg_pad = np.full(n_parts * n_loc_f, nc, dtype=np.int64)
                for p in range(n_parts):
                    lo, hi = offsets[p], offsets[p + 1]
                    agg_pad[p * n_loc_f:p * n_loc_f + (hi - lo)] = \
                        agg_real[lo:hi]
                level = AggregationLevel(cur, idx, agg_pad, n_coarse=nc,
                                         trash_segment=True)
                level.submesh_parts = 1
                return level, Ac, ("aggregation-consolidated",
                                   (agg_real, nc))
            # sub-mesh: re-bucket coarse rows onto the first p_active
            # ranks (equal split); the other ranks hold only padding
            if plan is not None:
                from ..distributed.agglomerate import \
                    redistribute_blocks
                coarse_offsets = np.asarray(plan.dst_offsets)
                c_blocks = redistribute_blocks(c_blocks, plan)
            else:
                nc_act = -(-nc // p_active)
                coarse_offsets = np.concatenate([
                    np.minimum(np.arange(p_active + 1) * nc_act, nc),
                    np.full(n_parts - p_active, nc, dtype=np.int64)])
                c_blocks = _rebucket_blocks(c_blocks, coarse_offsets)

        nc_loc = int(np.max(np.diff(coarse_offsets))) + 1  # ≥1 pad slot
        Ac = Matrix()
        Ac.set_distributed_blocks(c_blocks, coarse_offsets, mesh,
                                  axis=axis)
        Ac.dist = (mesh, axis, coarse_offsets, nc_loc)
        Ac.device_dtype = cur.device_dtype
        Ac.placement = cur.placement
        # aggregates in padded coordinates: fine pad rows → coarse pad
        # slot under the (possibly re-bucketed) coarse offsets
        own = np.searchsorted(coarse_offsets, np.arange(nc),
                              side="right") - 1
        pad_of = own * nc_loc + (np.arange(nc) - coarse_offsets[own])
        agg_pad = np.empty(n_parts * n_loc_f, dtype=np.int64)
        for p in range(n_parts):
            lo, hi = offsets[p], offsets[p + 1]
            row = np.full(n_loc_f, p * nc_loc + nc_loc - 1,
                          dtype=np.int64)
            row[:hi - lo] = pad_of[agg_real[lo:hi]]
            agg_pad[p * n_loc_f:(p + 1) * n_loc_f] = row
        level = AggregationLevel(cur, idx, agg_pad,
                                 n_coarse=n_parts * nc_loc)
        from ..distributed.agglomerate import active_parts
        level.submesh_parts = active_parts(coarse_offsets)
        return level, Ac, ("aggregation-dist", (agg_real, nc))

    def _effective_hierarchy_dtype(self):
        """The per-level storage dtype the precision policy applies, or
        None.  An explicit ``hierarchy_dtype`` wins; otherwise a
        sub-f32 fine-matrix ``device_dtype`` (the tpu_matrix_dtype /
        AMGX mode path) implies the same narrowing for device-born
        levels, which inherit-by-construction only on the host paths."""
        if self.hierarchy_dtype is not None:
            return np.dtype(self.hierarchy_dtype)
        if not self.levels:
            return None
        from ..core.precision import is_sub_f32
        fine = self.levels[0].A
        dd = getattr(fine, "device_dtype", None)
        if dd is not None and is_sub_f32(dd):
            return np.dtype(dd)
        return None

    def _apply_precision_policy(self):
        """Narrow the STORED hierarchy to the policy dtype, level
        ``mixed_precision_from_level`` down: each covered level's
        operator and transfer packs are replaced by precision views
        (``core.precision.precision_view`` — device-side cast when the
        f32 pack already exists, cast-on-upload otherwise).  Host-side
        setup structures stay shared and wide, the caller's matrix and
        the coarsest grid (dense-LU data) are untouched, and packs
        whose SpMV would lose an f32-only kernel keep their dtype."""
        hd = self._effective_hierarchy_dtype()
        if hd is None:
            return
        from ..core import precision
        from_level = max(self.mixed_from_level, 0)
        for i, lvl in enumerate(self.levels):
            if i < from_level:
                continue
            A = lvl.A
            if isinstance(A, Matrix) and A.dist is None:
                cur_dt = np.dtype(A.device_dtype or A.dtype)
                if hd.itemsize < cur_dt.itemsize:
                    view = precision.precision_view(A, hd)
                    if view is not A:
                        lvl.A = view
                        lvl._Ad = view._device
            for mslot, dslot in (("_Pm", "_Pd"), ("_Rm", "_Rd")):
                Pm = getattr(lvl, mslot, None)
                if Pm is not None:
                    if Pm.dist is not None:
                        continue
                    pdt = np.dtype(Pm.device_dtype or Pm.dtype)
                    if hd.itemsize < pdt.itemsize:
                        v = precision.precision_view(Pm, hd)
                        if v is not Pm:
                            setattr(lvl, mslot, v)
                            setattr(lvl, dslot, v._device)
                elif getattr(lvl, dslot, None) is not None:
                    # device-born transfer (classical device pipeline)
                    d = getattr(lvl, dslot)
                    if precision.narrowable_pack(d) and \
                            np.dtype(d.dtype).itemsize > hd.itemsize:
                        setattr(lvl, dslot, d.astype(hd))

    def _setup_smoothers_and_coarse(self, coarsest: Matrix):
        from ..core.matrix import batch_upload
        from ..utils.thread_manager import ThreadManager

        # ONE arena upload for every level's pack — operators AND
        # classical P/R transfers — plus DIA inverted diagonals: one
        # transfer call instead of one per array (reference: the
        # hierarchy lives on device from the start, amg.cu:177-450)
        stream = getattr(self, "_stream_uploader", None)
        if stream is not None:
            # wait out the per-level uploads streamed during coarsening
            # (only the residual wire time shows up here)
            with cpu_profiler("hierarchy_upload_drain"), \
                    setup_profile.phase("upload", kind="device"):
                stream.join_threads()
            self._stream_uploader = None
        # mixed precision: the policy runs AFTER the streamed uploads
        # land (their f32 packs cast on device, zero wire bytes) and
        # BEFORE the arena upload (host-built levels then ship narrow)
        self._apply_precision_policy()
        with cpu_profiler("hierarchy_upload"), \
                setup_profile.phase("upload", kind="device"):
            mats, fine_ids = [], set()
            for lvl in self.levels:
                ms, le = self._level_pack_mats(lvl)
                mats.extend(ms)
                fine_ids |= le
            batch_upload(mats + [coarsest], lean_except=fine_ids)

        def smoother_task(lvl):
            def run():
                # worker-thread phase: OVERLAPS the main thread's
                # smoother_setup wall (excluded from coverage) but owns
                # the smoother-setup jit compiles for attribution
                with setup_profile.phase("smoother_setup",
                                         level=lvl.level_index):
                    lvl.smoother = SolverFactory.allocate(
                        self.cfg, self.scope, "smoother")
                    lvl.smoother.setup(lvl.A)
            return run

        # per-level smoother setups are independent — overlap their host
        # work and device uploads on the async task pool (reference
        # ThreadManager, thread_manager.h:46-173; ``serialize_threads``
        # forces the serial order for debugging)
        serialize = bool(self.cfg.get("serialize_threads"))
        with cpu_profiler("setup_smoothers"), \
                setup_profile.phase("smoother_setup"), \
                ThreadManager(serialize=serialize) as tm:
            for lvl in self.levels:
                tm.push_work(smoother_task(lvl))
            tm.wait_threads()
        self.coarsest = coarsest
        with cpu_profiler("setup_coarse_solver"), \
                setup_profile.phase("coarse_solver"):
            self.coarse_solver = SolverFactory.allocate(
                self.cfg, self.scope, "coarse_solver")
            self.coarse_solver.setup(coarsest)
        self.coarse_solver_is_smoother = self.coarse_solver.is_smoother

    # ------------------------------------------------------------------ info
    def num_levels(self):
        return len(self.levels) + 1

    def level_sizes(self) -> List[tuple]:
        """(rows, nnz) per level, fine to coarsest — the single source
        for the grid-stats table and the hierarchy telemetry gauges
        (per-level logical sizing lives in ``AMGLevel.level_stats``)."""
        sizes = [l.level_stats() for l in self.levels]
        sizes.append((self.coarsest.n_block_rows, self.coarsest.nnz))
        return sizes

    def _emit_telemetry(self):
        """Hierarchy gauges: per-level rows/nnz plus operator and grid
        complexity — the structured twin of the grid-stats table (the
        data every serious AMG user reads before trusting a solve)."""
        sizes = self.level_sizes()
        tot_rows = sum(n for n, _ in sizes)
        tot_nnz = sum(z for _, z in sizes)
        op_cmpl = tot_nnz / max(sizes[0][1], 1)
        grid_cmpl = tot_rows / max(sizes[0][0], 1)
        telemetry.gauge_set("amgx_hierarchy_levels", len(sizes))
        # a shallower re-setup must not leave the previous hierarchy's
        # deeper levels dangling in the registry snapshot
        telemetry.registry().gauge_clear("amgx_level_rows")
        telemetry.registry().gauge_clear("amgx_level_nnz")
        telemetry.registry().gauge_clear("amgx_level_bandwidth")
        mats = [lvl.A for lvl in self.levels] + [self.coarsest]
        for i, ((n, nnz), m) in enumerate(zip(sizes, mats)):
            telemetry.gauge_set("amgx_level_rows", n, level=i)
            telemetry.gauge_set("amgx_level_nnz", nnz, level=i)
            bw = _bandwidth(m)
            if bw is not None:
                telemetry.gauge_set("amgx_level_bandwidth", bw, level=i)
        telemetry.gauge_set("amgx_operator_complexity", op_cmpl)
        telemetry.gauge_set("amgx_grid_complexity", grid_cmpl)
        self._emit_cost_telemetry(sizes)
        telemetry.event("hierarchy", levels=len(sizes),
                        operator_complexity=round(op_cmpl, 6),
                        grid_complexity=round(grid_cmpl, 6),
                        setup_s=round(self.setup_time, 6))

    def _register_memledger(self):
        """HBM-ledger ownership registration (telemetry/memledger.py):
        one entry per materialised level pack
        (``amgx/hierarchy/level<N>``), per P/R transfer pack
        (``amgx/transfer/level<N>``), per smoother's device state
        (``amgx/smoother/level<N>`` — ``dinv``, DILU ``Einv``, ILU
        factors) and the coarse solver's factors
        (``amgx/coarse/solver``).  Re-registration on re-setup releases
        the previous tokens first, so the register/release balance holds
        across setup→resetup→teardown.  One attribute check when the
        ledger is off; never triggers an upload (reads only packs that
        already exist)."""
        from ..telemetry import memledger as ml
        if not ml.is_enabled():
            return
        for tok in getattr(self, "_ml_tokens", ()):
            ml.release(tok)
        toks = self._ml_tokens = []

        def reg(owner, name, tree):
            if tree:
                try:
                    toks.append(ml.register(ml.owner_name(owner, name),
                                            tree))
                except Exception:
                    pass    # the ledger must never break setup

        packs = self._materialized_packs()
        for i, Ad in enumerate(packs[:-1]):
            if Ad is not None:
                reg("hierarchy", f"level{i}", Ad)
        if packs and packs[-1] is not None:
            reg("hierarchy", "coarse", packs[-1])
        for i, lvl in enumerate(self.levels):
            pr = {k: v for k, v in (("p", getattr(lvl, "_Pd", None)),
                                    ("r", getattr(lvl, "_Rd", None)))
                  if v is not None}
            reg("transfer", f"level{i}", pr)
            sm = lvl.smoother
            if sm is not None:
                st = {k: v for k in ("dinv", "Einv", "dinv_f")
                      if (v := getattr(sm, k, None)) is not None}
                reg("smoother", f"level{i}", st)
        cs = self.coarse_solver
        if cs is not None:
            st = {k: v for k in ("_lu", "_piv", "dinv", "Einv",
                                 "dinv_f")
                  if (v := getattr(cs, k, None)) is not None}
            reg("coarse", "solver", st)

    def release_memledger(self):
        """Drop this hierarchy's ledger registrations (teardown)."""
        from ..telemetry import memledger as ml
        for tok in getattr(self, "_ml_tokens", ()):
            ml.release(tok)
        self._ml_tokens = []

    def _materialized_packs(self) -> list:
        """Per-level device packs WHERE THEY ALREADY EXIST (never
        triggers an upload as a side effect — ``.Ad`` would), fine to
        coarsest — the single pack walk behind the cost gauges and the
        distributed overlap audit."""
        packs = [l._Ad if l._Ad is not None
                 else getattr(l.A, "_device", None) for l in self.levels]
        packs.append(getattr(self.coarsest, "_device", None))
        return packs

    def level_costs(self, sizes=None) -> List[tuple]:
        """(level index, spmv cost dict) per level whose device pack
        already exists, fine to coarsest — the single pack walk behind
        the cost-telemetry gauges AND bench's bytes-per-cycle column.
        Reads packs only where they are materialised (never triggers a
        device upload as a side effect — ``.Ad`` would)."""
        from ..telemetry import costmodel
        if sizes is None:
            sizes = self.level_sizes()
        packs = self._materialized_packs()
        out = []
        for i, Ad in enumerate(packs):
            if Ad is None:
                continue
            try:
                out.append((i, costmodel.spmv_cost(Ad, nnz=sizes[i][1])))
            except Exception:
                continue      # a cost-model gap must never break setup
        return out

    def _emit_cost_telemetry(self, sizes):
        """Per-level static cost descriptors (telemetry/costmodel.py):
        modelled SpMV bytes/FLOPs and the padding-waste ratio of each
        level's device pack — what turns recorded span durations into
        achieved-vs-peak bandwidth fractions.  ``sizes`` is the
        ``level_sizes()`` list, so the true nnz comes for free (no
        device download just for telemetry)."""
        reg = telemetry.registry()
        for name in ("amgx_level_spmv_bytes", "amgx_level_spmv_flops",
                     "amgx_level_padding_waste"):
            reg.gauge_clear(name)
        self._emit_dist_telemetry(sizes)
        for i, cost in self.level_costs(sizes):
            if cost.get("bytes_per_apply") is not None:
                # dtype-labeled (mixed precision): a Prometheus consumer
                # can see per level which precision the bytes stream at
                dt = str(cost.get("dtype", "?"))
                telemetry.gauge_set("amgx_level_spmv_bytes",
                                    cost["bytes_per_apply"], level=i,
                                    dtype=dt)
                telemetry.gauge_set("amgx_level_spmv_flops",
                                    cost["flops_per_apply"], level=i,
                                    dtype=dt)
                telemetry.gauge_set("amgx_level_padding_waste",
                                    cost["padding_waste"], level=i,
                                    dtype=dt)
            telemetry.event("level_cost", level=i, **cost)

    def _emit_dist_telemetry(self, sizes):
        """Distributed-level overlap audit (telemetry/costmodel.py
        ``dist_overlap``): one event + gauges per SHARDED level —
        modelled interior-vs-halo seconds, overlap fraction, and the
        sub-mesh each level lives on — the doctor's "distributed
        levels" input.  Silent on single-device hierarchies."""
        from ..telemetry import costmodel
        reg = telemetry.registry()
        reg.gauge_clear("amgx_dist_overlap_fraction")
        reg.gauge_clear("amgx_dist_submesh_parts")
        packs = self._materialized_packs()
        for i, lay in self.dist_levels().items():
            Ad = packs[i]
            try:
                d = costmodel.dist_overlap(
                    Ad, nnz=sizes[i][1] if i < len(sizes) else None,
                    level=i)
            except Exception:
                continue     # a cost-model gap must never break setup
            if d is None:
                continue
            # the level's layout IS its sub-mesh: active_parts derives
            # from the (possibly agglomerated) offsets the level's
            # packs were built against
            d["submesh_parts"] = lay["submesh_parts"]
            d["interior"] = lay["interior"]
            telemetry.gauge_set("amgx_dist_overlap_fraction",
                                d["overlap_fraction"], level=i)
            telemetry.gauge_set("amgx_dist_submesh_parts",
                                d["submesh_parts"], level=i)
            telemetry.event("dist_overlap", **d)

    def dist_levels(self) -> dict:
        """Level index -> ``{"interior", "submesh_parts", "n_parts"}`` for
        each level whose device pack is sharded and already exists: the
        interior SpMV path its applies take (``window`` / ``gather`` /
        ``block``, ``distributed.matrix.interior_path``) and the ranks
        that own its rows.  Empty on single-device hierarchies."""
        from ..distributed.agglomerate import active_parts
        from ..distributed.matrix import interior_path
        out = {}
        for i, Ad in enumerate(self._materialized_packs()):
            if getattr(Ad, "fmt", "") != "sharded-ell":
                continue
            out[i] = {"interior": interior_path(Ad),
                      "submesh_parts": active_parts(Ad.offsets),
                      "n_parts": int(Ad.n_parts)}
        return out

    def grid_stats(self) -> str:
        """Grid-stats table mirroring the reference README sample output;
        a sharded level adds its interior SpMV path and the ranks that
        own its rows (``sharded/window 4/4``)."""
        rows = []
        tot_rows = tot_nnz = 0
        all_levels = self.level_sizes()
        dist = self.dist_levels()
        for i, (n, nnz) in enumerate(all_levels):
            sprs = nnz / max(n * n, 1)
            d = dist.get(i)
            tail = "" if d is None else (
                f"  sharded/{d['interior']} "
                f"{d['submesh_parts']}/{d['n_parts']}")
            rows.append(f"         {i}(D)  {n:12d}  {nnz:12d} "
                        f" {sprs:9.3g}{tail}\n")
            tot_rows += n
            tot_nnz += nnz
        op_cmpl = tot_nnz / max(all_levels[0][1], 1)
        grid_cmpl = tot_rows / max(all_levels[0][0], 1)
        return ("        Number of Levels: "
                f"{self.num_levels()}\n"
                "            LVL         ROWS           NNZ    SPRSTY\n"
                "         ------------------------------------------\n"
                + "".join(rows) +
                "         ------------------------------------------\n"
                f"         Grid Complexity: {grid_cmpl:.5g}\n"
                f"         Operator Complexity: {op_cmpl:.5g}\n")


def _rebucket_blocks(blocks, new_offsets):
    """Re-split per-rank row blocks to new offsets (consolidation-time
    only — the data being migrated is small by definition)."""
    from ..distributed.partition import split_row_blocks
    return split_row_blocks(sp.vstack(blocks), new_offsets)


def _block_condensed(m: Matrix) -> sp.csr_matrix:
    """Condense a block matrix to a scalar weight graph for selectors
    (reference uses one component per block,
    ``aggregation_edge_weight_component``)."""
    bsr = m.host if isinstance(m.host, sp.bsr_matrix) else sp.bsr_matrix(
        m.host, blocksize=(m.block_dim, m.block_dim))
    bsr.sort_indices()
    b = m.block_dim
    n = bsr.shape[0] // b
    # Frobenius-norm condensation of each block
    vals = np.sqrt((bsr.data ** 2).sum(axis=(1, 2)))
    return sp.csr_matrix((vals, bsr.indices, bsr.indptr), shape=(n, n))
