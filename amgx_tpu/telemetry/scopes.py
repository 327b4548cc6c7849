"""The versioned ``jax.named_scope`` naming contract.

Host spans (:mod:`amgx_tpu.telemetry.recorder`) measure *dispatch*
time under JAX's async dispatch — to attribute measured **device**
time to amgx concepts, the kernels themselves carry
``jax.named_scope`` annotations that XLA threads through to the
profiler trace's op metadata.  This module is the single authority for
those names:

``amgx/<area>/<name>``

where ``<area>`` is one of :data:`AREAS` and every ``/``-separated
segment matches ``[a-z0-9_]+``.  Dots and hyphens are deliberately
EXCLUDED from the segment alphabet: XLA appends its own op names to
the scope ("…/fusion.3", "…/all-reduce.1"), and the restricted
alphabet lets :func:`extract_scopes` cut the known-contract prefix
back out of a polluted trace string.

The vocabulary per area:

* ``cycle``  — ``level<N>/{pre_smooth,post_smooth,restrict,prolong}``,
  ``coarse_solve``, ``kcycle<N>`` (amg/cycles.py)
* ``spmv``   — the sanitised dispatch pack names of
  :data:`SPMV_PACKS` (ops/spmv.py; ``-`` → ``_``)
* ``smoother`` — the registered smoother's config name, sanitised
  (solvers/base.py wraps every smoother application)
* ``krylov`` — the fixed stage vocabulary :data:`KRYLOV_STAGES`
  (solvers/krylov.py; ``refine``, a solve's true residuals and the f64
  refinement's corrections, in solvers/base.py)
* ``dist``   — :data:`DIST_STAGES`: ``halo_exchange`` (the B2L gather,
  the collectives and the halo read of a sharded SpMV or a bare
  ``exchange_halo``) and ``boundary`` (the SpMV's correction of its
  boundary rows from the halo) (distributed/matrix.py)

Bump :data:`SCOPE_VERSION` when names change meaning — the
``device_anatomy`` event carries it so old traces stay interpretable.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Optional

#: version of the naming contract carried by every device_anatomy event
#: (2: the ``krylov/refine`` stage)
SCOPE_VERSION = 2

#: the taxonomy's top-level areas
AREAS = ("cycle", "spmv", "smoother", "krylov", "dist")

#: every SpMV dispatch pack name ops/spmv.py can label a dispatch with
#: (the un-sanitised telemetry spelling — scripts/telemetry_check.py
#: cross-checks this list against the dispatch sites so it cannot rot)
SPMV_PACKS = (
    "sharded/window", "sharded/gather", "sharded/block", "dia3", "op",
    "dia/kernel", "dia/slices", "dia/block-kernel", "dia/block-slices",
    "dense",
    "ell/shift", "ell/window", "ell/binned", "ell/gather",
    "ell/binned-block", "ell/block-gather",
    "csr/binned", "csr/binned-block",
    "csr/segsum", "csr/segsum-lean", "csr/block-segsum",
)

#: the Krylov per-stage vocabulary (solvers/krylov.py)
KRYLOV_STAGES = ("precond", "reduce", "arnoldi", "givens", "update",
                 "refine")

#: the sharded SpMV's stages outside its interior (distributed/matrix.py)
DIST_STAGES = ("halo_exchange", "boundary")

#: the per-level cycle components (amg/cycles.py)
CYCLE_COMPONENTS = ("pre_smooth", "post_smooth", "restrict", "prolong")

_SEG = r"[a-z0-9_]+"
#: full-match check of a finished scope name
SCOPE_RE = re.compile(rf"amgx(?:/{_SEG})+\Z")
#: extraction hint over raw trace strings (op names / metadata) — no
#: trailing anchor; dots, hyphens, uppercase terminate the match
TRACE_RE = re.compile(rf"amgx(?:/{_SEG})+")

_CYCLE_LEVEL_RE = re.compile(r"level\d+\Z")
_KCYCLE_RE = re.compile(r"kcycle\d+\Z")


def sanitize(name: str) -> str:
    """Map any label into the scope segment alphabet: lowercase, and
    every character outside ``[a-z0-9_/]`` becomes ``_`` (so the pack
    name ``ell/binned-block`` scopes as ``ell/binned_block``)."""
    return re.sub(r"[^a-z0-9_/]", "_", str(name).lower())


def scope_name(area: str, name: str) -> str:
    """The contract name ``amgx/<area>/<sanitised name>``.

    Raises ``ValueError`` on an unknown area or a name that cannot be
    sanitised into the contract (empty segments).
    """
    if area not in AREAS:
        raise ValueError(f"unknown scope area {area!r} "
                         f"(contract v{SCOPE_VERSION} areas: {AREAS})")
    s = f"amgx/{area}/{sanitize(name)}"
    if not SCOPE_RE.match(s):
        raise ValueError(f"scope name {s!r} violates the "
                         f"amgx/<area>/<name> contract")
    return s


def scope(area: str, name: str):
    """A ``jax.named_scope`` context manager carrying the contract name
    (the one primitive every instrumented kernel calls)."""
    import jax
    return jax.named_scope(scope_name(area, name))


def validate(name: str) -> bool:
    """True iff ``name`` is a well-formed contract scope name with a
    known area."""
    if not isinstance(name, str) or not SCOPE_RE.match(name):
        return False
    parts = name.split("/")
    return len(parts) >= 3 and parts[1] in AREAS


#: sanitised pack names, longest first so two-segment packs win the
#: prefix match over their one-segment heads
_SPMV_LEAVES = sorted({sanitize(p) for p in SPMV_PACKS},
                      key=lambda p: -p.count("/"))


def canonicalize(raw: str) -> Optional[str]:
    """Trim a trace-extracted ``amgx/…`` string back to its contract
    scope name, dropping the XLA op-name segments the profiler appended
    ("amgx/cycle/level0/pre_smooth/fusion" →
    "amgx/cycle/level0/pre_smooth").  None when the string is not a
    recognisable scope."""
    if not isinstance(raw, str) or not raw.startswith("amgx/"):
        return None
    segs = raw.split("/")[1:]
    if len(segs) < 2:
        return None
    area, rest = segs[0], segs[1:]
    leaf: Optional[List[str]] = None
    if area == "cycle":
        if _CYCLE_LEVEL_RE.match(rest[0]) and len(rest) >= 2 \
                and rest[1] in CYCLE_COMPONENTS:
            leaf = rest[:2]
        elif rest[0] == "coarse_solve" or _KCYCLE_RE.match(rest[0]):
            leaf = rest[:1]
    elif area == "spmv":
        joined = "/".join(rest)
        for pack in _SPMV_LEAVES:
            if joined == pack or joined.startswith(pack + "/"):
                leaf = pack.split("/")
                break
    elif area == "smoother":
        leaf = rest[:1]
    elif area == "krylov":
        if rest[0] in KRYLOV_STAGES:
            leaf = rest[:1]
    elif area == "dist":
        if rest[0] in DIST_STAGES:
            leaf = rest[:1]
    if leaf is None:
        return None
    name = "/".join(["amgx", area] + leaf)
    return name if validate(name) else None


def extract_scopes(text: str) -> List[str]:
    """Every canonical scope name embedded in a raw trace string,
    outermost first.  Nested ``jax.named_scope``s concatenate in the
    profiler metadata ("amgx/cycle/level0/pre_smooth/amgx/spmv/dia3/
    fusion.3"), so each interior ``amgx/`` segment boundary starts a
    new candidate."""
    out: List[str] = []
    for m in TRACE_RE.finditer(text):
        raw = m.group(0)
        starts = [i for i in range(len(raw))
                  if raw.startswith("amgx/", i)
                  and (i == 0 or raw[i - 1] == "/")]
        for j, st in enumerate(starts):
            end = starts[j + 1] if j + 1 < len(starts) else len(raw)
            c = canonicalize(raw[st:end].rstrip("/"))
            if c and c not in out:
                out.append(c)
    return out


def scopes_in_event(ev: dict) -> List[str]:
    """The canonical scopes referenced by one chrome-trace event: its
    name plus any string ``args`` values (XLA places the annotation
    stack in op metadata — ``args["name"]`` / ``args["long_name"]`` /
    ``args["tf_op"]`` depending on version)."""
    found = extract_scopes(str(ev.get("name", "")))
    args = ev.get("args")
    if isinstance(args, dict):
        for v in args.values():
            if isinstance(v, str):
                for s in extract_scopes(v):
                    if s not in found:
                        found.append(s)
    return found


def smoother_scopes(names: Iterable[str]) -> List[str]:
    """Contract scope names for a set of smoother config names (what
    the coverage lint expects solvers/base.py to emit)."""
    return [scope_name("smoother", n) for n in names]
