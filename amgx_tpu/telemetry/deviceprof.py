"""Device-time attribution: the profiler-trace → scope correlator.

Host spans measure dispatch under JAX's async execution; this module
answers where the TPU actually spends a cycle.  It reads a
``jax.profiler`` capture (``.xplane.pb``, through
:func:`amgx_tpu.telemetry.proftrace.read_xspace`): the XLA operations of
the device planes' ``XLA Ops`` line, each joined to the
``jax.named_scope`` path of its HLO instruction (the ``tf_op`` stat of
the plane's event metadata), mapped onto the scope taxonomy
(:mod:`amgx_tpu.telemetry.scopes`).  Only operations that hold no others
count (a ``while`` event spans its body's), so no device time counts
twice and host threads never count.  The result is a **device-time cycle
anatomy**: per-level pre/post-smooth + restrict/prolong seconds, the
coarse solve, the shares of busy time on the coarse levels and in the
smoothers (by each operation's innermost cycle scope), per-pack SpMV
device time with *measured* bandwidth (cost-model bytes per apply ×
executions counted in the trace ÷ measured device seconds) next to the
roofline, per-smoother and per-Krylov-stage splits, the halo-exchange
share, the largest unscoped operations by Python source, and the idle
time between operations labelled by the program's host span
(``amgx/<marker>``) open at the time.

A loaded chrome-trace dict or an event list is accepted as well (the
synthetic captures of the tests and the checks).

The anatomy is emitted as a schema-validated ``device_anatomy`` event
(``measured`` provenance bool, like PR 16's ``dist_overlap``) and as
``amgx_device_time_seconds_total{scope}`` counters.  Every entry point
degrades to a ``measured=False`` stub when the capture carries no
scoped device ops (CPU runs, no capture at the path).
"""
from __future__ import annotations

import bisect
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import proftrace, scopes

_LEVEL_RE = re.compile(r"\Aamgx/cycle/level(\d+)/([a-z0-9_]+)\Z")
_COARSE_RE = re.compile(r"\Aamgx/cycle/(?:level[1-9]\d*/|coarse_solve)")
_SMOOTH_RE = re.compile(r"\Aamgx/cycle/level\d+/(?:pre|post)_smooth\Z")

#: anatomy sections keyed by scope-name prefix
_AREA_PREFIX = {a: f"amgx/{a}/" for a in scopes.AREAS}

#: entries kept in the anatomy's ranked lists
_TOP = 10


class _Slice(NamedTuple):
    """One device operation that holds no others (times in µs)."""
    pid: object
    start: float
    end: float
    #: the operation's name (an HLO instruction)
    name: str
    #: the raw string its scopes were read from
    text: str
    #: canonical contract scopes, outermost first
    scopes: List[str]
    #: Python file:line (XSpace captures only)
    source: str


def _round_s(us: float) -> float:
    return round(us * 1e-6, 9)


def _xspace_slices(path: str):
    """(leaf device slices, host spans) of the capture at ``path``; None
    when there is no readable capture."""
    try:
        ops, spans = proftrace.read_xspace(path)
    except (OSError, ValueError):
        return None
    out = [_Slice(d, o.start * 1e6, o.end * 1e6, o.name, o.scope,
                  scopes.extract_scopes(o.scope), o.source)
           for d, lst in ops.items() for o in lst if not o.container]
    return out, [(s.name, s.start * 1e6, s.end * 1e6) for s in spans]


def _chrome_slices(trace) -> List[_Slice]:
    out = []
    for ev in proftrace.complete_slices(proftrace.trace_events(trace)):
        ts = float(ev["ts"])
        name = str(ev.get("name", ""))
        args = ev.get("args")
        strs = [v for v in (args or {}).values() if isinstance(v, str)] \
            if isinstance(args, dict) else []
        out.append(_Slice(ev.get("pid", 0), ts, ts + float(ev["dur"]),
                          name, "/".join(strs + [name]),
                          scopes.scopes_in_event(ev), ""))
    return out


def _innermost_cycle(found: List[str]) -> Optional[str]:
    for s in reversed(found):
        if s.startswith(_AREA_PREFIX["cycle"]):
            return s
    return None


def _by_level(slices: List[_Slice], area: str) -> Dict[str, Dict[str, float]]:
    """Device seconds of each name under ``amgx/<area>/``, by the cycle
    level its operation ran in: ``"<i>"`` under ``amgx/cycle/level<i>``,
    ``"coarse"`` under the coarse solve, ``"outside"`` outside the cycle
    (the Krylov method's own operator applies).  Summed durations of
    operations that hold no others, over the devices."""
    pre = _AREA_PREFIX[area]
    out: Dict[str, Dict[str, float]] = {}
    for sl in slices:
        names = [s[len(pre):] for s in sl.scopes if s.startswith(pre)]
        if not names:
            continue
        inner = _innermost_cycle(sl.scopes)
        m = _LEVEL_RE.match(inner or "")
        lvl = m.group(1) if m else ("coarse" if inner else "outside")
        d = out.setdefault(lvl, {})
        d[names[-1]] = d.get(names[-1], 0.0) + (sl.end - sl.start)
    return {k: {n: _round_s(us) for n, us in sorted(out[k].items())}
            for k in sorted(out, key=lambda k: (not k.isdigit(),
                                                int(k) if k.isdigit()
                                                else 0, k))}


def _pack_executions(slices: List[_Slice]) -> Dict[str, int]:
    """Executions of each SpMV pack counted in the trace.  A call site
    is the scope path up to its ``amgx/spmv/<pack>`` segment; it ran as
    often as the least-run operation under it (each operation of one
    application runs at least once), and a pack's executions are the
    sum over its call sites."""
    pre = _AREA_PREFIX["spmv"]
    sites: Dict[Tuple[object, str, str], Dict[str, int]] = {}
    for sl in slices:
        for s in sl.scopes:
            if not s.startswith(pre):
                continue
            i = sl.text.find(s)
            site = sl.text[:i + len(s)] if i >= 0 else s
            counts = sites.setdefault((sl.pid, s, site), {})
            counts[sl.name] = counts.get(sl.name, 0) + 1
    out: Dict[str, int] = {}
    for (_pid, s, _site), counts in sites.items():
        pack = s[len(pre):]
        out[pack] = out.get(pack, 0) + min(counts.values())
    return out


def _idle_by_span(slices: List[_Slice], spans) -> Dict[str, float]:
    """Idle µs between the first and the last operation of the first
    device, by the innermost program host span open at each gap's
    middle ("none" outside every span)."""
    if not slices:
        return {}
    dev = min((sl.pid for sl in slices), key=str)
    busy = proftrace.merge_intervals(
        [(sl.start, sl.end) for sl in slices if sl.pid == dev])
    gaps = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(busy, busy[1:])]
    mids = [0.5 * (e0 + s1) for e0, s1 in gaps]
    labels = ["none"] * len(gaps)
    # longest span first, so that an inner span relabels its gaps
    for name, s, e in sorted(spans, key=lambda sp: sp[1] - sp[2]):
        lo, hi = bisect.bisect_left(mids, s), bisect.bisect_left(mids, e)
        labels[lo:hi] = [name[len(proftrace.HOST_SPAN_PREFIX):]] * (hi - lo)
    out: Dict[str, float] = {}
    for label, (e0, s1) in zip(labels, gaps):
        out[label] = out.get(label, 0.0) + (s1 - e0)
    return out


def measure_anatomy(trace: "str | dict | Iterable[dict]",
                    pack_bytes: Optional[Dict[str, int]] = None,
                    peak_gbs: Optional[float] = None) -> dict:
    """The device-time cycle anatomy of one profiler capture.

    ``trace``: a path (a ``.xplane.pb`` file or a profiler log
    directory, whose newest capture is read), a loaded chrome-trace
    dict, or an iterable of chrome-trace events.  ``pack_bytes``
    (optional, from :func:`pack_stats`) maps SpMV pack names to modelled
    bytes per apply; where it covers a pack that the trace saw execute,
    the anatomy adds measured GB/s and the roofline fraction next to its
    device seconds.

    ALWAYS returns a dict; ``measured`` is True only when at least one
    device operation carried a contract scope.  Per-scope seconds are
    the per-device **union** of that scope's intervals, summed across
    devices; ``total_device_s`` is the union of every operation the same
    way, so attributed + unattributed ≡ total.
    """
    if peak_gbs is None:
        # the chip that holds this process's trace; None on the CPU
        # (no device roofline: the share is reported as not measured)
        from .peaks import device_peak
        p = device_peak()
        peak_gbs = p.hbm_gbs if p is not None else None
    spans = []
    if isinstance(trace, (str, os.PathLike)):
        got = _xspace_slices(os.fspath(trace))
        slices, spans = got if got is not None else ([], [])
    else:
        slices = _chrome_slices(trace)

    all_iv: Dict[object, List[tuple]] = {}        # pid -> intervals
    scoped_iv: Dict[object, List[tuple]] = {}     # pid -> intervals
    by_scope: Dict[Tuple[object, str], List[tuple]] = {}
    n_scoped = 0
    busy_us = coarse_us = smooth_us = 0.0
    unscoped: Dict[str, float] = {}
    for sl in slices:
        iv = (sl.start, sl.end)
        dur = sl.end - sl.start
        busy_us += dur
        all_iv.setdefault(sl.pid, []).append(iv)
        if not sl.scopes:
            # an HLO text reads as its instruction's name
            key = sl.source or sl.name.split(" = ", 1)[0].lstrip("%")
            unscoped[key] = unscoped.get(key, 0.0) + dur
            continue
        n_scoped += 1
        scoped_iv.setdefault(sl.pid, []).append(iv)
        for s in sl.scopes:
            by_scope.setdefault((sl.pid, s), []).append(iv)
        inner = _innermost_cycle(sl.scopes)
        if inner and _COARSE_RE.match(inner):
            coarse_us += dur
        if inner and _SMOOTH_RE.match(inner):
            smooth_us += dur

    total_us = sum(proftrace.union_len(iv) for iv in all_iv.values())
    attrib_us = sum(proftrace.union_len(iv) for iv in scoped_iv.values())
    scope_us: Dict[str, float] = {}
    for (_pid, s), iv in by_scope.items():
        scope_us[s] = scope_us.get(s, 0.0) + proftrace.union_len(iv)

    # ---- per-level cycle anatomy (union across a level's components,
    # ---- so a level's total is honest even if components overlap) ----
    levels: Dict[str, dict] = {}
    level_iv: Dict[Tuple[object, str], List[tuple]] = {}
    for (pid, s), iv in by_scope.items():
        m = _LEVEL_RE.match(s)
        if m:
            level_iv.setdefault((pid, m.group(1)), []).extend(iv)
    for s, us in scope_us.items():
        m = _LEVEL_RE.match(s)
        if m:
            levels.setdefault(m.group(1), {})[m.group(2)] = _round_s(us)
    for (_pid, lvl), iv in level_iv.items():
        d = levels.setdefault(lvl, {})
        d["total_s"] = round(d.get("total_s", 0.0)
                             + _round_s(proftrace.union_len(iv)), 9)
    coarse_s = _round_s(scope_us.get("amgx/cycle/coarse_solve", 0.0))

    # ---- per-pack SpMV device time + measured bandwidth -------------
    pb = {scopes.sanitize(k): v for k, v in (pack_bytes or {}).items()
          if v}
    execs = _pack_executions(slices)
    spmv: Dict[str, dict] = {}
    for s, us in scope_us.items():
        if not s.startswith(_AREA_PREFIX["spmv"]):
            continue
        pack = s[len(_AREA_PREFIX["spmv"]):]
        d: dict = {"device_s": _round_s(us)}
        # op_cost events label the base pack kind ("dia", "dia/block"),
        # the scopes the refined label ("dia/slices",
        # "dia/block_kernel") — join on the longest base-kind key that
        # prefixes the scope's pack at a segment boundary
        byt = pb.get(pack)
        if not byt:
            for k in sorted(pb, key=len, reverse=True):
                if pack.startswith(k) and (len(pack) == len(k)
                                           or pack[len(k)] in "/_"):
                    byt = pb[k]
                    break
        n = execs.get(pack)
        if n:
            d["executions"] = int(n)
        if byt and n and us > 0:
            d["bytes_per_apply"] = int(byt)
            gbs = (float(byt) * float(n)) / (us * 1e-6) / 1e9
            d["measured_gbs"] = round(gbs, 2)
            if peak_gbs:
                d["roofline_fraction"] = round(gbs / peak_gbs, 6)
        spmv[pack] = d

    def _area(area: str) -> Dict[str, float]:
        pre = _AREA_PREFIX[area]
        return {s[len(pre):]: _round_s(us)
                for s, us in scope_us.items() if s.startswith(pre)}

    def _ranked(d: Dict[str, float]) -> List[list]:
        return [[k, _round_s(v)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:_TOP]]

    def _share(us: float) -> Optional[float]:
        return round(us / busy_us, 6) if busy_us > 0 else None

    return {
        "measured": n_scoped > 0,
        "scope_version": scopes.SCOPE_VERSION,
        "total_device_s": _round_s(total_us),
        "attributed_s": _round_s(attrib_us),
        "unattributed_s": _round_s(max(total_us - attrib_us, 0.0)),
        "n_devices": len(all_iv),
        "n_slices": len(slices),
        "n_scoped_slices": n_scoped,
        "scopes": {s: _round_s(us)
                   for s, us in sorted(scope_us.items())},
        "levels": {k: levels[k] for k in sorted(levels, key=int)},
        "coarse_s": coarse_s,
        "cycle_shares": {"coarse": _share(coarse_us),
                         "smooth": _share(smooth_us)},
        "spmv": {k: spmv[k] for k in sorted(spmv)},
        "smoothers": _area("smoother"),
        "krylov": _area("krylov"),
        "dist": _area("dist"),
        # the path each level's operator took (a sharded level's
        # interior: sharded/window, sharded/gather or sharded/block) and
        # where its halo exchanges ran
        "spmv_by_level": _by_level(slices, "spmv"),
        "dist_by_level": _by_level(slices, "dist"),
        "unscoped_top": _ranked(unscoped),
        "idle_by_span": _ranked(_idle_by_span(slices, spans)),
        "hbm_peak_gbs": float(peak_gbs) if peak_gbs else None,
    }


def pack_stats(records: Iterable[dict]) -> Dict[str, int]:
    """pack → modelled bytes per apply, from the ``op_cost`` events of
    recorder ring records.  The biggest descriptor per pack kind wins
    (the fine operator dominates the bandwidth story)."""
    pack_bytes: Dict[str, int] = {}
    for r in records:
        if r.get("kind") == "event" and r.get("name") == "op_cost":
            a = r.get("attrs") or {}
            pack, byt = a.get("pack"), a.get("bytes_per_apply")
            if pack and isinstance(byt, (int, float)) and byt > 0:
                pack_bytes[str(pack)] = max(
                    pack_bytes.get(str(pack), 0), int(byt))
    return pack_bytes


def capture_anatomy(trace, records: Optional[Iterable[dict]] = None
                    ) -> dict:
    """:func:`measure_anatomy` fed with pack bytes from a recorder ring
    snapshot (default: the live ring)."""
    if records is None:
        from . import recorder
        records = recorder.records()
    return measure_anatomy(trace, pack_bytes=pack_stats(records))


def emit(anatomy: dict):
    """Record the anatomy: one schema-validated ``device_anatomy``
    event plus ``amgx_device_time_seconds_total{scope}`` counter
    increments (one per attributed scope).  No-op when telemetry is
    off."""
    from . import metrics, recorder
    if not recorder.is_enabled():
        return
    for s, sec in (anatomy.get("scopes") or {}).items():
        if sec:
            metrics.counter_inc("amgx_device_time_seconds_total",
                                float(sec), scope=s)
    recorder.event("device_anatomy", **anatomy)


def top_scopes(anatomy: dict, n: int = 2) -> List[Tuple[str, float]]:
    """The ``n`` largest (scope, seconds) pairs — what bench_trend
    prints per round."""
    sc = anatomy.get("scopes") or {}
    return sorted(((k, float(v)) for k, v in sc.items()),
                  key=lambda kv: -kv[1])[:n]
