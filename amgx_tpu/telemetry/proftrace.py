"""Shared ``jax.profiler`` trace plumbing.

Two capture formats are read here:

* the ``XSpace`` protobuf that ``jax.profiler`` writes
  (``plugins/profile/<run>/<host>.xplane.pb``), which
  :mod:`amgx_tpu.telemetry.deviceprof` reads: :func:`read_xspace` gives
  the XLA operations of every device plane, each joined to the
  ``jax.named_scope`` path (``tf_op``) and Python ``file:line``
  (``source``) of its HLO instruction, and the program's own host spans
  (``amgx/<marker>``, :mod:`amgx_tpu.utils.profiler`);
* chrome-trace JSON (path / loaded dict / raw event iterable), which
  :mod:`amgx_tpu.telemetry.overlap` still reads, and the dict and
  event-list spellings that :mod:`deviceprof` accepts for synthetic
  captures.

Plus the interval arithmetic both use.  Host-side file parsing only:
the protobuf is read from its wire format, with no protobuf package.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

#: XLA op-name fragments that mean inter-chip communication.  HLO names
#: keep their kind as a prefix ("all-reduce.1", "fusion.all_gather", …)
#: across XLA versions; matching fragments is robust to the separators.
COMM_RE = re.compile(
    r"all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter|"
    r"collective[-_]?permute|all[-_]?to[-_]?all|ppermute|psum",
    re.IGNORECASE)

#: trace-viewer metadata / host-side bookkeeping phases that are not
#: device work at all
SKIP_PH = {"M", "I", "C"}


def load_json(path: str) -> Optional[dict]:
    """Load a chrome-trace JSON file (gzip-aware); None on any error."""
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def find_trace_file(path: str) -> Optional[str]:
    """Resolve a trace argument to a concrete chrome-trace file.

    Accepts the file itself (``.trace.json`` / ``.trace.json.gz`` or any
    ``.json``) or a profiler log directory, which is searched recursively
    (``jax.profiler.trace`` writes ``plugins/profile/<run>/
    <host>.trace.json.gz``); the newest match wins.
    """
    if os.path.isfile(path):
        return path
    if not os.path.isdir(path):
        return None
    hits: List[str] = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith((".trace.json", ".trace.json.gz")):
                hits.append(os.path.join(root, f))
    if not hits:
        return None
    return max(hits, key=lambda p: os.path.getmtime(p))


def trace_events(trace: "str | dict | Iterable[dict]") -> List[dict]:
    """Normalise any accepted trace spelling to its event list.

    ``trace``: a path (file or profiler logdir), a loaded chrome-trace
    dict, or an iterable of trace events.  Returns ``[]`` when the path
    resolves to nothing or the file is unreadable/malformed — callers
    then degrade the same way they would on an empty capture.
    """
    if isinstance(trace, str):
        f = find_trace_file(trace)
        data = load_json(f) if f else None
        if data is None:
            return []
        ev = data.get("traceEvents", [])
        return ev if isinstance(ev, list) else []
    if isinstance(trace, dict):
        ev = trace.get("traceEvents", [])
        return ev if isinstance(ev, list) else []
    try:
        return list(trace)
    except TypeError:           # None, int, ... — nothing to measure
        return []


def complete_slices(events: Iterable[dict]) -> Iterator[dict]:
    """The complete ("X") duration slices of a trace: every event that
    carries real wall extent (metadata/instant/counter phases and
    zero/None-duration rows are dropped).  Malformed rows (non-dict, or
    non-numeric ts/dur) are skipped rather than raised — profiler traces
    in the wild carry junk."""
    for ev in events:
        if not isinstance(ev, dict):
            continue
        if ev.get("ph", "X") in SKIP_PH:
            continue
        dur = ev.get("dur")
        ts = ev.get("ts")
        if not isinstance(dur, (int, float)) or \
                not isinstance(ts, (int, float)) or dur <= 0:
            continue
        yield ev


def merge_intervals(iv: List[tuple]) -> List[tuple]:
    """Coalesce (start, end) intervals into a sorted disjoint cover."""
    iv = sorted(iv)
    out: List[tuple] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_len(s: float, e: float, merged: List[tuple]) -> float:
    """Length of [s, e) covered by a :func:`merge_intervals` result."""
    total = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        total += min(e, me) - max(s, ms)
    return total


def union_len(iv: List[tuple]) -> float:
    """Total length of the union of (start, end) intervals."""
    return sum(e - s for s, e in merge_intervals(iv))


# --------------------------------------------------------------- XSpace
#: a device plane of an XSpace capture
DEVICE_PLANE_RE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
#: the line of a device plane that holds one event per executed XLA
#: operation (``while`` / ``conditional`` events hold their body's)
OPS_LINE = "XLA Ops"
#: prefix of the program's host spans (utils/profiler.py)
HOST_SPAN_PREFIX = "amgx/"

# field numbers of tsl/profiler/protobuf/xplane.proto
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_XEVENT_METADATA_NAME, _XEVENT_METADATA_STATS = 2, 5
_XSTAT_METADATA_ID, _XSTAT_STR, _XSTAT_REF = 1, 5, 7
_XSTATMETA_ID, _XSTATMETA_NAME = 1, 2
#: the event-metadata stats read: the named-scope path and the source
_STATS = ("tf_op", "source")


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    """One executed XLA operation of a device plane.  Times are seconds
    on the capture's clock, which the host spans share."""
    device: int
    #: the HLO instruction's text, as the trace names the event
    name: str
    start: float
    end: float
    #: the ``jax.named_scope`` path of the instruction (``tf_op``)
    scope: str = ""
    #: the Python ``file:line`` that emitted the instruction
    source: str = ""
    #: True for an operation that holds others (``while``,
    #: ``conditional``): its body's operations are events of their own
    container: bool = False


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: float
    end: float


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one message: an int for a varint, the
    (start, end) of the bytes for a length-delimited field (not decoded,
    so a skipped field costs nothing), None for a fixed-width one."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat_strings(b: bytes, span, stat_names: Dict[int, str],
                  ref_strings: Dict[int, str]) -> Dict[str, str]:
    """The string-valued stats of one ``XStat`` list entry."""
    mid, val = None, None
    for f, v in _fields(b, *span):
        if f == _XSTAT_METADATA_ID:
            mid = v
        elif f == _XSTAT_STR:
            val = _text(b, v)
        elif f == _XSTAT_REF:
            val = ref_strings.get(v)
    name = stat_names.get(mid)
    return {name: val} if name and val is not None else {}


def _plane_metadata(b: bytes, span) -> Dict[str, Dict[str, str]]:
    """{event name: {stat name: string}} of one XPlane, the stats
    limited to :data:`_STATS`.  Lines are skipped undecoded."""
    ev_spans, stat_names = [], {}
    for f, v in _fields(b, *span):
        if f == _XPLANE_EVENT_METADATA:
            ev_spans.append(v)
        elif f == _XPLANE_STAT_METADATA:
            for mf, mv in _fields(b, *v):
                if mf != _MAP_VALUE:
                    continue
                sid, sname = None, ""
                for sf, sv in _fields(b, *mv):
                    if sf == _XSTATMETA_ID:
                        sid = sv
                    elif sf == _XSTATMETA_NAME:
                        sname = _text(b, sv)
                stat_names[sid] = sname
    # a stat whose value is a ref_value names its string by the id of a
    # stat metadata entry
    wanted = {k: v for k, v in stat_names.items() if v in _STATS}
    events: Dict[str, Dict[str, str]] = {}
    for es in ev_spans:
        for mf, mv in _fields(b, *es):
            if mf != _MAP_VALUE:
                continue
            ev_name, stats = "", {}
            for ef, ev in _fields(b, *mv):
                if ef == _XEVENT_METADATA_NAME:
                    ev_name = _text(b, ev)
                elif ef == _XEVENT_METADATA_STATS and wanted:
                    stats.update(_stat_strings(b, ev, wanted, stat_names))
            prev = events.get(ev_name)
            if prev is None or (stats and not prev):
                events[ev_name] = stats
    return events


def xspace_metadata(data: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Device plane name → event name → the ``tf_op`` and ``source``
    stats of the event's metadata.

    ``jax.profiler.ProfileData`` exposes only the stats an event itself
    carries; the ``jax.named_scope`` path of an XLA operation (``tf_op``)
    and its Python ``source`` live on the plane's event metadata, which
    this reads from the protobuf's wire format."""
    out = {}
    for f, v in _fields(data):
        if f != _XSPACE_PLANES:
            continue
        # the name precedes the plane's lines: stop at it
        name = next((_text(data, pv) for pf, pv in _fields(data, *v)
                     if pf == _XPLANE_NAME), "")
        if DEVICE_PLANE_RE.match(name):
            out[name] = _plane_metadata(data, v)
    return out


def find_xplane_file(path: str) -> Optional[str]:
    """A ``.xplane.pb`` file itself, or the newest one under a profiler
    log directory; None when there is none."""
    if os.path.isfile(path):
        return path if path.endswith(".xplane.pb") else None
    if not os.path.isdir(path):
        return None
    hits = [os.path.join(root, f) for root, _d, files in os.walk(path)
            for f in files if f.endswith(".xplane.pb")]
    return max(hits, key=os.path.getmtime) if hits else None


def mark_containers(ops: List[DeviceOp]) -> List[DeviceOp]:
    """Sort by start and flag each operation whose interval holds the
    next one's start (events of one line nest, never cross)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [dataclasses.replace(o, container=True)
            if i + 1 < len(ops) and ops[i + 1].start < o.end else o
            for i, o in enumerate(ops)]


def read_xspace(path: str) -> Tuple[Dict[int, List[DeviceOp]],
                                    List[HostSpan]]:
    """The XLA operations of every device plane of one capture (a
    ``.xplane.pb`` file, or the newest under a log directory), per
    device and sorted by start, with their scope and source; and the
    program's host spans (names starting ``amgx/``) from the host
    planes.  Raises ``FileNotFoundError`` when there is no capture."""
    from jax.profiler import ProfileData
    f = find_xplane_file(path)
    if f is None:
        raise FileNotFoundError(f"no .xplane.pb at {path}")
    with open(f, "rb") as fh:
        data = fh.read()
    meta = xspace_metadata(data)
    ops: Dict[int, List[DeviceOp]] = {}
    spans: List[HostSpan] = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            pm = meta.get(plane.name, {})
            lst = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    st = pm.get(ev.name, {})
                    lst.append(DeviceOp(
                        dev, ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9,
                        st.get("tf_op", ""), st.get("source", "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(HostSpan(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    return ({d: mark_containers(v) for d, v in ops.items()},
            sorted(spans, key=lambda s: s.start))
