"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Only device planes count as device time: a plane named
``/device:TPU:<n>`` (or GPU), and on it the line of XLA operations.
Host threads, whatever they record, never do.  The benchmark's own host
spans (``jax.profiler.TraceAnnotation`` names starting ``bench/``) come
from the host plane and only label what the host was doing in an idle
gap.

All times are seconds on the trace's clock.  The interval arithmetic
follows ``amgx_tpu/telemetry/proftrace.py`` (copied, so that the
yardstick does not move when the program's telemetry does).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

#: a device plane
DEVICE_PLANE_RE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
#: the line of a device plane that holds one event per executed XLA
#: operation (a Pallas kernel is one such operation)
OPS_LINE = "XLA Ops"
#: XLA operation names that mean communication between chips
COMM_RE = re.compile(
    r"all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter|"
    r"collective[-_]?permute|all[-_]?to[-_]?all|ppermute|psum",
    re.IGNORECASE)
#: prefix of the benchmark's own host spans
SPAN_PREFIX = "bench/"
#: the span around the whole measured window
WINDOW_SPAN = "bench/window"


@dataclasses.dataclass(frozen=True)
class Op:
    #: the HLO instruction's name (``fusion.626``, ``_dia_spmv_call.66``)
    name: str
    start: float
    end: float
    #: the instruction's HLO text as the trace gives it (shapes of the
    #: result and operands included)
    text: str
    #: True for an operation that holds others (``while``,
    #: ``conditional``): its body's operations are events of their own
    container: bool = False


def short_name(text: str) -> str:
    """``%fusion.626 = f32[...] fusion(...)`` -> ``fusion.626``."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def merge(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Coalesce (start, end) intervals into a sorted disjoint cover."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of merged cover ``a`` that merged cover ``b`` leaves."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Trace:
    """Device operations per device and the benchmark's host spans."""

    def __init__(self, ops: Dict[int, List[Op]], spans: List[Span]):
        self.ops = {d: _mark_containers(v) for d, v in ops.items()}
        self.spans = sorted(spans, key=lambda s: s.start)
        win = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        self.window = (win[0].start, win[-1].end)

    # ------------------------------------------------------------ basics
    @property
    def devices(self) -> List[int]:
        return sorted(d for d, v in self.ops.items() if v)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def window_ops(self, dev: int) -> List[Op]:
        lo, hi = self.window
        return [o for o in self.ops.get(dev, ()) if o.end > lo
                and o.start < hi]

    def busy(self, dev: int) -> List[Tuple[float, float]]:
        """Merged intervals in which an operation ran on ``dev`` inside
        the window."""
        lo, hi = self.window
        return merge(clip([(o.start, o.end) for o in self.window_ops(dev)],
                          lo, hi))

    def busy_s(self) -> Optional[float]:
        """Busy seconds in the window, the mean over the devices."""
        devs = self.devices
        if not devs:
            return None
        return sum(length(self.busy(d)) for d in devs) / len(devs)

    def idle_share(self) -> Optional[float]:
        b = self.busy_s()
        if b is None or self.window_s <= 0:
            return None
        return 1.0 - b / self.window_s

    # ------------------------------------------------------- by operation
    def op_seconds(self, pred=None) -> Dict[str, float]:
        """Device seconds per operation name inside the window, summed
        over the devices and divided by their number; only operations
        that hold no others count, so no time counts twice.
        ``pred(op)`` filters."""
        lo, hi = self.window
        tot: Dict[str, float] = collections.Counter()
        devs = self.devices
        for d in devs:
            for o in self.window_ops(d):
                if not o.container and (pred is None or pred(o)):
                    tot[o.name] += min(o.end, hi) - max(o.start, lo)
        return {k: v / len(devs) for k, v in tot.items()} if devs else {}

    def select(self, pred) -> List[Op]:
        """Every operation that holds no others, in the window on any
        device, with ``pred``."""
        return [o for d in self.devices for o in self.window_ops(d)
                if not o.container and pred(o)]

    def busy_share(self, pred) -> Optional[float]:
        """Share of the devices' busy time spent in operations with
        ``pred`` (summed durations over summed busy time)."""
        busy = sum(length(self.busy(d)) for d in self.devices)
        if busy <= 0:
            return None
        part = sum(self.op_seconds(pred).values()) * len(self.devices)
        return part / busy

    # ------------------------------------------------------ collectives
    def exposed_collective_share(self) -> Optional[float]:
        """Per device, the time in collective operations during which no
        other operation runs on that device, over the window; the mean
        over the devices.  None when no collective ran."""
        devs = self.devices
        if not devs or self.window_s <= 0:
            return None
        lo, hi = self.window
        seen, shares = False, []
        for d in devs:
            ops = self.window_ops(d)
            comm = merge(clip([(o.start, o.end) for o in ops
                               if COMM_RE.search(o.name)], lo, hi))
            other = merge(clip([(o.start, o.end) for o in ops
                                if not o.container
                                and not COMM_RE.search(o.name)], lo, hi))
            seen = seen or bool(comm)
            shares.append(length(subtract(comm, other)) / self.window_s)
        return sum(shares) / len(shares) if seen else None

    # ----------------------------------------------------------- gaps
    def label(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (without the
        ``bench/`` prefix); "harness" inside the window but outside
        every step span."""
        best = None
        for s in self.spans:
            if s.start <= t < s.end and (best is None
                                         or s.end - s.start
                                         < best.end - best.start):
                best = s
        if best is None:
            return "outside"
        if best.name == WINDOW_SPAN:
            return "harness"
        return best.name[len(SPAN_PREFIX):]

    def idle_gaps(self, dev: Optional[int] = None
                  ) -> List[Tuple[str, float]]:
        """Idle gaps of device ``dev`` (default: the first) inside the
        window, longest first, each labelled by what the host was doing
        at its middle."""
        devs = self.devices
        if not devs:
            return []
        d = devs[0] if dev is None else dev
        lo, hi = self.window
        gaps = subtract([(lo, hi)], self.busy(d))
        out = [(self.label(0.5 * (s + e)), e - s) for s, e in gaps]
        return sorted(out, key=lambda g: -g[1])


def _mark_containers(ops: List[Op]) -> List[Op]:
    """Sort by start and flag each operation whose interval holds the
    next one's start (events of one line nest, never cross)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [dataclasses.replace(o, container=True)
            if i + 1 < len(ops) and ops[i + 1].start < o.end else o
            for i, o in enumerate(ops)]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file, or the newest one under a profiler
    log directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        hits = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                         recursive=True)
        if not hits:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(hits, key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lst = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    lst.append(Op(short_name(ev.name), ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns)
                                          * 1e-9))
    return Trace(ops, spans)
