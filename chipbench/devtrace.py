"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain events: for each TPU (``/device:TPU:<n>`` planes) its operations
(the "XLA Ops" line), and from the host planes the harness's own
annotations. ``TraceView`` then answers the questions the metric readers
ask: device busy time as the union of operation intervals, operations by
name, which operations ran inside which host annotation, and the idle gaps
between operations labelled by what the host was doing.

Host and device timestamps in these traces share one clock (the device's
are converted by the profiler), so an operation belongs to the host
annotation that contains its midpoint. That holds for this program's
synchronous dispatch, where each step blocks until its results are ready.

The harness names its annotations ``<kind>.<index>``: ``decode``,
``prefill_chunk`` and ``prefill`` around the executor's calls, ``schedule``
around the scheduler's ``next_action``, ``wait`` around the idle sleep,
and ``window`` around the traced stretch of the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

STEP_KINDS = ("decode", "prefill_chunk", "prefill")
# operations that hold other operations (a scan's loop): their events span
# their bodies' events, so a ranking by time leaves them out
CONTAINERS = ("while", "conditional", "call")
HOST_KINDS = STEP_KINDS + ("schedule", "wait", "window")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEV = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Ev:
    name: str
    start: float            # ns
    dur: float              # ns

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def mid(self) -> float:
        return self.start + self.dur / 2.0


def op_base(raw: str) -> str:
    """'%paged_decode_attention.4 = f32[...] custom-call(...)' ->
    'paged_decode_attention'."""
    head = raw.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def span_kind(name: str) -> Optional[Tuple[str, int]]:
    """'decode.12' -> ('decode', 12); names that are not the harness's
    annotations -> None."""
    kind, _, idx = name.rpartition(".")
    if kind in HOST_KINDS and idx.isdigit():
        return kind, int(idx)
    return None


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str):
    """(ops per device id, host annotation events) from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Ev]] = {}
    host: List[Ev] = []
    for plane in pd.planes:
        m = _DEV.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        Ev(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if span_kind(e.name) is not None:
                        host.append(Ev(e.name, float(e.start_ns),
                                       float(e.duration_ns)))
    return devices, host


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


@dataclass
class Span:
    kind: str
    index: int
    start: float
    end: float
    ops: Dict[int, List[Ev]] = field(default_factory=dict)


class TraceView:
    """The traced window: ``devices`` maps a device id to its operations,
    ``host`` holds the harness's annotations. The window is the
    ``window.<n>`` annotation; steps are the step annotations wholly
    inside it, each with the operations whose midpoints fall inside it."""

    def __init__(self, devices: Dict[int, List[Ev]], host: Sequence[Ev]):
        win = [e for e in host if e.name.startswith("window.")]
        if not win or not devices:
            raise ValueError("trace holds no window annotation or no device")
        self.lo, self.hi = win[0].start, win[0].end
        self.devices = {d: sorted((e for e in evs
                                   if e.end > self.lo and e.start < self.hi),
                                  key=lambda e: e.start)
                        for d, evs in devices.items()}
        self.host = sorted((e for e in host
                            if e.end > self.lo and e.start < self.hi
                            and not e.name.startswith("window.")),
                           key=lambda e: e.start)
        self.steps: List[Span] = []
        for e in self.host:
            kind, idx = span_kind(e.name)
            if kind in STEP_KINDS and e.start >= self.lo and e.end <= self.hi:
                self.steps.append(Span(kind, idx, e.start, e.end))
        starts = [s.start for s in self.steps]
        for d, evs in self.devices.items():
            for e in evs:
                i = bisect.bisect_right(starts, e.mid) - 1
                if i >= 0 and e.mid <= self.steps[i].end:
                    self.steps[i].ops.setdefault(d, []).append(e)

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    @property
    def chips(self) -> int:
        return len(self.devices)

    def busy_ns(self, dev: int) -> float:
        """Union of the device's operation intervals inside the window."""
        return length(clip(merge((e.start, e.end) for e in self.devices[dev]),
                           self.lo, self.hi))

    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns(d) for d in self.devices) / self.chips

    def steps_of(self, *kinds: str) -> List[Span]:
        return [s for s in self.steps if s.kind in kinds]

    @staticmethod
    def span_busy_ns(span: Span) -> float:
        """Device busy time inside one step, averaged over the chips."""
        if not span.ops:
            return 0.0
        per = [length(merge((e.start, e.end) for e in evs))
               for evs in span.ops.values()]
        return sum(per) / len(per)

    @staticmethod
    def span_op_ns(span: Span, pred) -> float:
        """Device time of the step's operations whose base name satisfies
        ``pred``, summed over the chips and averaged over them."""
        if not span.ops:
            return 0.0
        tot = sum(e.dur for evs in span.ops.values() for e in evs
                  if pred(op_base(e.name)))
        return tot / len(span.ops)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Operations by total device time in the window (seconds, mean
        over chips), longest first, without the loops that hold them."""
        tot: Dict[str, float] = {}
        for evs in self.devices.values():
            for e in evs:
                k = op_base(e.name)
                if k not in CONTAINERS:
                    tot[k] = tot.get(k, 0.0) + e.dur
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / self.chips / 1e9] for k, v in ranked]

    def idle_gaps(self, n: int = 10, dev: Optional[int] = None
                  ) -> List[Tuple[str, float]]:
        """The longest gaps between operations on one device inside the
        window, each labelled by the innermost harness annotation that
        holds the gap's midpoint ('host_other' where none does)."""
        dev = min(self.devices) if dev is None else dev
        busy = clip(merge((e.start, e.end) for e in self.devices[dev]),
                    self.lo, self.hi)
        edges = [self.lo] + [x for ab in busy for x in ab] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2.0
            label = "host_other"
            best = None
            for e in self.host:
                if e.start <= mid <= e.end and (best is None
                                                 or e.dur < best.dur):
                    best = e
            if best is not None:
                label = span_kind(best.name)[0]
            out.append([label, (b - a) / 1e9])
        return out


def view(trace_dir: str) -> Optional[TraceView]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    devices, host = load(path)
    return TraceView(devices, host)
