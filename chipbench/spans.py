#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and a tool that prints them.

The serving program marks its phases ``slice/<...>`` on the profiler's
host clock (``repro.serving.trace.span``), the clock the device's
operations are aligned to:

- ``slice/step`` for each loop action (``step``, ``action``; a poll that
  found no action has no ``action``), and ``slice/schedule`` around the
  scheduler (``replanned``; ``rt_slack_min_ms``, the least deadline slack
  of the real-time tasks it holds);
- ``slice/decode`` (``b``, ``rows``, ``ctx_max``) with ``prep``,
  ``launch``, ``sync`` and ``readback`` under it;
- ``slice/prefill_chunk/piece`` for each compiled chunk call (``start``,
  ``n``, ``bucket``) with ``prep``, ``launch`` and ``sync``, then
  ``slice/prefill_chunk/first_token``; ``slice/prefill`` for an atomic
  prefill;
- ``slice/drop`` for each drop (``task``, ``reason``; a deadline drop also
  ``slack_ms``, ``tokens_done`` and ``prefill_ms``).

``devtrace.load`` keeps only the harness's annotations, so the metrics that
read the program's spans get them here (``program``), from the same
``.xplane.pb``: the one under ``run.py``'s ``--trace-dir``, and only if its
``window`` annotation is the view's own. A trace without program spans (a
checkout whose program has none) reads None.

  python chipbench/spans.py <trace_dir> [--cut FIXTURE.json.gz]

prints each program span's count, mean and max in the traced stretch, the
ten longest device idle gaps labelled by the innermost program span, the
replans and the least real-time slack in the stretch and in the whole
trace, the drops by reason and each with its slack, and the metrics read
from the spans.
``--cut`` writes a test fixture: the last piece of a chunk step and the two
decode steps after it, with device 0's operations, the harness's
annotations and the program's spans. A one-off tool, not part of the
benchmark's run.
"""
from __future__ import annotations

import bisect
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
PREFIX = "slice/"
DECODE_HOST = ("slice/decode/prep", "slice/decode/launch",
               "slice/decode/readback")
PIECE_HOST = ("slice/prefill_chunk/piece/prep",
              "slice/prefill_chunk/piece/launch")


@dataclass
class PSpan:
    name: str
    start: float            # ns, the profiler's clock
    dur: float              # ns
    args: Dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(path: str):
    """(program spans sorted by start, start of the ``window.<n>``
    annotation or None) from one xplane file."""
    from jax.profiler import ProfileData
    out: List[PSpan] = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    out.append(PSpan(name, float(e.start_ns),
                                     float(e.duration_ns), dict(e.stats)))
                elif name.startswith("window.") and window is None:
                    window = float(e.start_ns)
    out.sort(key=lambda s: (s.start, -s.dur))
    return out, window


def run_trace_dir(argv: Optional[List[str]] = None) -> Path:
    """The ``--trace-dir`` of run.py's command line, else its default."""
    argv = sys.argv[1:] if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--trace-dir" and i + 1 < len(argv):
            return Path(argv[i + 1])
        if a.startswith("--trace-dir="):
            return Path(a.split("=", 1)[1])
    return HERE / "out" / "trace"


def attach(view, spans: List[PSpan]) -> None:
    view.program = spans


def program(view) -> Optional[List[PSpan]]:
    """Every program span of the view's trace, or None where it has none."""
    if view is None:
        return None
    if getattr(view, "program", None) is None:
        import devtrace
        spans: List[PSpan] = []
        path = devtrace.find_xplane(str(run_trace_dir()))
        if path is not None:
            got, window = load(path)
            if window == view.lo:
                spans = got
        attach(view, spans)
    return view.program or None


def in_window(view, spans: List[PSpan], name: str) -> List[PSpan]:
    return [s for s in spans if s.name == name
            and s.start >= view.lo and s.end <= view.hi]


def inside(spans: List[PSpan], outer: PSpan, names) -> List[PSpan]:
    """Spans named one of ``names`` that lie within ``outer`` (``spans``
    sorted by start)."""
    i = bisect.bisect_left(spans, outer.start, key=lambda s: s.start)
    out = []
    for s in spans[i:]:
        if s.start > outer.end:
            break
        if s.name in names and s.end <= outer.end:
            out.append(s)
    return out


def busy_ns(view, lo: float, hi: float) -> float:
    """Device busy time within [lo, hi]: the union of the operations,
    clipped to it, averaged over the chips."""
    import devtrace
    merged = getattr(view, "merged_ops", None)
    if merged is None:
        merged = view.merged_ops = {
            d: devtrace.merge((e.start, e.end) for e in evs)
            for d, evs in view.devices.items()}
    tot = 0.0
    for ivs in merged.values():       # disjoint, so sorted by end too
        i = bisect.bisect_right(ivs, lo, key=lambda ab: ab[1])
        for a, b in ivs[i:]:
            if a >= hi:
                break
            tot += min(b, hi) - max(a, lo)
    return tot / len(merged)


def exec_host_ms_per_step(view, spans: List[PSpan]) -> Optional[float]:
    """Executor host time per loop action in the window: decode ``prep``,
    ``launch`` and ``readback`` and chunk pieces' ``prep`` and ``launch``,
    summed over the actions and divided by their number."""
    steps = [s for s in in_window(view, spans, "slice/step")
             if "action" in s.args]
    if not steps:
        return None
    host = sum(s.dur for st in steps
               for s in inside(spans, st, DECODE_HOST + PIECE_HOST))
    return host / len(steps) / 1e6


def chunk_piece_device_ms(view, spans: List[PSpan]) -> Optional[float]:
    """Mean device busy time inside a chunk piece's span."""
    pieces = in_window(view, spans, "slice/prefill_chunk/piece")
    if not pieces:
        return None
    return sum(busy_ns(view, p.start, p.end) for p in pieces) \
        / len(pieces) / 1e6


def decode_sync_idle_ms(view, spans: List[PSpan]) -> Optional[float]:
    """Mean over decode steps of the device's idle time inside the step's
    ``sync`` span: the host waits on a device that runs nothing."""
    idle, n = 0.0, 0
    for d in in_window(view, spans, "slice/decode"):
        for s in inside(spans, d, ("slice/decode/sync",)):
            idle += s.dur - busy_ns(view, s.start, s.end)
        n += 1
    return idle / n / 1e6 if n else None


def plan(spans: List[PSpan]) -> Dict:
    """Over the given ``slice/schedule`` spans: how many, how many
    replanned, and the least real-time deadline slack they carry (ms;
    None where no real-time task was held)."""
    sched = [s for s in spans if s.name == "slice/schedule"]
    slack = [s.args["rt_slack_min_ms"] for s in sched
             if "rt_slack_min_ms" in s.args]
    return {"schedules": len(sched),
            "replans": sum(bool(s.args.get("replanned")) for s in sched),
            "rt_slack_min_ms": min(slack) if slack else None}


def drops_by_reason(spans: List[PSpan]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s in spans:
        if s.name == "slice/drop":
            r = s.args.get("reason", "?")
            out[r] = out.get(r, 0) + 1
    return out


def innermost(spans: List[PSpan], t: float) -> Optional[PSpan]:
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and (best is None or s.dur <= best.dur):
            best = s
    return best


def report(view, spans: List[PSpan]) -> None:
    import devtrace
    print(f"stretch_s={view.window_ns / 1e9:.3f} program_spans="
          f"{len(spans)}")
    names = sorted({s.name for s in spans})
    print("program spans in the stretch (name n mean_ms max_ms):")
    for name in names:
        got = in_window(view, spans, name)
        if got:
            durs = [s.dur / 1e6 for s in got]
            print(f"  {name} {len(got)} {sum(durs) / len(durs):.4f} "
                  f"{max(durs):.4f}")
    dev = min(view.devices)
    busy = devtrace.clip(devtrace.merge(
        (e.start, e.end) for e in view.devices[dev]), view.lo, view.hi)
    edges = [view.lo] + [x for ab in busy for x in ab] + [view.hi]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    harness = [PSpan(e.name, e.start, e.dur) for e in view.host]
    print(f"longest idle gaps on device {dev} (ms, innermost span):")
    for a, b in gaps[:10]:
        s = innermost(spans, (a + b) / 2.0)
        h = innermost(harness, (a + b) / 2.0)
        label = (f"{s.name} {s.args}" if s is not None else
                 f"no program span ({h.name if h else 'no annotation'})")
        print(f"  {(b - a) / 1e6:.3f} at +{(a - view.lo) / 1e6:.1f} {label}")
    print(f"schedule, the stretch: "
          f"{plan(in_window(view, spans, 'slice/schedule'))}")
    print(f"schedule, the whole trace: {plan(spans)}")
    drops = [s for s in spans if s.name == "slice/drop"]
    print(f"drops: {drops_by_reason(spans)} (the whole trace; ms from "
          f"the stretch)")
    for s in drops:
        print(f"  +{(s.start - view.lo) / 1e6:.1f} {s.args}")
    for fn in (exec_host_ms_per_step, chunk_piece_device_ms,
               decode_sync_idle_ms):
        print(f"{fn.__name__}={fn(view, spans)!r}")


def cut(view, spans: List[PSpan], out: Path) -> None:
    """Write the last chunk piece of a chunk step and the two decode steps
    after it as a gzip JSON fixture."""
    import gzip
    import json
    import devtrace
    steps = [s for s in in_window(view, spans, "slice/step")
             if "action" in s.args]
    for i in range(len(steps) - 2):
        a, b, c = steps[i:i + 3]
        if a.args["action"] == "prefill_chunk" and \
                b.args["action"] == c.args["action"] == "decode":
            break
    else:
        raise SystemExit("no chunk step followed by two decode steps")
    lo = inside(spans, a, ("slice/prefill_chunk/piece",))[-1].start
    hi = c.end
    dev = min(view.devices)
    ops = [[devtrace.op_base(e.name), e.start, e.dur]
           for e in view.devices[dev] if e.end > lo and e.start < hi]
    host = [[e.name, e.start, e.dur] for e in view.host
            if e.start >= lo and e.end <= hi]
    prog = [[s.name, s.start, s.dur, s.args] for s in spans
            if s.start >= lo and s.end <= hi]
    fx = {"source": f"device {dev} of one chip: the last piece of a chunk "
                    "step and the two decode steps after it, with the "
                    "program's spans",
          "window": [lo, hi], "host": host, "ops": ops, "program": prog}
    with gzip.open(out, "wt") as f:
        json.dump(fx, f)
    print(f"fixture: {out} ops={len(ops)} host={len(host)} "
          f"program={len(prog)}")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--cut", default=None, metavar="FIXTURE.json.gz")
    args = ap.parse_args(argv)
    import devtrace
    path = devtrace.find_xplane(args.trace_dir)
    if path is None:
        print(f"no trace under {args.trace_dir}", file=sys.stderr)
        return 2
    devices, host = devtrace.load(path)
    view = devtrace.TraceView(devices, host)
    spans, _ = load(path)
    report(view, spans)
    if args.cut:
        cut(view, spans, Path(args.cut))
    return 0


if __name__ == "__main__":
    sys.exit(main())
