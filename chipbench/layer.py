"""Shared arithmetic of the per-layer metric readers (``metrics/<name>.py``).

A reader gets a ``Ctx``: the traced window (``trace``, a
``trace.TraceView``, or None in a run without a trace), the harness's
record of each step's shapes (``calls``: annotation index -> (kind,
detail)), the model's shapes (``arch``), the chip's peaks, and the
program's own counters over the window. A reader returns a number, or None
where it finds nothing to read.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Optional

import flops

HERE = Path(__file__).resolve().parent


def reader(group: str, name: str, base: Path = HERE) -> Callable:
    """The ``read`` function of ``<base>/<group>/<name>.py``: a metric is
    found by its name in BENCHMARK.json, with no list to edit."""
    path = base / group / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{group}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Ctx:
    trace: object
    calls: Dict[int, tuple]
    arch: object
    peak_flops: float
    peak_bw: float
    schedule_ms: float
    actions: int


def step_flops(ctx: Ctx, span) -> Optional[float]:
    call = ctx.calls.get(span.index)
    if call is None:
        return None
    kind, detail = call
    if kind == "decode":
        return flops.decode_step_flops(ctx.arch, detail)
    return sum(flops.chunk_step_flops(ctx.arch, s, n) for s, n in detail)


def mfu(ctx: Ctx, *kinds: str) -> Optional[float]:
    """Model operations of the traced steps of these kinds over the device
    time they took (busy time inside their annotations, mean over chips)
    times the chips' peak, in percent."""
    if ctx.trace is None:
        return None
    work, busy = 0.0, 0.0
    for span in ctx.trace.steps_of(*kinds):
        f = step_flops(ctx, span)
        t = ctx.trace.span_busy_ns(span)
        if f is None or t <= 0:
            continue
        work += f
        busy += t
    if busy <= 0:
        return None
    return 100.0 * work / (busy / 1e9 * ctx.trace.chips * ctx.peak_flops)


def kernel_roofline(ctx: Ctx, kind: str, op: str, cost) -> Optional[float]:
    """Least time of the kernel's calls in the traced steps of ``kind``
    (one call per layer per step or piece, ``cost(detail)`` giving each
    layer's (flops, bytes)) over the device time of the operations named
    ``op``, in percent."""
    if ctx.trace is None:
        return None
    least, took = 0.0, 0.0
    for span in ctx.trace.steps_of(kind):
        call = ctx.calls.get(span.index)
        t = ctx.trace.span_op_ns(span, lambda n: n == op)
        if call is None or t <= 0:
            continue
        for f, b in cost(call[1]):
            least += ctx.arch.layers * flops.roofline_s(
                f, b, ctx.peak_flops, ctx.peak_bw)[0]
        took += t / 1e9
    return 100.0 * least / took if took > 0 else None
