"""Device busy time per decode step: the union of device operations inside
each traced ``decode`` annotation, averaged over steps (and chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    steps = [s for s in ctx.trace.steps_of("decode") if s.ops]
    if not steps:
        return None
    return sum(ctx.trace.span_busy_ns(s) for s in steps) / len(steps) / 1e6
