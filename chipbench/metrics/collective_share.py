"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) inside the traced decode
steps, over those steps' device busy time (percent, mean over chips).
Nothing to read on one chip."""
from devtrace import COLLECTIVES


def read(ctx):
    if ctx.trace is None or ctx.trace.chips < 2:
        return None
    coll, busy = 0.0, 0.0
    for s in ctx.trace.steps_of("decode"):
        coll += ctx.trace.span_op_ns(
            s, lambda n: any(c in n for c in COLLECTIVES))
        busy += ctx.trace.span_busy_ns(s)
    return 100.0 * coll / busy if busy > 0 else None
