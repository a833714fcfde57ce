"""Share of the traced window in which no operation ran on the device
(percent, mean over chips)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_ns() / ctx.trace.window_ns)
