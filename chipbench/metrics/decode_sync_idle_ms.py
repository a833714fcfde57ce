"""Per decode step of the traced window, the device's idle time inside
the step's ``slice/decode/sync`` span, where the host waits on a device
that runs nothing; the mean over steps (and chips). None on a trace without
program spans."""
import spans


def read(ctx):
    got = spans.program(ctx.trace)
    return None if got is None else spans.decode_sync_idle_ms(ctx.trace, got)
