"""Mean device busy time inside each ``slice/prefill_chunk/piece`` span
of the traced window (mean over chips). None on a trace without program
spans."""
import spans


def read(ctx):
    got = spans.program(ctx.trace)
    return None if got is None else spans.chunk_piece_device_ms(ctx.trace, got)
