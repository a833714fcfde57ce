"""Model operations of every traced step (decode, prefill chunk, prefill)
over their device time times the chips' peak (percent)."""
from layer import mfu


def read(ctx):
    return mfu(ctx, "decode", "prefill_chunk", "prefill")
