"""Model operations of the traced decode steps over their device time times
the chips' peak (percent)."""
from layer import mfu


def read(ctx):
    return mfu(ctx, "decode")
