"""Model operations of the traced prefill chunk calls over their device
time times the chips' peak (percent)."""
from layer import mfu


def read(ctx):
    return mfu(ctx, "prefill_chunk")
