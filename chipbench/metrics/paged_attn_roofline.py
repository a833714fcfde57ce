"""Share of its roofline that the Pallas paged decode kernel
(``paged_decode_attention``) reaches in the traced decode steps: the least
time its calls could take on the chip, from their shapes (each active row's
context), over their device time (percent)."""
import flops
from layer import kernel_roofline


def read(ctx):
    return kernel_roofline(
        ctx, "decode", "paged_decode_attention",
        lambda contexts: [flops.paged_decode_kernel_cost(ctx.arch, contexts)])
