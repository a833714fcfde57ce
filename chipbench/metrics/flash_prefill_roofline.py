"""Share of its roofline that the Pallas flash chunk kernel
(``flash_prefill_chunk``) reaches in the traced prefill chunk calls: the
least time of its calls from their shapes (each piece's start and length),
over their device time (percent)."""
import flops
from layer import kernel_roofline


def read(ctx):
    return kernel_roofline(
        ctx, "prefill_chunk", "flash_prefill_chunk",
        lambda pieces: [flops.flash_chunk_kernel_cost(ctx.arch, s, n)
                        for s, n in pieces])
