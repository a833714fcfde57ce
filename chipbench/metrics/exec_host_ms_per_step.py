"""Executor host time per loop action in the traced window, from the
program's spans: decode ``prep``, ``launch`` and ``readback`` plus chunk
pieces' ``prep`` and ``launch``, over the ``slice/step`` spans that ran an
action. None on a trace without program spans."""
import spans


def read(ctx):
    got = spans.program(ctx.trace)
    return None if got is None else spans.exec_host_ms_per_step(ctx.trace, got)
