"""Seconds from the start of the process to the opening of the window:
imports, device start, weights, compiles (or compile-cache reads), the
engine's own latency probe and the warm-up."""


def read(run):
    return run.setup_s
