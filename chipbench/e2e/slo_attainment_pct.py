"""Share of the requests sent in the window that met their SLO by SLICE's
rule; failed and unfinished requests count as misses."""
from stats import attainment_pct


def read(run):
    return attainment_pct(run.sent)
