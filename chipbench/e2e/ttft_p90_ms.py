"""90th percentile of time to first token over every request sent in the
window, timed from its due time on the harness's wall clock; a request
that never got its first token counts with the wait it had reached when
serving stopped."""
from stats import percentile, ttft_ms


def read(run):
    return percentile([ttft_ms(t, run.end_ms) for t in run.sent], 90)
