"""90th percentile of the time per output token over every token gap of
every request sent in the window, after each request's first decode token
(wall clock); a request that had not finished when serving stopped adds the
wait it had reached for its next token."""
from stats import percentile, token_gaps


def read(run):
    return percentile([g for t in run.sent for g in token_gaps(t, run.end_ms)],
                      90)
