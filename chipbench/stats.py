"""Arithmetic of the end-to-end metrics, over every request sent in the
window.

The percentile is numpy's linear interpolation, as the program's own
``serving.metrics.percentile``. Unlike ``serving.metrics.summarize``, which
drops requests that never produced a token, nothing here drops a request:
a request that never got its first token counts with the TTFT it had
reached when serving stopped (a lower bound, so it lands in the tail), and
SLO attainment counts a request that failed or never finished as a miss.
A request's TPOT is the program's steady-state definition
(``Task.tpot_measured_ms``); the TPOT tail is taken over every token gap of
every request, so that a window of tens of requests still gives thousands
of samples.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """numpy's linear percentile; None when empty."""
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, dtype=float), q))


def ttft_ms(t, end_ms: float = math.inf) -> float:
    """Time to first token; ``end_ms - due`` for a request that had none
    when serving stopped at ``end_ms``."""
    tt = t.token_times_ms
    return (tt[0] - t.arrival_ms) if tt else end_ms - t.arrival_ms


def tpot_ms(t) -> Optional[float]:
    """Steady-state TPOT: mean gap after the first decode token (the gap
    between the prefill's first token and the first decode token is
    queueing, counted in TTFT)."""
    tt = t.token_times_ms
    if len(tt) < 2:
        return None
    if len(tt) == 2:
        return tt[1] - tt[0]
    return (tt[-1] - tt[1]) / (len(tt) - 2)


def token_gaps(t, end_ms: float = math.inf) -> List[float]:
    """The gaps between a request's successive tokens after its first
    decode token (the steady-state TPOT's own gaps). A request that had
    not finished when serving stopped at ``end_ms`` adds the wait it had
    reached for its next token."""
    tt = t.token_times_ms
    out = [tt[j + 1] - tt[j] for j in range(1, len(tt) - 1)]
    if len(tt) >= 2 and not finished(t) and math.isfinite(end_ms):
        out.append(end_ms - tt[-1])
    return out


def finished(t) -> bool:
    return (not t.dropped) and len(t.token_times_ms) >= t.output_len


def slo_met(t) -> bool:
    """SLICE's rule (paper section VI-A): a real-time request meets its
    SLO when its last token lands by its deadline, a non-real-time one when
    both its TTFT and its steady-state TPOT are within their limits. A
    request that failed or did not finish misses."""
    if not finished(t):
        return False
    if t.slo.realtime:
        return t.token_times_ms[-1] - t.arrival_ms <= t.slo.deadline_ms
    return ttft_ms(t) <= t.slo.ttft_ms and tpot_ms(t) <= t.slo.tpot_ms


def failed(tasks: Iterable) -> int:
    return sum(not finished(t) for t in tasks)


def attainment_pct(tasks: List) -> Optional[float]:
    return 100.0 * sum(slo_met(t) for t in tasks) / len(tasks) if tasks \
        else None
