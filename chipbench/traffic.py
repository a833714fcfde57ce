"""Traffic generator: one open-loop request schedule from a mix file, a
rate and a seed.

The mix file (``mixes/<mix>.json``) gives the request classes: each class's
share of requests, its prompt-length range [lo, hi), its output length
~N(mean, sd) floored at ``output_min``, and its SLO. A real-time class
states an end-to-end deadline, split into a TTFT budget (``ttft_share`` of
it) and a per-token budget for the rest, as SLICE translates deadlines
(``SLOSpec.realtime_deadline``). A non-real-time class states TTFT and TPOT
limits. The SLOs are absolute: nothing here scales them.

Every seed offers the same work. The count is rate x seconds; each class
gets its share of them (largest remainder); its prompt and output lengths
are the evenly spaced quantiles of the class's distributions; and the gaps
between arrivals are the evenly spaced quantiles of an exponential law
with mean 1000/rate ms, rescaled to span the window. One fixed permutation
sets which class comes when and which gap follows which; the run's seed
sets which request of a class gets which lengths, and draws the weights and
every prompt's tokens. At the tens of requests a window holds, the order
of classes and gaps is the work itself: which long requests overlap sets
the decode batch, and so the TPOT tail (10-40% from seed to seed where the
seed drew that order too).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

import numpy as np

TASK_ID_BASE = 10_000_000
ORDER_SEED = 20251021          # the one order of classes and gaps


def _largest_remainder(n: int, shares: List[float]) -> List[int]:
    total = sum(shares)
    raw = [n * s / total for s in shares]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def class_lengths(cls: Dict, n: int) -> List[tuple]:
    """The n (prompt_len, output_len) pairs of one class, in quantile
    order: prompts evenly over [lo, hi), outputs at the normal law's
    quantiles. The caller permutes them."""
    lo, hi = cls["prompt"]
    q = _quantiles(n)
    prompts = [int(lo + math.floor(x * (hi - lo))) for x in q]
    nd = statistics.NormalDist(cls["output_mean"], cls["output_sd"])
    outs = [max(int(cls["output_min"]), int(round(nd.inv_cdf(x)))) for x in q]
    return list(zip(prompts, outs))


def schedule(mix: Dict, rate_per_s: float, seconds: float,
             seed: int) -> List[Dict]:
    """Request specs sorted by due time: dicts with ``index``, ``kind``,
    ``due_ms``, ``prompt_len``, ``output_len`` and the class (``cls``).
    Every seed gets the same classes, gaps and lengths; the seed deals
    each class's lengths to its requests."""
    n = max(1, int(round(rate_per_s * seconds)))
    fixed = np.random.default_rng(np.random.SeedSequence(ORDER_SEED))
    drawn = np.random.default_rng(np.random.SeedSequence(
        [seed & (2**64 - 1), ORDER_SEED]))
    classes = mix["classes"]
    counts = _largest_remainder(n, [c["share"] for c in classes])
    reqs = []
    for cls, k in zip(classes, counts):
        pairs = class_lengths(cls, k)
        for j in drawn.permutation(k):
            reqs.append((cls, pairs[j]))
    order = fixed.permutation(n)
    mean_gap = 1000.0 / rate_per_s
    gaps = -np.log1p(-_quantiles(n)) * mean_gap
    gaps = gaps[fixed.permutation(n)]
    due = np.cumsum(gaps)
    # the last request is due one mean gap before the window closes
    due *= max(seconds * 1000.0 - mean_gap, 0.0) / due[-1]
    out = []
    for i, slot in enumerate(order):
        cls, (p, o) = reqs[slot]
        out.append({"index": i, "kind": cls["kind"], "due_ms": float(due[i]),
                    "prompt_len": p, "output_len": o, "cls": cls})
    return out


def make_task(spec: Dict, task_id: int, due_ms: float = None):
    """A program Task for one request spec, due at ``due_ms`` (default: the
    spec's own due time) on the harness's wall clock."""
    from repro.core.task import SLOSpec, Task
    cls = spec["cls"]
    out_len = spec["output_len"]
    if cls["realtime"]:
        slo = SLOSpec.realtime_deadline(cls["deadline_ms"], out_len,
                                        ttft_frac=cls["ttft_share"])
    else:
        slo = SLOSpec(tpot_ms=cls["tpot_ms"], ttft_ms=cls["ttft_ms"])
    return Task(slo, utility=cls["utility"], prompt_len=spec["prompt_len"],
                output_len=out_len,
                arrival_ms=spec["due_ms"] if due_ms is None else due_ms,
                task_id=task_id, kind=spec["kind"])


def max_budget_ms(mix: Dict) -> float:
    """The mix's largest end-to-end budget: a deadline, or TTFT plus the
    class's mean output at its TPOT (voice: 2 s + 256 x 125 ms)."""
    best = 0.0
    for c in mix["classes"]:
        if c["realtime"]:
            best = max(best, c["deadline_ms"])
        else:
            best = max(best, c["ttft_ms"] + c["output_mean"] * c["tpot_ms"])
    return best
