#!/usr/bin/env python3
"""Find a cell's knee once: serve the cell's mix at several offered rates
on one engine, one window per rate, and report for each the share of
requests sent that met their SLO, the tails, and whether the backlog grew.

  python chipbench/sweep.py --workload <cell> --rates 0.5,1,2 --seconds 30

The knee is the highest rate at which at least 90% of the requests sent
meet their SLO and the backlog does not grow. This is a one-off measurement
for choosing a cell's rate, not a benchmark run: the windows share one
engine and one warm-up, and nothing is checked against the reference.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import cell as cellmod
    import harness
    import stats
    import traffic
    cell = cellmod.load(args.workload)
    b = harness.build(cell, args.seed, rehearse=False)
    t_origin = time.perf_counter()

    def clock():
        return (time.perf_counter() - t_origin) * 1000.0

    harness.warm_up(b, cell.mix, clock)
    print(f"setup_s={time.perf_counter() - T_PROCESS:.1f}", flush=True)
    base = traffic.TASK_ID_BASE
    for rate in [float(r) for r in args.rates.split(",")]:
        specs = traffic.schedule(cell.mix, rate, args.seconds, args.seed)
        t_w = clock()
        tasks = [traffic.make_task(s, base + s["index"], t_w + s["due_ms"])
                 for s in specs]
        base += len(tasks)
        close = t_w + args.seconds * 1000.0
        # backlog: requests delivered and unfinished, sampled each step
        samples = []
        step = b.drv.step

        def sampled_step():
            samples.append((clock() - t_w, len(b.drv.live_tasks())))
            return step()

        b.drv.step = sampled_step
        harness.serve(b, tasks, clock,
                      deadline_ms=close + traffic.max_budget_ms(cell.mix))
        b.drv.step = step
        end = clock()
        in_win = [n for t, n in samples if t <= args.seconds * 1000.0]
        third = max(1, len(in_win) // 3)
        first = sum(in_win[:third]) / third if in_win else 0.0
        last = sum(in_win[-third:]) / third if in_win else 0.0
        out = {
            "rate": rate, "sent": len(tasks),
            "failed": stats.failed(tasks),
            "slo_attainment_pct": stats.attainment_pct(tasks),
            "ttft_p90_ms": stats.percentile(
                [stats.ttft_ms(t, end) for t in tasks], 90),
            "tpot_p90_ms": stats.percentile(
                [g for t in tasks for g in stats.token_gaps(t, end)], 90),
            "backlog_first_third": first, "backlog_last_third": last,
            "drain_s": (end - close) / 1000.0,
            "by_kind": {k: stats.attainment_pct(
                [t for t in tasks if t.kind == k])
                for k in sorted({t.kind for t in tasks})},
        }
        print("SWEEP " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
