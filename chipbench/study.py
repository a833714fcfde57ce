#!/usr/bin/env python3
"""Readings from which the check's limits are set: for each seed, one run
of the cell as the benchmark runs it (window at the cell's own rate, every
request followed to its end), then the comparison numbers of what the
program served and of each control, the same reference computed in a
lower precision (``references.<ref>.CONTROLS``) and read at the same
prompts and tokens. Each control's numbers go through the harness's own
judgement beside the cell's limits, as ``correct``.

  python chipbench/study.py --workload <cell> --seeds 1,2,3 --seconds 20

One process serves every seed, one after the other, each with its own
weights, engine and state. Prints one ``STUDY`` JSON line per seed. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import cell as cellmod
    cell = cellmod.load(args.workload)
    if args.rehearse:
        import os
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell.chips}")
    import jax
    if not args.rehearse:
        if jax.default_backend() != "tpu":
            print("study: needs a TPU", file=sys.stderr)
            return 3
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness
    import stats
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, None, t,
                               rehearse=args.rehearse, control=True)
        out = {"seed": seed, "correct": harness.correct(run),
               "sent": len(run.sent), "failed": stats.failed(run.sent),
               "checked": run.checked,
               "program": dict({k: c["value"] for k, c in run.checks.items()},
                               max_gap=run.max_gap),
               "control": run.control,
               "seconds": time.perf_counter() - t}
        print("STUDY " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
