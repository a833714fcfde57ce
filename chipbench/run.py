#!/usr/bin/env python3
"""Benchmark entry: one run of one cell, on the chips of this machine.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process checks that JAX's backend is a TPU with as many chips as the
cell asks for (and exits non-zero otherwise: there is no CPU fallback),
builds the cell from its files (``BENCHMARK.json``, ``configs/``,
``mixes/``, ``cells/``), makes the weights on the device from the seed,
warms up every shape the window uses, serves the window on the wall clock,
follows every request sent to its end, checks what was served against the
plain reference, and prints one JSON line last on stdout. With ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from ten seconds mid-window of a profiler trace
that runs from before the window opens until serving ends.
The numbers the check compares are printed beside their limits as the last
lines on stderr, and under ``checks``, the last key of the JSON line.

  JAX_PLATFORMS=cpu python chipbench/run.py --workload <cell> --rehearse

runs the same pipeline on the CPU at the configuration's reduced size
(``rehearsal`` in its file) with the Pallas kernels interpreted, prints a
summary and no device metric, and exits 3.

The persistent compilation cache is ``<checkout>/.jax_cache``, whatever
JAX_COMPILATION_CACHE_DIR says: only the first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NO_CHIP = 3


def fail(msg: str, code: int = NO_CHIP) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def check_lines(checks):
    for name, c in checks.items():
        yield f"check {name}={c['value']!r} limit={c['limit']!r}"


def device_info(jax, run, view):
    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs), "memory_peak_bytes": run.memory_peak}
    if view is not None:
        d["busy_s"] = view.mean_busy_ns() / 1e9
        d["window_s"] = view.window_ns / 1e9
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=str(HERE / "out" / "trace"),
                    help="where the traced run writes its profile")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the reduced size; exits 3")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"{ROOT / 'src' / 'repro'} is missing: run from a "
                    "checkout of the repository", 2)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import cell as cellmod
    cell = cellmod.load(args.workload)
    if args.rehearse and cell.chips > 1 and \
            "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform"
            f"_device_count={cell.chips}").strip()

    import jax
    backend = jax.default_backend()
    if args.rehearse:
        if backend != "cpu":
            return fail("--rehearse runs on the CPU (JAX_PLATFORMS=cpu)", 2)
    else:
        if backend != "tpu":
            return fail(f"JAX's backend is {backend!r}, not a TPU; the "
                        "benchmark runs only on the chip")
        if len(jax.devices()) < cell.chips:
            return fail(f"{args.workload} needs {cell.chips} chips, JAX "
                        f"finds {len(jax.devices())}")
        # a fixed directory inside the checkout, whatever the environment
        # says, so that two checkouts share nothing; every program is kept
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
        kind = jax.devices()[0].device_kind
        if kind not in peaks:
            return fail(f"no peaks for device kind {kind!r} in peaks.json")

    import harness
    seconds = args.seconds
    if seconds is None:
        seconds = (cell.config["rehearsal"]["seconds"] if args.rehearse
                   else json.loads((ROOT / "BENCHMARK.json").read_text())
                   ["run_seconds"])
    trace_dir = Path(args.trace_dir) if args.trace else None
    run = harness.run_cell(cell, args.seed, seconds, trace_dir, T_PROCESS,
                           rehearse=args.rehearse)

    import stats
    late = sorted(run.late_ms)
    n_fail = stats.failed(run.sent)
    print(f"window: sent={len(run.sent)} finished={len(run.sent) - n_fail} "
          f"failed={n_fail} actions={run.actions} "
          f"window_s={run.window_s:.3f} setup_s={run.setup_s:.3f}",
          flush=True)
    print(f"generator lateness ms: p50={late[len(late) // 2]:.3f} "
          f"max={late[-1]:.3f}" if late else "generator lateness: none",
          flush=True)
    import layer
    info = {m: layer.reader("e2e", m)(run) for m in
            ("ttft_p90_ms", "tpot_p90_ms", "slo_attainment_pct")}
    print("window (host clock): " + " ".join(
        f"{k}={v!r}" for k, v in info.items()), flush=True)
    gaps = [g for t in run.sent for g in stats.token_gaps(t, run.end_ms)]
    print(f"token gaps ms (host clock): n={len(gaps)} " + " ".join(
        f"p{q}={stats.percentile(gaps, q)!r}" for q in (50, 75, 90, 95, 99)),
        flush=True)
    print(f"checked: requests={run.checked[0]} tokens={run.checked[1]} "
          f"max_gap={run.max_gap!r} reference_s={run.reference_s:.1f}",
          flush=True)
    print(f"compiles_in_window={run.compiles - run.cache_hits} "
          f"cache_reads_in_window={run.cache_hits}", flush=True)
    ok = harness.correct(run)
    if args.rehearse:
        print(f"rehearsal of {cell.name} on {backend}: correct={ok}; "
              "no device metric is reported off the chip", flush=True)
        for line in check_lines(run.checks):
            print(line, file=sys.stderr, flush=True)
        return NO_CHIP

    view = None
    metrics = {}
    breakdown = None
    if args.trace:
        import devtrace
        view = devtrace.view(str(trace_dir))
        if view is None:
            return fail(f"the traced run wrote no trace under {trace_dir}", 4)
        empty = [st.kind for st in view.steps if not st.ops]
        print(f"trace: stretch_s={view.window_ns / 1e9:.3f} "
              f"steps={len(view.steps)} steps_without_device_ops="
              f"{ {k: empty.count(k) for k in sorted(set(empty))} }",
              flush=True)
        peak = peaks[kind]
        ctx = layer.Ctx(trace=view, calls=run.calls,
                        arch=importlib.import_module(
                            f"references.{cell.config['reference']}"
                        ).Arch.from_config(cell.config),
                        peak_flops=peak["flops_per_s"],
                        peak_bw=peak["hbm_bytes_per_s"],
                        schedule_ms=run.schedule_ms, actions=run.actions)
        for m in cell.per_layer:
            v = layer.reader("metrics", m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": view.top_ops(10),
                     "idle_gaps": view.idle_gaps(10)}
    else:
        for m in cell.end_to_end:
            v = layer.reader("e2e", m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": ok, "attempted": len(run.sent), "failed": n_fail,
              "metrics": metrics, "device": device_info(jax, run, view)}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = run.checks
    sys.stdout.flush()
    for line in check_lines(run.checks):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
