"""Sharded-serving benchmark (EXPERIMENTS.md §Sharded-serving, gate 5):
tensor-parallel paged decode on a forced-host-device mesh.

A CPU-only structural gate: it must be run with JAX_PLATFORMS=cpu and
refuses to start otherwise. Its numbers come from four host devices that
share one CPU, so it says nothing about chips; the tensor-parallel path
on real chips is checked by ``chip_smoke.py --four-chips``.

Shards the paged engine 2- and 4-way over KV heads (DESIGN.md §9) and
drives the SAME workload on a single-device twin, asserting the
equivalence contract the test harness enforces (logits < 1e-5), zero page
leaks after release on every engine, and a genuinely partitioned arena
(4 distinct device shards). Throughput is reported for the scaling table
but NOT gated: forced host devices share one physical CPU, so wall-clock
"scaling" there measures XLA partition overhead, not parallel speedup.

Runs its measurement in a SUBPROCESS: run.py's earlier benches initialise
jax with the default single CPU device, and
``--xla_force_host_platform_device_count`` only takes effect before first
backend init. The worker re-execs this module with XLA_FLAGS forced.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.sharded_serving [--tiny]
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

N_DEVICES = 4


def _worker(tiny: bool) -> None:
    import dataclasses
    import time

    import numpy as np
    import jax

    from benchmarks.common import emit, save_json
    from repro.configs import get_config
    from repro.core.task import qa_task
    from repro.launch.mesh import make_serving_mesh
    from repro.models import model as M
    from repro.serving.executor import PagedJaxExecutor

    assert jax.device_count() >= N_DEVICES, jax.device_count()
    # MHA (4 KV heads): the reduced GQA head count of 1 would fall back to
    # replicated slabs and make the sharding vacuous (tests/helpers.py)
    cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                              n_kv_heads=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n_tasks = 4
    eq_steps = 6 if tiny else 10
    timed_steps = 8 if tiny else 24
    prompt = 12 if tiny else 24
    kw = dict(n_pages=64, page_size=8, max_seq=256, max_batch=4, seed=0)

    def build(ways: int) -> PagedJaxExecutor:
        mesh = make_serving_mesh(model=ways) if ways > 1 else None
        return PagedJaxExecutor(cfg, params=params, mesh=mesh, **kw)

    engines = {w: build(w) for w in (1, 2, 4)}
    tasks = [qa_task(prompt_len=prompt, output_len=eq_steps + timed_steps + 4)
             for _ in range(n_tasks)]
    for t in tasks:
        for ex in engines.values():
            ex.prefill(t)

    # equivalence phase: decode all engines in lockstep, compare logits
    max_err = 0.0
    for _ in range(eq_steps):
        engines[1].decode(tasks)
        ref = engines[1].last_logits.copy()
        for w in (2, 4):
            engines[w].decode(tasks)
            max_err = max(max_err, float(
                np.abs(engines[w].last_logits - ref).max()))
            engines[w].pool.check()
    equiv_ok = 1.0 if max_err < 1e-5 else 0.0
    assert equiv_ok, f"sharded logits diverged: max_abs_err={max_err}"

    # arena really is partitioned: 4 shards on 4 distinct devices
    shards = engines[4].pages["k_pages"].addressable_shards
    distinct_devices = len({s.device for s in shards})
    assert distinct_devices == N_DEVICES, distinct_devices

    # throughput scaling table (informational — host devices share a CPU)
    thr = {}
    for w, ex in engines.items():
        ex.decode(tasks)                      # warm
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            ex.decode(tasks)
        thr[str(w)] = n_tasks * timed_steps / (time.perf_counter() - t0)

    pages_leaked = 0
    for ex in engines.values():
        for t in tasks:
            ex.release(t)
        ex.pool.check()                       # clean on every device slab
        pages_leaked += ex.pool.used_pages
    assert pages_leaked == 0, pages_leaked

    payload = {"engine": {"equiv_ok": equiv_ok, "max_abs_err": max_err,
                          "pages_leaked": pages_leaked,
                          "n_devices": jax.device_count(),
                          "arena_shards_4way": distinct_devices,
                          "throughput_tok_s": thr},
               "config": {"tiny": tiny, "n_tasks": n_tasks,
                          "eq_steps": eq_steps, "timed_steps": timed_steps,
                          "prompt_len": prompt, "n_kv_heads": cfg.n_kv_heads}}
    emit("sharded_serving/equiv_ok", equiv_ok)
    emit("sharded_serving/max_abs_err", f"{max_err:.3g}")
    emit("sharded_serving/pages_leaked", pages_leaked)
    emit("sharded_serving/n_devices", jax.device_count())
    for w in ("1", "2", "4"):
        emit(f"sharded_serving/throughput_tok_s/ways={w}",
             round(thr[w], 1), derived="informational")
    save_json("sharded_serving", payload)


def run(tiny: bool = False, engine: bool = True) -> None:
    """Re-exec in a worker with the device count forced (see module doc).
    ``engine`` is accepted for harness symmetry; the bench IS the engine
    measurement, tiny by construction, so it always runs."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("sharded_serving is a CPU-only structural gate on "
                         "forced host devices: run it with "
                         "JAX_PLATFORMS=cpu (chip_smoke.py --four-chips "
                         "checks the sharded path on real chips)")
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                        f"{N_DEVICES}").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_serving", "--worker"]
        + (["--tiny"] if tiny else []), env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded_serving worker failed (exit {proc.returncode})")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke config: fewer steps, shorter prompts")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run the measurement in THIS process "
                         "(expects XLA_FLAGS already forced)")
    args = ap.parse_args()
    if args.worker:
        _worker(tiny=args.tiny)
    else:
        print("name,value,derived")
        run(tiny=args.tiny)
