#!/usr/bin/env python3
"""Chip smoke check: drive the SLICE serving path once on a TPU and check
what comes out.

  python chip_smoke.py                # one chip: phases A, B and C
  python chip_smoke.py --four-chips   # four chips: the tensor-parallel path

One chip, smollm-360m at its published width (32 layers, d_model 960,
15/5 heads, vocab 49152; random f32 weights from --seed):

  A  serve      ``repro.launch.serve.main`` with the paged engine and the
                SLICE scheduler on a few seconds of Poisson traffic. Every
                request must be served, the page-pool audit must be clean
                and tokens must have been produced.
  B  reference  The engine that served A prefills a few seeded prompts and
                decodes greedily. Its logits must match ``M.forward`` over
                the served tokens (teacher-forced) at matmul precision
                "highest", within LOGIT_ATOL.
  C  kernels    B again on an engine whose attention runs the Pallas paged
                decode kernel and whose prefill is chunked through the flash
                chunk kernel, both compiled for the chip (checked in the
                compiled HLO). Compared with the reference and with B.

Four chips (``--four-chips``): yi-6b, whose f32 weights (~24 GB) no single
chip holds, served through the same entry point over a (data=1, model=4)
mesh; each chip's memory is printed and checked against its share, and the
served engine is compared with the reference forward on the same mesh. No
other phase runs.

Everything runs in this one process: a TPU belongs to the process that
first touched JAX. Before any work the script checks that JAX's default
backend is a TPU and exits non-zero if not; it never falls back to the CPU.
A failed phase makes the exit code non-zero; on success the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``. The
lines before it are informational, not a benchmark.

The persistent compilation cache is wherever JAX_COMPILATION_CACHE_DIR
points, and otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# f32 matmuls on the TPU run at DEFAULT precision: one bf16 pass (operands
# rounded to 8 mantissa bits, f32 accumulation); the reference runs at
# "highest" (full f32). Emulating that rounding on the CPU through 32
# layers of this model family at d_model 192, 384 and 576 moved logits by
# at most 0.077-0.086 (mean 0.010) with a logit std of 1.0, and the drift
# did not grow with width. The bounds leave about 3x room. A wrong page,
# position or head mixes in unrelated activations and moves logits by
# O(std) = O(1).
LOGIT_ATOL = 0.25
MEAN_ATOL = 0.03

N_PROMPTS = 3
DECODE_STEPS = 16
CHUNK = 32


class CheckFailed(Exception):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


class CompileStats:
    """Backend compiles (and persistent-cache hits) seen by this process,
    from JAX's monitoring events."""

    def __init__(self, jax):
        self.count, self.secs, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles={self.count} compile_s={self.secs:.1f} "
                f"persistent_cache_hits={self.cache_hits}")


def seeded_tasks(seed: int, max_seq: int):
    """N_PROMPTS tasks with seeded prompt lengths that fit the engine's
    prompt cap (max_seq // 2) with DECODE_STEPS tokens to spare."""
    from repro.core.task import qa_task
    rng = np.random.default_rng(seed)
    lens = rng.integers(9, max_seq // 2, size=N_PROMPTS)
    return [qa_task(prompt_len=int(n), output_len=DECODE_STEPS + 1)
            for n in lens]


def engine_rows(ex, tasks, chunk=None):
    """Prefill every task (atomically, or in ``chunk``-token pieces), then
    decode them together for DECODE_STEPS greedy steps. Returns per task
    (prompt tokens, served token stream, logits rows [DECODE_STEPS+1, V]):
    row 0 comes from the prefill, row j from decode step j. The tasks are
    released afterwards and the pool audited."""
    prompts, streams, rows = {}, {}, {}
    for t in tasks:
        tid = t.task_id
        if chunk is None:
            ex.prefill(t)
        else:
            while not ex.prefill_chunk(t, chunk)[1]:
                pass
        prompts[tid] = np.asarray(ex._task_tokens(t)[0])
        streams[tid] = [ex.last_tok[tid]]
        rows[tid] = [np.asarray(ex.last_prefill_logits)[0]]
    for _ in range(DECODE_STEPS):
        ex.decode(tasks)
        logits = np.asarray(ex.last_logits)
        for r, t in enumerate(tasks):
            streams[t.task_id].append(ex.last_tok[t.task_id])
            rows[t.task_id].append(logits[r])
    for t in tasks:
        ex.release(t)
    ex.pool.check()
    require(ex.pool.used_pages == 0,
            f"{ex.pool.used_pages} pages still held after release")
    return {t.task_id: (prompts[t.task_id], streams[t.task_id],
                        np.stack(rows[t.task_id])) for t in tasks}


def reference_rows(jax, cfg, params, served, mesh=None):
    """Teacher-forced reference: ``M.forward`` at matmul precision
    "highest" over each prompt followed by its served tokens, all rows in
    one padded batch (the model is causal, so the zero tail is inert).
    Returns the logits at the positions the engine predicted from."""
    from repro.models import model as M
    seqs = {tid: np.concatenate([p, np.asarray(s[:-1], p.dtype)])
            for tid, (p, s, _) in served.items()}
    T = max(len(s) for s in seqs.values())
    toks = np.zeros((len(seqs), T), np.int32)
    for r, s in enumerate(seqs.values()):
        toks[r, : len(s)] = s
    fwd = jax.jit(lambda p, t: M.forward(cfg, p, t)[0])
    with jax.default_matmul_precision("highest"):
        if mesh is None:
            logits = np.asarray(fwd(params, toks))
        else:
            from repro.launch.mesh import batch_axes
            from repro.models.partitioning import activation_partitioning
            with mesh, activation_partitioning(batch_axes(mesh), "model"):
                logits = np.asarray(fwd(params, toks))
    out = {}
    for r, (tid, (prompt, stream, _)) in enumerate(served.items()):
        L = len(prompt)
        out[tid] = logits[r, L - 1: L - 1 + len(stream)]
    return out


def compare(name: str, got: dict, want: dict) -> None:
    """Logits within LOGIT_ATOL/MEAN_ATOL; every greedy disagreement must
    sit where the reference's top-2 margin is inside the tolerance (a flip
    the allowed drift can cause). Prints max/mean |dlogit| and agreement."""
    diffs, agree, n, unexplained = [], 0, 0, []
    for tid, g in got.items():
        w = want[tid][: len(g)]
        g = g[: len(w)]
        diffs.append(np.abs(g - w))
        top2 = np.sort(w, -1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        same = g.argmax(-1) == w.argmax(-1)
        agree += int(same.sum())
        n += len(same)
        unexplained += [(tid, int(i)) for i in np.flatnonzero(
            ~same & (margin > 2 * LOGIT_ATOL))]
    d = np.concatenate([x.ravel() for x in diffs])
    print(f"  {name}: max|dlogit|={d.max():.6f} mean|dlogit|={d.mean():.6f} "
          f"greedy_agreement={agree}/{n} (atol {LOGIT_ATOL}, mean "
          f"{MEAN_ATOL})", flush=True)
    require(bool(np.isfinite(d).all()), f"{name}: non-finite logits")
    require(d.max() <= LOGIT_ATOL,
            f"{name}: max|dlogit| {d.max():.6f} > {LOGIT_ATOL}")
    require(d.mean() <= MEAN_ATOL,
            f"{name}: mean|dlogit| {d.mean():.6f} > {MEAN_ATOL}")
    require(not unexplained,
            f"{name}: greedy flips outside the tolerance at {unexplained}")


def serve(argv):
    """Phase A (and the four-chip serve): the serve CLI's own code path."""
    from repro.launch import serve as serve_mod
    run = serve_mod.main(argv)
    tasks = run.result.tasks
    all_ = run.summary["all"]
    n_tokens = sum(t.tokens_done for t in tasks)
    print(f"  served {sum(t.finished for t in tasks)}/{len(tasks)} requests, "
          f"{n_tokens} tokens; SLO attainment all={all_.slo:.1%} "
          f"realtime={run.summary['realtime'].slo:.1%} "
          f"non_realtime={run.summary['non_realtime'].slo:.1%}", flush=True)
    require(len(tasks) > 0, "the workload had no requests")
    require(not any(t.dropped for t in tasks),
            f"{sum(t.dropped for t in tasks)} requests dropped")
    require(all(t.finished for t in tasks), "not every request finished")
    require(n_tokens > 0, "no tokens produced")
    for ex in run.executors:
        ex.pool.check()
        require(ex.pool.used_pages == 0,
                f"{ex.pool.used_pages} pages leaked after serving")
    return run


def run_phase(name, failures, fn, *args):
    """Run one phase; a failure is printed with its traceback and recorded,
    and the remaining phases still run so one call reports them all."""
    print(f"phase {name}", flush=True)
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception:
        traceback.print_exc()
        failures.append(name)
        out = None
    status = "FAILED" if name in failures else "ok"
    print(f"phase {name}: {status} ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    return out


def one_chip(jax, seed: int, failures: list) -> None:
    argv = ["--arch", "smollm-360m", "--executor", "paged",
            "--scheduler", "slice", "--no-reduced", "--slots", "16",
            "--pages", "1024", "--page-size", "16", "--max-seq", "256",
            "--rate", "2", "--duration", "4", "--seed", str(seed)]
    run = run_phase("A serve", failures, serve, argv)
    if run is None:
        failures.append("B reference (needs A's engine)")
        failures.append("C kernels (needs A's engine)")
        return
    ex = run.executors[0]
    tasks = seeded_tasks(seed, ex.max_seq)

    def phase_b():
        served = engine_rows(ex, tasks)
        ref = reference_rows(jax, ex.cfg, ex.params, served)
        compare("engine vs reference", {k: v[2] for k, v in served.items()},
                ref)
        return served, ref

    b = run_phase("B reference", failures, phase_b)

    def phase_c():
        from repro.serving.executor import PagedJaxExecutor
        exk = PagedJaxExecutor(ex.cfg, params=ex.params, n_pages=64,
                               page_size=ex.page_size, max_seq=ex.max_seq,
                               seed=seed, max_batch=4, use_paged_kernel=True,
                               prefill_chunk_size=CHUNK)
        for what, compiled in (("decode", exk._step_jit[4]),
                               ("prefill chunk", exk._chunk_jit[CHUNK])):
            require("tpu_custom_call" in compiled.as_text(),
                    f"{what} step has no compiled Pallas kernel")
        served = engine_rows(exk, tasks, chunk=CHUNK)
        got = {k: v[2] for k, v in served.items()}
        ref = reference_rows(jax, exk.cfg, exk.params, served)
        compare("kernel engine vs reference", got, ref)
        if b is not None:
            # against the jnp gather path: teacher forcing differs after
            # the first token where the two streams part, so compare rows
            # up to and including it
            jnp_rows = {}
            for tid, (_, s, rows) in served.items():
                s_b = b[0][tid][1]
                k = next((i for i, (x, y) in enumerate(zip(s, s_b))
                          if x != y), len(s))
                jnp_rows[tid] = b[0][tid][2][: k + 1]
                got[tid] = rows[: k + 1]
            compare("kernel engine vs jnp engine", got, jnp_rows)

    run_phase("C kernels", failures, phase_c)


def four_chips(jax, seed: int, failures: list) -> None:
    argv = ["--arch", "yi-6b", "--executor", "paged", "--scheduler", "slice",
            "--no-reduced", "--mesh-shape", "1,4", "--slots", "4",
            "--pages", "512", "--page-size", "16", "--max-seq", "256",
            "--rate", "2", "--duration", "3", "--seed", str(seed)]
    run = run_phase("serve yi-6b over a (1,4) mesh", failures, serve, argv)
    if run is None:
        failures.append("mesh reference (needs the served engine)")
        return
    ex = run.executors[0]

    def memory():
        leaves = jax.tree.leaves((ex.params, ex.pages))
        param_bytes = sum(x.nbytes for x in jax.tree.leaves(ex.params))
        for d in ex.mesh.devices.ravel():
            share = sum(s.data.nbytes for x in leaves
                        for s in x.addressable_shards if s.device == d)
            st = d.memory_stats()
            require(st is not None, f"{d} reports no memory stats")
            used, peak = st["bytes_in_use"], st.get("peak_bytes_in_use", -1)
            print(f"  {d}: bytes_in_use={used} peak_bytes_in_use={peak} "
                  f"share(params+pages)={share} "
                  f"whole_model_params={param_bytes}", flush=True)
            require(share < param_bytes,
                    f"{d} holds the whole model ({share} bytes)")
            require(used <= share + (1 << 30),
                    f"{d}: {used} bytes in use > its share {share} + 1 GiB")

    run_phase("per-chip memory", failures, memory)

    def reference():
        tasks = seeded_tasks(seed, ex.max_seq)
        served = engine_rows(ex, tasks)
        ref = reference_rows(jax, ex.cfg, ex.params, served, mesh=ex.mesh)
        compare("mesh engine vs mesh reference",
                {k: v[2] for k, v in served.items()}, ref)

    run_phase("mesh reference", failures, reference)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path (yi-6b over a "
                         "(1,4) mesh) and its reference; needs 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not a "
              "TPU; this check runs only on the chip", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT / 'src' / 'repro'} is missing; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import configure_compile_cache
    configure_compile_cache(ROOT)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device['kind']} x{device['count']} "
          f"(jax {jax.__version__})", flush=True)
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, have {len(devs)}",
              file=sys.stderr)
        return 1
    stats = CompileStats(jax)
    failures: list = []
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(jax, args.seed, failures)
    else:
        one_chip(jax, args.seed, failures)
    print(f"{stats.line()} total_s={time.perf_counter() - t0:.1f}",
          flush=True)
    if failures:
        print(f"chip_smoke: FAILED phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
