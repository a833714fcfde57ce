"""Serving launcher: run the SLICE-scheduled engine for any --arch.

By default it serves the reduced config (2 layers, d<=256), which is what
the CPU backend can run (``JAX_PLATFORMS=cpu``, Pallas kernels in
interpret mode). ``--no-reduced`` serves the published widths; on a TPU
chip that is the normal way to run it (see chip_smoke.py at the repo root
for the one-chip and four-chip checks). ``--mesh-shape`` shards the paged
engine over a (data, model) mesh of the local devices.

The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
says; when that is unset, at ``<checkout>/.jax_cache``.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --scheduler slice --rate 1.0 --duration 30

  # full published width on one TPU chip, paged engine
  PYTHONPATH=src python -m repro.launch.serve --no-reduced \
      --executor paged --pages 1024 --slots 16

  # paged KV arena + memory-aware SLICE admission (DESIGN.md §3 adapt. #2):
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --pages 64 --page-size 16

  # chunked prefill (DESIGN.md §5): slice prompts into 32-token chunks
  # co-scheduled with decode under the Eq. 7 headroom budget
  PYTHONPATH=src python -m repro.launch.serve --prefill-chunk 32

  # prefix sharing (DESIGN.md §6): dedup shared system prompts in the
  # paged KV arena via the radix prefix cache
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --prefix-cache --shared-prefix-frac 0.7

  # host-offload KV swap (DESIGN.md §7): suspend low-utility residents to
  # host memory to admit realtime arrivals under page pressure
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --kv-swap --swap-bw-gbps 8

  # speculative decoding (DESIGN.md §8): a tiny draft model proposes
  # per-request windows the target verifies in one step — lagging
  # realtime requests get multiple tokens per iteration
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --spec-decode --spec-depth 4 [--draft-config smollm-360m]

  # tensor-parallel sharded serving (DESIGN.md §9): partition weights and
  # the KV page arena over a (data, model) mesh — forced host CPU devices
  # here, real chips on TPU
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --mesh-shape 1,4

  # async pipelined engine (DESIGN.md §10): dispatch-ahead double
  # buffering — host scheduling and KV-swap I/O overlap device compute,
  # byte-identical streams to the synchronous reference
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --async-pipeline

  # fleet routing (DESIGN.md §11): N model tiers (small -> large) behind
  # one admission layer — tight-TPOT realtime traffic lands on the fast
  # tier, quality requests on the large one, with degraded down-tier
  # fallback and overflow spill between instances
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --fleet smollm-360m,edge-6b

  # observability (DESIGN.md §13): record the per-request lifecycle
  # stream and export a Perfetto/Chrome-trace timeline (one track per
  # instance, flow arrows per request). Composes with every flag above,
  # including --async-pipeline (spans are recorded at commit time, so
  # timestamps stay causal under dispatch-ahead)
  PYTHONPATH=src python -m repro.launch.serve --executor paged \
      --trace out.json
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

CHECKOUT = Path(__file__).resolve().parents[3]


@dataclasses.dataclass
class ServeRun:
    """What one ``main`` call served: the engines it built (one per fleet
    instance), the serving loop's result and ``metrics.summarize`` of its
    tasks."""
    executors: List[Any]
    result: Any
    summary: Dict[str, Any]


def configure_compile_cache(checkout: Path = CHECKOUT) -> None:
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless JAX_COMPILATION_CACHE_DIR is set, in which case JAX reads the
    variable itself and nothing is set here. The path is fixed so a later
    run in the same checkout finds what this one compiled. Entry points
    call this; importing the library never does."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(checkout) / ".jax_cache"))


def _make_trace(args):
    """TraceRecorder for --trace, or None (the zero-overhead default)."""
    if args.trace is None:
        return None
    from repro.serving.trace import TraceRecorder
    return TraceRecorder(capacity=1 << 20)


def _export_trace(tr, args, tasks, events) -> None:
    """Write the Perfetto JSON + print the observability summary line
    (events, SLO-violation attribution buckets)."""
    if tr is None:
        return
    from repro.serving.metrics import slo_attribution
    rows = tr.export_perfetto(args.trace)
    att = slo_attribution(tasks, events)
    buckets = {k: v for k, v in att["buckets"].items() if v}
    print(f"trace: {len(tr)} events ({tr.dropped} dropped) "
          f"-> {args.trace} ({rows} rows); "
          f"violations={att['violations']} attribution={buckets or '{}'}")


def _run_fleet(args):
    """--fleet path: one PagedJaxExecutor + SliceScheduler per arch under
    a single FleetRouter. With ONE arch this produces byte-identical
    streams to the single-model run_serving_loop path (same event order,
    same engines) — the degenerate config costs nothing."""
    from repro.configs import get_config
    from repro.core.schedulers import SliceScheduler
    from repro.data.workload import poisson_workload
    from repro.serving.executor import PagedJaxExecutor
    from repro.serving.fleet import FleetInstance, FleetRouter, run_fleet_loop
    from repro.serving.metrics import per_tier, summarize

    archs = [a.strip() for a in args.fleet.split(",") if a.strip()]
    if not archs:
        raise SystemExit("--fleet wants a comma-separated arch list ordered "
                         "small -> large, e.g. smollm-360m,edge-6b")
    n_pages = args.pages or (args.slots * args.max_seq) // args.page_size
    insts = []
    for tier, arch in enumerate(archs):
        cfg = get_config(arch)
        if args.reduced:
            cfg = cfg.reduced()
        if cfg.is_encoder_only:
            raise SystemExit(f"{arch} is encoder-only: no decode serving "
                             "(DESIGN.md §4)")
        if args.prefill_chunk is not None and (not cfg.has_attention
                                               or cfg.has_ssm):
            raise SystemExit(f"{arch}: executor-level chunked prefill needs "
                             "a pure-attention arch — SSM/hybrid archs "
                             "serve with atomic prefill (DESIGN.md §12)")
        draft_cfg = None
        if args.spec_decode and args.draft_config is not None:
            from repro.serving.spec_decode import draft_config_from_registry
            draft_cfg = draft_config_from_registry(args.draft_config, cfg)
        ex = PagedJaxExecutor(cfg, n_pages=n_pages, page_size=args.page_size,
                              max_seq=args.max_seq, seed=args.seed,
                              max_batch=args.slots,
                              use_paged_kernel=args.paged_kernel,
                              prefill_chunk_size=args.prefill_chunk,
                              prefix_cache=args.prefix_cache,
                              spec_decode=args.spec_decode,
                              draft_cfg=draft_cfg,
                              max_spec_depth=args.spec_depth,
                              async_dispatch=args.async_pipeline)
        budget = ex.page_budget()
        lat = ex.latency_model()
        lat.swap_bw_gbps = args.swap_bw_gbps
        prefix_hint = ex.cached_prompt_tokens if args.prefix_cache else None
        sched = SliceScheduler(lat, page_budget=budget,
                               prefill_chunk=args.prefill_chunk,
                               prefix_hint=prefix_hint,
                               kv_swap=args.kv_swap,
                               spec_decode=args.spec_decode,
                               max_spec_depth=args.spec_depth)
        print(f"fleet[{tier}] {cfg.name}: l(1)={lat.decode_ms(1):.2f}ms "
              f"l({args.slots})={lat.decode_ms(args.slots):.2f}ms")
        insts.append(FleetInstance(name=arch, tier=tier, scheduler=sched,
                                   executor=ex, lat=lat, page_budget=budget,
                                   quality=(tier + 1) / len(archs)))
    router = FleetRouter(insts)
    # scale the paper's workload SLOs to the SLOWEST instance so quality-
    # tier requests are achievable on the model that must serve them; with
    # one arch this is exactly the single-model path's scaling
    scale = max(max(i.lat.decode_ms(max(2, args.slots // 2))
                    for i in insts) / 50.0, 0.02)
    tasks = poisson_workload(args.rate, args.duration,
                             realtime_frac=args.ratio,
                             seed=args.seed, rt_output_len=8,
                             voice_output_len=24, qa_output_len=32,
                             shared_prefix_frac=args.shared_prefix_frac,
                             prefix_len_range=(args.max_seq // 8,
                                               args.max_seq // 4))
    top = len(archs) - 1
    for t in tasks:
        t.slo.tpot_ms *= scale
        t.slo.ttft_ms *= max(scale, 1.0)
        if t.slo.deadline_ms:
            t.slo.deadline_ms *= max(scale, 1.0)
        t.prompt_len = min(t.prompt_len, args.max_seq // 4)
        t.prefix_len = min(t.prefix_len, t.prompt_len)
        t.output_len = min(t.output_len, args.max_seq // 2)
        if top > 0 and t.kind == "qa":
            t.min_tier = top           # quality tier: wants the big model
    tr = _make_trace(args)
    res = run_fleet_loop(router, tasks, max_ms=3e7, trace=tr)
    s = summarize(res.tasks)
    print(f"fleet({','.join(archs)}): n={s['all'].n} SLO={s['all'].slo:.1%} "
          f"RT={s['realtime'].slo:.1%} nRT={s['non_realtime'].slo:.1%} "
          f"spills={res.spills} degraded={res.degraded} "
          f"defers={dict(res.merged.defers_by_reason)} "
          f"drops={dict(res.merged.drops_by_reason)}")
    for name, a in per_tier(res.tasks).items():
        print(f"  {name}: served={a.n} "
              f"admitted={res.admissions.get(name, 0)} SLO={a.slo:.1%}")
    if tr is not None:
        _export_trace(tr, args, res.tasks, tr.events)
    return ServeRun([i.executor for i in insts], res, s)


def main(argv: Optional[Sequence[str]] = None) -> ServeRun:
    """Parse ``argv`` (default: the command line), build the engine(s),
    serve a Poisson workload through the chosen scheduler and print the
    summary. Returns what was served, so callers in the same process
    (chip_smoke.py) can check it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--scheduler", default="slice",
                    choices=["slice", "orca", "fastserve"])
    ap.add_argument("--executor", default="slot", choices=["slot", "paged"])
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--ratio", type=float, default=0.5)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--pages", type=int, default=None,
                    help="paged executor: KV pool size in pages (default: "
                         "the slot arena's bytes, slots*max_seq/page_size)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged executor: tokens per page")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged executor: use the Pallas scalar-prefetch "
                         "kernel instead of the jnp gather")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (SLICE only): max prompt tokens "
                         "per chunk, interleaved with decode columns under "
                         "the Eq. 7 headroom budget (default: atomic)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged executor: radix prefix cache — tasks with a "
                         "common page-aligned prompt prefix share physical "
                         "KV pages (DESIGN.md §6)")
    ap.add_argument("--kv-swap", action="store_true",
                    help="paged executor: host-offload KV swap (DESIGN.md "
                         "§7) — SLICE suspends low-utility residents (and "
                         "FastServe its demoted queues) to host memory to "
                         "admit arrivals under page pressure")
    ap.add_argument("--swap-bw-gbps", type=float, default=8.0,
                    help="device<->host link bandwidth pricing swap "
                         "transfers in the scheduler's resume headroom")
    ap.add_argument("--spec-decode", action="store_true",
                    help="paged executor + SLICE: speculative decoding "
                         "(DESIGN.md §8) — a draft model proposes per-"
                         "request token windows, the target verifies them "
                         "in one step, lagging realtime requests commit "
                         "multiple tokens per iteration")
    ap.add_argument("--spec-depth", type=int, default=4,
                    help="max speculation depth (draft tokens per verify "
                         "window)")
    ap.add_argument("--draft-config", default=None,
                    help="registry arch for the draft model (reduced, "
                         "reshaped to the target vocab); default: the "
                         "target architecture cut to one layer")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="fraction of workload tasks opening with a shared "
                         "system prompt from a per-seed prefix pool")
    ap.add_argument("--async-pipeline", action="store_true",
                    help="paged executor: dispatch-ahead pipelining "
                         "(DESIGN.md §10) — decode cycles are enqueued "
                         "without blocking on device results; sampling "
                         "and bookkeeping land at commit time, KV-swap "
                         "transfers overlap decode on a background "
                         "worker. Streams and metrics stay byte-"
                         "identical to the synchronous engine")
    ap.add_argument("--fleet", default=None,
                    help="comma-separated registry archs ordered small -> "
                         "large: run one paged SLICE instance per arch "
                         "behind a single routing/admission layer "
                         "(DESIGN.md §11). A single-arch fleet is byte-"
                         "identical to the plain single-model path")
    ap.add_argument("--mesh-shape", default=None,
                    help="paged executor: 'data,model' serving mesh, e.g. "
                         "1,4 — shards weights + the KV page arena over "
                         "the model axis (DESIGN.md §9). On CPU the device "
                         "count is forced via XLA_FLAGS automatically")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the per-request lifecycle stream "
                         "(DESIGN.md §13) on the loop clock and write a "
                         "Perfetto/Chrome-trace JSON timeline here — open "
                         "in ui.perfetto.dev or chrome://tracing. Its rows "
                         "come from the same span call that, while a "
                         "jax.profiler session runs, also marks the "
                         "program's phases (slice/step, slice/decode/"
                         "sync, ...) on the profiler's clock. Composes "
                         "with every mode incl. --fleet and "
                         "--async-pipeline (commit-time spans)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced (CPU-feasible) config; "
                         "--no-reduced serves the published widths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    mesh_shape = None
    if args.mesh_shape is not None:
        try:
            mesh_shape = tuple(int(x) for x in args.mesh_shape.split(","))
            assert len(mesh_shape) == 2 and min(mesh_shape) >= 1
        except (ValueError, AssertionError):
            raise SystemExit("--mesh-shape wants 'data,model', e.g. 1,4")
        if args.executor != "paged":
            raise SystemExit("--mesh-shape requires --executor paged "
                             "(the slot engine has no sharded arena)")
        if args.paged_kernel:
            raise SystemExit("--mesh-shape shards the jnp attention path "
                             "via GSPMD; --paged-kernel needs a shard_map "
                             "wrapper (not implemented)")
        # must happen before the heavy imports below first-init jax
        n = mesh_shape[0] * mesh_shape[1]
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={n}").strip()

    configure_compile_cache()
    if args.fleet is not None:
        if args.executor != "paged":
            raise SystemExit("--fleet requires --executor paged (every "
                             "instance is a paged SLICE engine)")
        if args.scheduler != "slice":
            raise SystemExit("--fleet routes onto per-instance SLICE "
                             "schedulers; Orca/FastServe fleets are not "
                             "a thing here")
        if mesh_shape is not None:
            raise SystemExit("--fleet with --mesh-shape is not supported "
                             "(one XLA device pool per process)")
        return _run_fleet(args)

    from repro.configs import get_config
    from repro.core.schedulers import (FastServeScheduler, OrcaScheduler,
                                       SliceScheduler)
    from repro.data.workload import poisson_workload
    from repro.serving.executor import JaxExecutor, PagedJaxExecutor
    from repro.serving.loop import run_serving_loop
    from repro.serving.metrics import summarize

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving "
                         "(DESIGN.md §4)")
    if args.prefill_chunk is not None and args.scheduler != "slice":
        raise SystemExit("--prefill-chunk requires --scheduler slice "
                         "(Orca/FastServe are atomic-prefill baselines)")
    if args.prefill_chunk is not None and (not cfg.has_attention or cfg.has_ssm):
        raise SystemExit(f"{args.arch}: executor-level chunked prefill needs "
                         "a pure-attention arch — SSM/hybrid archs serve "
                         "with atomic prefill (DESIGN.md §12)")
    if cfg.has_ssm and args.executor == "paged" and (
            args.spec_decode or args.prefix_cache or mesh_shape is not None):
        raise SystemExit(f"{args.arch}: spec-decode/prefix-cache/mesh need "
                         "rewindable/sharable per-token KV; the recurrent "
                         "state kind has none (DESIGN.md §12)")
    if args.prefix_cache and args.executor != "paged":
        raise SystemExit("--prefix-cache requires --executor paged "
                         "(sharing rides on the refcounted page pool)")
    if args.kv_swap and args.executor != "paged":
        raise SystemExit("--kv-swap requires --executor paged "
                         "(the slot arena has no page pool to swap from)")
    if args.kv_swap and args.scheduler == "orca":
        raise SystemExit("--kv-swap requires --scheduler slice or fastserve "
                         "(Orca has no preemption policy)")
    if args.spec_decode and args.executor != "paged":
        raise SystemExit("--spec-decode requires --executor paged "
                         "(the verify window rides the paged KV arena)")
    if args.spec_decode and args.scheduler != "slice":
        raise SystemExit("--spec-decode requires --scheduler slice "
                         "(depth grants come from the Eq. 7 headroom)")
    if args.async_pipeline and args.executor != "paged":
        raise SystemExit("--async-pipeline requires --executor paged "
                         "(the dispatch queue rides the paged engine)")
    page_budget = None
    prefix_hint = None
    n_pages = args.pages or (args.slots * args.max_seq) // args.page_size
    mesh = None
    if mesh_shape is not None:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(data=mesh_shape[0], model=mesh_shape[1])
    if args.executor == "paged":
        draft_cfg = None
        if args.spec_decode and args.draft_config is not None:
            from repro.serving.spec_decode import draft_config_from_registry
            draft_cfg = draft_config_from_registry(args.draft_config, cfg)
        ex = PagedJaxExecutor(cfg, n_pages=n_pages,
                              page_size=args.page_size,
                              max_seq=args.max_seq, seed=args.seed,
                              max_batch=args.slots,
                              use_paged_kernel=args.paged_kernel,
                              prefill_chunk_size=args.prefill_chunk,
                              prefix_cache=args.prefix_cache,
                              spec_decode=args.spec_decode,
                              draft_cfg=draft_cfg,
                              max_spec_depth=args.spec_depth,
                              mesh=mesh,
                              async_dispatch=args.async_pipeline)
        page_budget = ex.page_budget()
        if args.prefix_cache:
            prefix_hint = ex.cached_prompt_tokens
    else:
        ex = JaxExecutor(cfg, max_slots=args.slots, max_seq=args.max_seq,
                         seed=args.seed,
                         prefill_chunk_size=args.prefill_chunk)
    lat = ex.latency_model()
    lat.swap_bw_gbps = args.swap_bw_gbps
    print(f"engine {cfg.name} ({args.executor}): l(1)={lat.decode_ms(1):.2f}ms "
          f"l({args.slots})={lat.decode_ms(args.slots):.2f}ms")
    # scale the paper's workload SLOs to this engine's speed
    scale = max(lat.decode_ms(max(2, args.slots // 2)) / 50.0, 0.02)
    tasks = poisson_workload(args.rate, args.duration, realtime_frac=args.ratio,
                             seed=args.seed, rt_output_len=8,
                             voice_output_len=24, qa_output_len=32,
                             shared_prefix_frac=args.shared_prefix_frac,
                             prefix_len_range=(args.max_seq // 8,
                                               args.max_seq // 4))
    for t in tasks:
        t.slo.tpot_ms *= scale
        t.slo.ttft_ms *= max(scale, 1.0)
        if t.slo.deadline_ms:
            t.slo.deadline_ms *= max(scale, 1.0)
        t.prompt_len = min(t.prompt_len, args.max_seq // 4)
        t.prefix_len = min(t.prefix_len, t.prompt_len)
        # keep every task inside the engine's per-task cap: the paged engine
        # would otherwise drop it as statically infeasible (and the slot
        # engine would silently ring-wrap past max_seq)
        t.output_len = min(t.output_len, args.max_seq // 2)
    # Orca/FastServe have no memory model — cap their batch so worst-case
    # residency (prompt cap + output cap per task) fits the engine; only
    # SLICE gets the live page-budget admission. With --kv-swap, FastServe
    # gains its own page budget (peak-reservation admission + proactive
    # swap), so the worst-case cap would only mask the pressure it manages.
    baseline_batch = args.slots
    if args.executor == "paged" and not (args.kv_swap
                                         and args.scheduler == "fastserve"):
        peak = args.max_seq // 4 + args.max_seq // 2
        baseline_batch = max(1, min(args.slots,
                                    (n_pages * args.page_size) // peak))
    sched = {"slice": lambda: SliceScheduler(lat, page_budget=page_budget,
                                             prefill_chunk=args.prefill_chunk,
                                             prefix_hint=prefix_hint,
                                             kv_swap=args.kv_swap,
                                             spec_decode=args.spec_decode,
                                             max_spec_depth=args.spec_depth),
             "orca": lambda: OrcaScheduler(max_batch=baseline_batch),
             "fastserve": lambda: FastServeScheduler(
                 max_batch=baseline_batch,
                 page_budget=page_budget if args.kv_swap else None,
                 kv_swap=args.kv_swap),
             }[args.scheduler]()
    tr = _make_trace(args)
    res = run_serving_loop(sched, ex, tasks, max_ms=3e7, trace=tr)
    s = summarize(res.tasks)
    swap_note = (f" suspends={res.suspends} resumes={res.resumes} "
                 f"swapped={res.swapped_bytes / 1e6:.1f}MB"
                 if args.kv_swap else "")
    spec_note = (f" spec_extra={res.spec_extra_tokens} "
                 f"accepted={res.accepted_tokens}/{res.drafted_tokens}"
                 if args.spec_decode else "")
    pipe_note = (f" host_gap={res.dispatch_ms + res.wait_ms:.1f}ms "
                 f"(dispatch={res.dispatch_ms:.1f} wait={res.wait_ms:.1f} "
                 f"swap_overlap={res.swap_overlap_ms:.1f}) "
                 f"stalls={res.pipeline_stalls}"
                 if args.async_pipeline else "")
    print(f"{args.scheduler}: n={s['all'].n} SLO={s['all'].slo:.1%} "
          f"RT={s['realtime'].slo:.1%} nRT={s['non_realtime'].slo:.1%} "
          f"decode_iters={res.decode_iterations} "
          f"prefill_chunks={res.prefill_chunks} "
          f"defers={dict(res.defers_by_reason)} "
          f"drops={dict(res.drops_by_reason)}"
          f"{swap_note}{spec_note}{pipe_note}")
    if tr is not None:
        _export_trace(tr, args, res.tasks, tr.events)
    return ServeRun([ex], res, s)


if __name__ == "__main__":
    main()
