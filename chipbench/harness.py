"""One run of one cell: build it from its files, warm up, serve the window
on the wall clock, follow every request to its end, and check what was
served against the plain reference.

The entry the window drives is the program's own serving loop,
``repro.serving.loop.InstanceDriver`` (``deliver`` + ``step``), over a
``SliceScheduler`` and a ``PagedJaxExecutor`` built as ``launch/serve.py``
builds them, with the sizes the configuration file names and every other
program default left alone. What the harness adds is the clock: before
each step it sets the driver's clock from the wall clock, when the
scheduler is idle it sleeps until the next request is due, and it stamps
every token with the wall clock read after the step that produced it.
Requests are timed from their due times (an open loop).
"""
from __future__ import annotations

import gc
import importlib
import math
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import stats
import traffic

# HF configuration key -> the program's ArchConfig attribute
ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tied_embeddings"}
WARM_ID_BASE = 9_000_000
TRACE_START = 0.4          # share of the window before the trace starts
TRACE_LEAD_MS = 200.0      # ... counted back from the next request due
TRACE_SECONDS = 10.0
REF_ROWS = 4               # reference rows per call


def chunk_pieces(n: int, chunk: int) -> List[int]:
    """How the executor splits an n-token prefill request: whole chunks,
    then a power-of-two decomposition of the rest (its compiled buckets)."""
    out = []
    while n > 0:
        p = chunk if n >= chunk else 1 << (n.bit_length() - 1)
        out.append(p)
        n -= p
    return out


def prompt_tokens(seed: int, task_id: int, vocab: int, length: int):
    """The prompt the program serves for a request: the program draws it
    from (seed, task id), and the harness draws the same stream to hand
    the reference its input."""
    rng = np.random.default_rng((seed + 1) * 100_003 + task_id)
    return rng.integers(0, vocab, (1, length))[0]


class CompileCounter:
    """Backend compile events and persistent-cache hits seen by this
    process. JAX reports a compile event for a program read from the
    cache too, so compiles proper are the difference."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Recorder:
    """Host annotations around the program's calls, named ``<kind>.<n>``,
    so that a profiler trace can attribute device operations to steps and
    idle gaps to host activity. With ``detail`` on it also keeps each
    step's shapes for the operation counts."""

    def __init__(self):
        self.n = 0
        self.detail = False
        self.calls: Dict[int, tuple] = {}

    def annotate(self, kind: str):
        from jax.profiler import TraceAnnotation
        i = self.n
        self.n += 1
        return i, TraceAnnotation(f"{kind}.{i}")

    def wrap(self, obj, meth: str, kind: str,
             describe: Optional[Callable] = None) -> None:
        fn = getattr(obj, meth)

        def wrapped(*args, **kw):
            i, ann = self.annotate(kind)
            if self.detail and describe is not None:
                self.calls[i] = (kind, describe(*args, **kw))
            with ann:
                return fn(*args, **kw)

        setattr(obj, meth, wrapped)


@dataclass
class Built:
    cfg: object
    arch: object                 # references.<ref>.Arch
    ref: object                  # the reference module
    eng: Dict
    params: object
    mesh: object
    ex: object
    sched: object
    drv: object
    rec: Recorder
    served: Dict[int, List[int]] = field(default_factory=dict)


def arch_config(config: Dict, rehearse: bool) -> Dict:
    return config["rehearsal"]["arch"] if rehearse else config


def program_config(config: Dict, rehearse: bool):
    """The program's registry config, held to the configuration file's
    numbers: a registry entry that drifts from the file stops the run."""
    from repro.configs import get_config
    cfg = get_config(config["registry_arch"])
    if rehearse:
        cfg = cfg.reduced()
    a = arch_config(config, rehearse)
    bad = {k: (a[k], getattr(cfg, attr)) for k, attr in ARCH_KEYS.items()
           if a[k] != getattr(cfg, attr)}
    if bad or cfg.block_kind != "dense" or not cfg.causal:
        raise SystemExit(f"registry {config['registry_arch']} differs from "
                         f"the configuration file: {bad}")
    import jax
    if jax.config.jax_default_matmul_precision not in (
            None, config["matmul_precision"]):
        raise SystemExit("JAX's default matmul precision is "
                         f"{jax.config.jax_default_matmul_precision!r}; the "
                         f"configuration states {config['matmul_precision']!r}")
    return cfg


def build(cell, seed: int, rehearse: bool, fault=None) -> Built:
    import jax
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import PagedJaxExecutor
    from repro.serving.loop import InstanceDriver

    config = cell.config
    eng = config["rehearsal"]["engine"] if rehearse else config["engine"]
    cfg = program_config(config, rehearse)
    ref = importlib.import_module(f"references.{config['reference']}")
    arch = ref.Arch.from_config(arch_config(config, rehearse))
    mesh, shardings = None, None
    if eng["mesh"]:
        from repro.launch import sharding as rules
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(data=eng["mesh"][0], model=eng["mesh"][1])
        shardings = rules.to_shardings(
            mesh, rules.param_specs(cfg, mesh, train=False))
    params = ref.init_weights(arch, seed, shardings)
    jax.block_until_ready(params)
    served_params = params
    if fault is not None and hasattr(fault, "on_params"):
        served_params = fault.on_params(params, arch)
    ex = PagedJaxExecutor(cfg, params=served_params, n_pages=eng["pages"],
                          page_size=eng["page_size"], max_seq=eng["max_seq"],
                          seed=seed, max_batch=eng["slots"],
                          use_paged_kernel=eng["paged_kernel"],
                          prefill_chunk_size=eng["prefill_chunk"], mesh=mesh)
    if fault is not None and hasattr(fault, "on_executor"):
        fault.on_executor(ex)
    budget = ex.page_budget()
    lat = ex.latency_model()
    lat.swap_bw_gbps = 8.0          # serve.py's default; no swap here
    sched = SliceScheduler(lat, page_budget=budget,
                           prefill_chunk=eng["prefill_chunk"])
    print(f"engine: l(1)={lat.decode_ms(1):.2f}ms "
          f"l({eng['slots']})={lat.decode_ms(eng['slots']):.2f}ms "
          "(the program's own probe, host clock)", flush=True)
    rec = Recorder()
    b = Built(cfg=cfg, arch=arch, ref=ref, eng=eng, params=params, mesh=mesh,
              ex=ex, sched=sched, drv=InstanceDriver(sched, ex), rec=rec)
    chunk = eng["prefill_chunk"]
    cap = eng["max_seq"] // 2

    def describe_decode(tasks, depths=None):
        return [ex.pool.length(t.task_id) + 1 for t in tasks]

    def describe_chunk(task, n_tokens):
        start = ex.prompt_progress(task)
        n = min(n_tokens, min(task.prompt_len, cap) - start)
        out = []
        for c in chunk_pieces(n, chunk):
            out.append((start, c))
            start += c
        return out

    def describe_prefill(task):
        return [(0, min(task.prompt_len, cap))]

    rec.wrap(sched, "next_action", "schedule")
    rec.wrap(ex, "decode", "decode", describe_decode)
    rec.wrap(ex, "prefill_chunk", "prefill_chunk", describe_chunk)
    rec.wrap(ex, "prefill", "prefill", describe_prefill)
    release = ex.release

    def release_and_keep(task):
        if task.task_id >= traffic.TASK_ID_BASE and \
                task.task_id not in b.served:
            b.served[task.task_id] = list(ex.generated_tokens(task))
        return release(task)

    ex.release = release_and_keep
    return b


def warm_up(b: Built, mix: Dict, clock) -> None:
    """Compile (or read from the cache) everything the window will run:
    a prompt of 2*chunk-1 tokens walks every chunk bucket, one decode at
    every batch bucket, then one short request of each class through the
    driver on the wall clock, which also runs the eager operations of the
    serving path once."""
    from repro.core.task import qa_task
    ex, eng = b.ex, b.eng
    chunk, slots = eng["prefill_chunk"], eng["slots"]
    wid = WARM_ID_BASE
    tasks = []
    for j in range(slots):
        t = qa_task(prompt_len=(2 * chunk - 1) if j == 0 else 8,
                    output_len=2)
        t.task_id = wid
        wid += 1
        while not ex.prefill_chunk(t, chunk)[1]:
            pass
        tasks.append(t)
    bucket = 1
    while True:
        ex.decode(tasks[:bucket])
        if bucket >= slots:
            break
        bucket = min(2 * bucket, slots)
    for t in tasks:
        ex.release(t)
    specs = []
    for k, cls in enumerate(mix["classes"]):
        lo, hi = cls["prompt"]
        specs.append({"index": k, "kind": cls["kind"], "due_ms": 0.0,
                      "prompt_len": hi - 1,
                      "output_len": min(mix["warmup_output_cap"],
                                        cls["output_mean"]), "cls": cls})
    t0 = clock()
    burst = [traffic.make_task(s, wid + s["index"], t0) for s in specs]
    serve(b, burst, clock, deadline_ms=t0 + 120_000.0)
    ex.pool.check()


class TraceWindow:
    """The profiler runs from before the window opens (``open``, counted in
    set-up, since starting it takes seconds) until serving has ended
    (``close``); the metrics read only the stretch from ``start_ms`` into
    the window for ``seconds``, which a ``window.<n>`` annotation marks.
    So neither the profiler's start nor its stop lands inside the window.
    Python calls are not traced."""

    def __init__(self, b: Built, trace_dir: Path, start_ms: float,
                 seconds: float):
        self.b, self.dir = b, trace_dir
        self.start_ms, self.stop_ms = start_ms, start_ms + seconds * 1000.0
        self.ann = None
        self.marked = False

    def open(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def tick(self, rel_ms: float) -> None:
        if self.ann is None and not self.marked and rel_ms >= self.start_ms:
            _, self.ann = self.b.rec.annotate("window")
            self.ann.__enter__()
        elif self.ann is not None and rel_ms >= self.stop_ms:
            self.unmark()

    def unmark(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
            self.marked = True

    def close(self) -> None:
        import jax
        self.unmark()
        jax.profiler.stop_trace()


def serve(b: Built, tasks: List, clock, deadline_ms: float,
          tw: Optional[TraceWindow] = None, t_w: float = 0.0):
    """Offer ``tasks`` at their due times (``arrival_ms``) and serve until
    every task has been sent and has finished or failed, or until
    ``deadline_ms`` passes.
    Returns (delivery lateness per task in ms, actions run)."""
    drv = b.drv
    i, n = 0, len(tasks)
    late: List[float] = []
    actions = 0
    while True:
        now = clock()
        while i < n and tasks[i].arrival_ms <= now:
            late.append(now - tasks[i].arrival_ms)
            drv.now = now
            drv.deliver(tasks[i])
            i += 1
        if tw is not None:
            tw.tick(now - t_w)
        if now >= deadline_ms:
            break
        drv.now = now
        before = [(t, len(t.token_times_ms), t.prefill_done_ms)
                  for t in drv.tracked]
        did = drv.step()
        after = clock()
        for t, k, p in before:
            tt = t.token_times_ms
            for j in range(k, len(tt)):
                tt[j] = after
            if p is None and t.prefill_done_ms is not None:
                t.prefill_done_ms = after
        if did:
            actions += 1
            continue
        if i == n:
            break                   # everything sent has finished or failed
        wait = tasks[i].arrival_ms - clock()
        if wait > 0:
            _, ann = b.rec.annotate("wait")
            with ann:
                time.sleep(wait / 1000.0)
    if tw is not None:
        tw.unmark()
    drv.drain()
    return late, actions


@dataclass
class Run:
    sent: List
    setup_s: float
    window_s: float
    seconds: float               # the window proper, before the drain
    window_start_ms: float       # on the clock the tokens are stamped with
    end_ms: float                # serving stopped (same clock)
    late_ms: List[float]
    compiles: int
    cache_hits: int
    actions: int
    schedule_ms: float
    memory_peak: int
    pages_held: int
    calls: Dict[int, tuple]
    trace_dir: Optional[Path]
    checks: Dict[str, Dict] = field(default_factory=dict)
    checked: tuple = (0, 0)          # requests, tokens
    reference_s: float = 0.0
    max_gap: float = math.inf        # widest gap: printed, not compared
    control: Dict[str, object] = field(default_factory=dict)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(cell, seed: int, seconds: float, trace_dir: Optional[Path],
             t_process: float, rehearse: bool = False, fault=None,
             control: bool = False) -> Run:
    import jax
    counter = CompileCounter()
    b = build(cell, seed, rehearse, fault)
    t_origin = time.perf_counter()

    def clock() -> float:
        return (time.perf_counter() - t_origin) * 1000.0

    mix = cell.mix
    rate = (cell.config["rehearsal"]["rate_per_s"] if rehearse
            else cell.rate_per_s)
    specs = traffic.schedule(mix, rate, seconds, seed)
    if rehearse:
        reh = cell.config["rehearsal"]
        for s in specs:
            s["output_len"] = min(s["output_len"], reh["output_cap"])
            s["prompt_len"] = min(s["prompt_len"], reh["prompt_cap"])
    warm_up(b, mix, clock)
    print(f"setup: compiles={counter.compiles - counter.cache_hits} "
          f"cache_reads={counter.cache_hits}", flush=True)
    devices = (list(b.mesh.devices.ravel()) if b.mesh is not None
               else jax.devices()[:1])
    b.rec.detail = trace_dir is not None
    tw = None
    if trace_dir is not None:
        # mark from just before a request is due, so that the traced
        # stretch holds its prefill as well as decode steps
        after = [s["due_ms"] for s in specs
                 if s["due_ms"] >= TRACE_START * seconds * 1000.0]
        start = (after[0] - TRACE_LEAD_MS) if after else \
            TRACE_START * seconds * 1000.0
        tw = TraceWindow(b, trace_dir, max(start, 0.0),
                         min(TRACE_SECONDS, 0.3 * seconds))
        tw.open()
    c0, h0 = counter.compiles, counter.cache_hits
    s0 = b.ex.gap_stats.schedule_ms
    t_w = clock()
    setup_s = time.perf_counter() - t_process
    tasks = [traffic.make_task(s, traffic.TASK_ID_BASE + s["index"],
                               t_w + s["due_ms"]) for s in specs]
    late, actions = serve(b, tasks, clock,
                          deadline_ms=t_w + seconds * 1000.0
                          + traffic.max_budget_ms(mix), tw=tw, t_w=t_w)
    end = clock()
    if tw is not None:
        tw.close()
    window_s = (end - t_w) / 1000.0
    run = Run(sent=tasks, setup_s=setup_s, window_s=window_s,
              seconds=seconds, window_start_ms=t_w, end_ms=end, late_ms=late,
              compiles=counter.compiles - c0,
              cache_hits=counter.cache_hits - h0, actions=actions,
              schedule_ms=b.ex.gap_stats.schedule_ms - s0,
              memory_peak=memory_peak(devices), pages_held=0,
              calls=dict(b.rec.calls), trace_dir=trace_dir)
    # requests still in flight when the drain ended hold their pages by
    # right: release them, and every page must then be free again
    b.drv.release_dropped()
    for t in b.drv.live_tasks():
        b.ex.release(t)
    b.ex.pool.check()
    run.pages_held = int(b.ex.pool.used_pages)
    reference_check(b, cell, run, seed, rehearse, control)
    return run


def sample_requests(run: Run, served: Dict, k: int, seed: int) -> List:
    """k finished requests drawn from the seed, the longest among them."""
    done = [t for t in run.sent
            if stats.finished(t) and len(served.get(t.task_id, ())) > 0]
    if not done:
        return []
    longest = max(done, key=lambda t: (t.output_len + t.prompt_len,
                                       -t.task_id))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_check(b: Built, cell, run: Run, seed: int, rehearse: bool,
                    control: bool) -> None:
    """Free the program's state, then run the reference over the sampled
    requests' prompts and served tokens, in blocks of rows, and fill
    ``run.checks`` with each compared number beside its limit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    t0 = time.perf_counter()
    eng, arch, ref = b.eng, b.arch, b.ref
    k = (cell.config["rehearsal"]["check_requests"] if rehearse
         else cell.mix["check_requests"])
    sample = sample_requests(run, b.served, k, seed)
    bad = sum(len(b.served.get(t.task_id, ())) != t.output_len
              for t in sample)
    served = {t.task_id: b.served[t.task_id] for t in sample}
    params, mesh = b.params, b.mesh
    b.drv = b.sched = b.ex = None
    b.served = {}
    gc.collect()
    T, cap = eng["max_seq"], eng["max_seq"] // 2
    toks = np.zeros((len(sample), T), np.int32)
    tgt = np.full((1, len(sample), T), -1, np.int32)
    for r, t in enumerate(sample):
        p = prompt_tokens(seed, t.task_id, arch.vocab, min(t.prompt_len, cap))
        s = np.asarray(served[t.task_id], np.int32)
        seq = np.concatenate([p, s[:-1]])
        toks[r, : len(seq)] = seq
        tgt[0, r, len(p) - 1: len(p) - 1 + len(s)] = s
    put = (lambda x: jnp.asarray(x)) if mesh is None else (
        lambda x: jax.device_put(x, NamedSharding(mesh, PartitionSpec())))
    gaps_fn = jax.jit(lambda w, x, y: ref.gaps(arch, w, x, y))
    names = list(ref.CONTROLS) if control else []
    lows = {n: jax.jit(ref.CONTROLS[n])(params) for n in names}
    low_fn = jax.jit(lambda w, x: ref.argmax_low(arch, w, x))
    got, ctl = [], {n: [] for n in names}
    for r0 in range(0, len(sample), REF_ROWS):
        x = toks[r0: r0 + REF_ROWS]
        y = tgt[:, r0: r0 + REF_ROWS]
        for n in names:
            am = np.asarray(low_fn(lows[n], put(x)))
            y = np.concatenate([y, np.where(y[:1] >= 0, am[None], -1)])
        g = np.asarray(gaps_fn(params, put(x), put(y)))
        valid = y[0] >= 0
        got.append(g[0][valid])
        for i, n in enumerate(names):
            ctl[n].append(g[1 + i][valid])
    got = np.concatenate(got) if got else np.zeros(0)
    lim = cell.config["rehearsal"]["limits"] if rehearse else cell.limits
    run.checked = (len(sample), int(got.size))
    run.reference_s = time.perf_counter() - t0
    run.checks = {
        "streams_short_or_long": {"value": int(bad), "limit": 0},
        "pages_held_after_drain": {"value": run.pages_held, "limit": 0},
        "mean_gap": {"value": float(got.mean()) if got.size else math.inf,
                     "limit": lim["mean_gap"]},
    }
    run.max_gap = float(got.max()) if got.size else math.inf
    for n in names:
        # the control in the program's place: the same comparison, with
        # the control's own first tokens where the program's were
        c = np.concatenate(ctl[n])
        checks = dict(run.checks, mean_gap={"value": float(c.mean()),
                                            "limit": lim["mean_gap"]})
        run.control[n] = {"correct": judge(checks),
                          "max_gap": float(c.max()),
                          "mean_gap": float(c.mean()),
                          "flips": int((c > 0).sum())}
    if names:
        run.control["program_flips"] = int((got > 0).sum())


def judge(checks: Dict[str, Dict]) -> bool:
    """Every compared number is finite and within its limit."""
    if not checks:
        return False
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def correct(run: Run) -> bool:
    return judge(run.checks)
