"""Observability spine (DESIGN.md §13): the trace contract as unit tests.

Four properties, mirrored at benchmark scale by the observability CI
gate (benchmarks/observability.py):

  * read-only    — the recorder never feeds back into scheduling: the
    same workload through the same engine, traced and untraced, yields
    identical policy counters and per-token timestamps (sim engines) and
    byte-identical greedy token streams (real JAX engine);
  * conservation — ``replay_counters`` over the event stream reproduces
    the LoopResult counters EXACTLY, on a full-featured engine loop and
    on a 2-instance fleet loop folded into the merged result;
  * bounded      — the ring drops (and counts) rows instead of growing,
    and the Perfetto export round-trips through ``json.load`` with
    per-track monotonically non-overlapping spans;
  * spans        — the program's ``slice/`` spans nest where the work
    happens under a profiler session, leave the dispatched and synced
    work unchanged, build no arguments without one, and every drop
    carries its reason and margin to both sinks.
"""
import glob
import json
import os
import tempfile

import pytest

from repro.core.latency_model import MeasuredLatencyModel, paper_fig1_model
from repro.core.schedulers import SliceScheduler
from repro.core.task import SLOSpec, control_task, qa_task
from repro.data.workload import poisson_workload
from repro.serving.executor import PagedSimExecutor
from repro.serving.fleet import SimTier, run_fleet_loop, sim_fleet
from repro.serving.loop import InstanceDriver, run_serving_loop
from repro.serving.metrics import ATTRIBUTION_BUCKETS, slo_attribution
from repro.serving.trace import (NO_SPAN, SPAN_KINDS, TraceRecorder,
                                 action_span, events_conserved, profiling,
                                 replay_counters, span)

LAT = paper_fig1_model()


def _tasks(seed=3, rate=2.0, duration_s=20.0):
    tasks = poisson_workload(rate_per_s=rate, duration_s=duration_s,
                             seed=seed, realtime_frac=0.4,
                             voice_output_len=64, qa_output_len=64)
    for i, t in enumerate(tasks):
        # pin ids: sim draft-acceptance streams seed from task_id, so the
        # traced/untraced runs must not depend on global counter state
        t.task_id = 10_000 * seed + i
    return tasks


def _engine_run(trace=None, chunk=32, seed=3):
    """Memory-starved SLICE run with every event source armed: kv_swap,
    spec decode, chunked prefill (``chunk=None`` = atomic, the regime
    where suspend/resume actually fire)."""
    ex = PagedSimExecutor(LAT, total_pages=48, page_size=16)
    sched = SliceScheduler(LAT, page_budget=ex.budget, kv_swap=True,
                           spec_decode=True, prefill_chunk=chunk,
                           drop_expired_realtime=False)
    return run_serving_loop(sched, ex, _tasks(seed=seed), trace=trace)


def _fingerprint(res):
    return (res.decode_iterations, res.prefills, res.prefill_chunks,
            res.suspends, res.resumes, res.spec_extra_tokens,
            res.swapped_bytes, dict(res.defers_by_reason),
            [(t.task_id, t.finished, t.tokens_done, t.ttft_ms,
              tuple(t.token_times_ms)) for t in res.tasks])


# ------------------------------------------------------------- read-only

def test_untraced_run_identical_to_traced():
    """Tracing must observe, never perturb: identical counters, defer
    ledger and per-token timestamps with the recorder on vs off."""
    tr = TraceRecorder()
    traced = _engine_run(trace=tr)
    plain = _engine_run(trace=None)
    assert len(tr) > 0
    assert _fingerprint(traced) == _fingerprint(plain)


def test_trace_events_readonly_payloads():
    """Event payloads are shared/interned dicts (defer reasons); mutating
    a consumer-side copy must be the consumer's bug, so the recorder
    hands out the SAME dict for every defer of one reason."""
    tr = TraceRecorder()
    _engine_run(trace=tr, chunk=None)
    defers = [e for e in tr.events if e.kind == "defer"]
    assert defers
    by_reason = {}
    for e in defers:
        assert e.args["reason"] in ("pages", "states", "time", "batch")
        prev = by_reason.setdefault(e.args["reason"], e.args)
        assert prev is e.args


def test_jax_engine_streams_identical_traced():
    """Real JAX engine: greedy token streams byte-identical traced vs
    untraced (the sim fingerprint proves counters; this proves tokens)."""
    from helpers import make_paged_engine, reduced_cfg

    def run(trace):
        ex = make_paged_engine(reduced_cfg(), seed=0)
        lat = ex.latency_model()     # probe tasks release before the hook
        sched = SliceScheduler(lat, page_budget=ex.page_budget())
        streams = {}
        orig_release = ex.release
        # snapshot each stream at release, before the engine drops it
        def release(task):
            streams[task.task_id] = tuple(ex.generated_tokens(task))
            orig_release(task)
        ex.release = release
        tasks = poisson_workload(rate_per_s=4.0, duration_s=2.0, seed=5)
        for i, t in enumerate(tasks):
            t.task_id = 500 + i
            t.slo.tpot_ms *= 50.0
            t.slo.ttft_ms *= 50.0
            t.prompt_len = min(t.prompt_len, 16)
            t.output_len = min(t.output_len, 8)
        run_serving_loop(sched, ex, tasks, trace=trace)
        assert streams and any(len(s) > 1 for s in streams.values())
        return streams

    assert run(TraceRecorder()) == run(None)


# ---------------------------------------------------------- conservation

def test_events_conserved_engine_loop():
    """Replaying the stream reproduces the LoopResult counters exactly,
    in both prefill regimes (chunked, and atomic where swap fires)."""
    for chunk in (32, None):
        tr = TraceRecorder()
        res = _engine_run(trace=tr, chunk=chunk)
        assert tr.dropped == 0
        assert events_conserved(tr.events, res)
    # the atomic-prefill regime must actually exercise suspend/resume,
    # or the swap half of the conservation check was vacuous
    assert res.suspends > 0 and res.resumes > 0
    kinds = {e.kind for e in tr.events}
    assert {"arrive", "admit", "defer", "decode", "suspend", "resume",
            "finish"} <= kinds


def test_events_conserved_fleet_loop():
    """2-instance fleet under one recorder: per-track streams fold into
    the MERGED LoopResult, and each track replays its own instance."""
    small = MeasuredLatencyModel(
        [(b, ms * 0.4) for b, ms in LAT._bs],
        prefill_samples=[(n, ms * 0.4) for n, ms in LAT._ps])
    router = sim_fleet([SimTier("small", 0, small, quality=0.8),
                        SimTier("large", 1, LAT, quality=1.0)],
                       total_pages=64)
    tasks = _tasks(seed=7)
    for t in tasks:
        if t.kind == "qa":
            t.min_tier = 1
    tr = TraceRecorder()
    res = run_fleet_loop(router, tasks, max_ms=3e7, trace=tr)
    assert events_conserved(tr.events, res.merged)
    tracks = [i for i in tr.instances() if i != "fleet"]
    assert len(tracks) == 2
    merged = replay_counters(tr.events)
    per = [replay_counters(tr.events, instance=i) for i in tr.instances()]
    assert merged["finished"] == sum(p["finished"] for p in per)
    assert merged["decode_iterations"] == sum(p["decode_iterations"]
                                              for p in per)


def test_attribution_partitions_violations():
    """Every violated request lands in exactly ONE bucket; attained and
    unfinished-but-attained requests land in none."""
    tr = TraceRecorder()
    res = _engine_run(trace=tr, chunk=None)
    att = slo_attribution(res.tasks, tr.events)
    assert att["violations"] > 0
    assert sum(att["buckets"].values()) == att["violations"]
    assert set(att["buckets"]) == set(ATTRIBUTION_BUCKETS)
    assert len(att["by_task"]) == att["violations"]
    violated = {t.task_id for t in res.tasks if not t.slo_met()}
    assert set(att["by_task"]) == violated


def test_attribution_without_trace_degrades_to_queueing():
    """An empty stream is a statement of ignorance, not a crash: with no
    spans, a late first token can only be blamed on queueing (never
    prefill interference) and a missed decode phase never on swap."""
    res = _engine_run(trace=None, chunk=None)
    att = slo_attribution(res.tasks, [])
    assert sum(att["buckets"].values()) == att["violations"]
    assert att["buckets"]["swap_stall"] == 0
    assert att["buckets"]["prefill_interference"] == 0


# ------------------------------------------------------ bounded + export

def test_ring_wraps_and_counts_drops():
    tr = TraceRecorder(capacity=64)
    _engine_run(trace=tr)
    assert len(tr) == 64
    assert tr.dropped > 0


def test_perfetto_round_trip(tmp_path):
    """Chrome-trace JSON loads back; per-track "X" spans sorted by start
    never overlap (the loop clock only moves forward); the drop counter
    is carried in otherData; flow arrows appear per finished request."""
    tr = TraceRecorder()
    res = _engine_run(trace=tr, chunk=None)
    path = tmp_path / "trace.json"
    rows = tr.export_perfetto(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert rows == len(evs)
    assert doc["otherData"]["dropped_events"] == 0
    tracks = {}
    for e in evs:
        if e.get("ph") == "X":
            assert e["name"] in SPAN_KINDS
            tracks.setdefault(e["tid"], []).append((e["ts"], e["dur"]))
    assert tracks
    for spans in tracks.values():
        spans.sort()
        for (t0, d0), (t1, _) in zip(spans, spans[1:]):
            assert t1 >= t0 + d0 - 1e-6
    flows = [e for e in evs if e.get("cat") == "req-flow"]
    finished = sum(t.finished for t in res.tasks)
    assert sum(e["ph"] == "s" for e in flows) >= finished


# ----------------------------------------------------------- program spans

def _profiled(fn):
    """Run ``fn`` under a CPU profiler session (Python calls untraced);
    returns (fn's result, the ``slice/`` host events as (name, start ns,
    end ns, args) sorted by start)."""
    import jax
    from jax.profiler import ProfileData
    out = tempfile.mkdtemp(prefix="slice-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        res = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("slice/"):
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return res, sorted(evs, key=lambda e: (e[1], -e[2]))


def _inside(evs, outer, name):
    return [e for e in evs if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


def test_span_without_session_builds_no_arguments():
    """No profiler session: one shared no-op span, and neither the
    arguments nor late arguments are ever built; with no recorder either,
    the action scope is a no-op too."""
    calls = []

    def sentinel():
        calls.append(1)
        return {"b": 1}

    assert not profiling()
    sp = span("decode", sentinel)
    assert sp is NO_SPAN and span("step") is NO_SPAN
    with sp as inner:
        inner.set(sentinel)
    with action_span(None, "engine", None, 0) as act:
        act.record("decode", -1, 0.0, 1.0, None)
    assert calls == []
    _, evs = _profiled(lambda: span("decode", sentinel).__enter__()
                       .__exit__(None, None, None))
    assert calls == [1]


def _engine_work(profile: bool):
    """One reduced paged engine (sync dispatch, chunk 8) serving three
    prompts that arrive together under a fixed latency model, so the
    schedule does not depend on wall-clock time. Returns the token
    streams, the compiled-step calls in order, the host syncs counted by
    kind, and (profiled) the program's spans."""
    import jax._src.array as jarr
    from helpers import make_paged_engine, reduced_cfg
    ex = make_paged_engine(reduced_cfg(), seed=0, prefill_chunk_size=8,
                           async_dispatch=False)
    calls, syncs = [], {"block_until_ready": 0, "__array__": 0, "_value": 0}

    def log(kind, key, fn):
        def call(*a):
            calls.append((kind, key))
            return fn(*a)
        return call

    for name, kind in (("_step_jit", "decode"), ("_chunk_jit", "chunk")):
        table = getattr(ex, name)
        for key, fn in list(table.items()):
            table[key] = log(kind, key, fn)
    streams = {}
    release = ex.release

    def keep(task):
        streams[task.task_id] = tuple(ex.generated_tokens(task))
        release(task)
    ex.release = keep
    tasks = []
    for i, n in enumerate((20, 11, 5)):
        t = qa_task(arrival_ms=0.0, prompt_len=n, output_len=4 + i)
        t.task_id = 900 + i
        t.slo = SLOSpec(tpot_ms=5_000.0, ttft_ms=100_000.0)
        tasks.append(t)
    sched = SliceScheduler(LAT, page_budget=ex.page_budget(),
                           prefill_chunk=8)
    saved = {k: getattr(jarr.ArrayImpl, k) for k in syncs}

    def counted(k, f):
        def g(self, *a, **kw):
            syncs[k] += 1
            return f(self, *a, **kw)
        return g
    try:
        for k, f in saved.items():
            setattr(jarr.ArrayImpl, k, property(counted(k, f.fget))
                    if isinstance(f, property) else counted(k, f))
        run = lambda: run_serving_loop(sched, ex, tasks)  # noqa: E731
        res, evs = _profiled(run) if profile else (run(), None)
    finally:
        for k, f in saved.items():
            setattr(jarr.ArrayImpl, k, f)
    assert all(t.finished for t in res.tasks)
    return streams, calls, syncs, evs


@pytest.fixture(scope="module")
def profiled_work():
    return _engine_work(True)


def test_spans_nest_where_the_work_happens(profiled_work):
    """slice/step > slice/schedule and slice/decode > prep/launch/sync/
    readback, one of each per decode; one piece span (with prep, launch,
    sync) per compiled chunk call, and each step names its action."""
    _, calls, _, evs = profiled_work
    steps = [e for e in evs if e[0] == "slice/step"]
    decodes = [e for e in evs if e[0] == "slice/decode"]
    pieces = [e for e in evs if e[0] == "slice/prefill_chunk/piece"]
    assert decodes and pieces
    ran = [e for e in steps if "action" in e[3]]      # the rest found none
    assert len(steps) - len(ran) == 1                 # the drained last poll
    assert [e[3]["step"] for e in ran] == list(range(len(ran)))
    assert {e[3]["action"] for e in ran} == {"decode", "prefill_chunk"}
    for st in steps:
        assert len(_inside(evs, st, "slice/schedule")) == 1
    for d in decodes:
        assert sum(len(_inside(evs, st, "slice/decode")) for st in steps
                   if st[1] <= d[1] and d[2] <= st[2]) == 1
        for child in ("prep", "launch", "sync", "readback"):
            assert len(_inside(evs, d, f"slice/decode/{child}")) == 1
        assert {"b", "rows", "ctx_max"} <= set(d[3])
    for p in pieces:
        for child in ("prep", "launch", "sync"):
            assert len(_inside(evs, p, f"slice/prefill_chunk/piece/{child}")
                       ) == 1
        assert p[3]["n"] == p[3]["bucket"]
    chunk_calls = [key for kind, key in calls if kind == "chunk"]
    assert [p[3]["n"] for p in pieces] == chunk_calls
    assert len(decodes) == sum(kind == "decode" for kind, _ in calls)
    assert len([e for e in evs if e[0] == "slice/prefill_chunk/first_token"]
               ) == 3


def test_profiler_leaves_the_work_unchanged(profiled_work):
    """Profiler on against off: the same tokens, the same compiled-step
    calls in the same order, and the same host syncs."""
    on, off = profiled_work, _engine_work(False)
    assert on[0] == off[0] and len(on[0]) == 3
    assert on[1] == off[1]
    assert on[2] == off[2] and on[2]["block_until_ready"] > 0


def _hopeless_run(trace):
    """A control task whose 1.5 s deadline has already run out when the
    scheduler first sees it, beside a control task and a QA task that stay
    feasible."""
    ctl = control_task(arrival_ms=0.0)
    ctl.task_id, ctl.arrival_ms = 71, -2_000.0
    ok = control_task(arrival_ms=0.0, prompt_len=32)
    ok.task_id = 73
    qa = qa_task(arrival_ms=0.0, prompt_len=64, output_len=8)
    qa.task_id = 72
    ex = PagedSimExecutor(LAT, total_pages=64, page_size=16)
    sched = SliceScheduler(LAT, page_budget=ex.budget, prefill_chunk=32)
    drv = InstanceDriver(sched, ex, trace=trace)
    for t in (ctl, ok, qa):
        drv.deliver(t)
    while drv.step():
        pass
    return sched, drv.result([ctl, ok, qa]), ctl, ok, qa


def test_deadline_drop_carries_its_slack():
    """The hopeless control task is dropped once, for its deadline, with a
    negative slack, in both sinks; drops_by_reason agrees untraced too,
    and the replayed ledger still counts one drop. The schedule spans
    carry the kept control task's slack, which stays positive."""
    tr = TraceRecorder()
    (sched, res, ctl, ok, qa), evs = _profiled(lambda: _hopeless_run(tr))
    assert ctl.dropped and ok.finished and qa.finished
    assert sched.drops_by_reason == res.drops_by_reason == {"deadline": 1}
    rows = [e for e in tr.events if e.kind == "drop"]
    assert [e.task_id for e in rows] == [71]
    args = rows[0].args
    assert args["reason"] == "deadline" and args["slack_ms"] < 0
    assert args["tokens_done"] == 0 and args["prefill_ms"] > 0
    assert replay_counters(tr.events)["dropped"] == 1
    (drop,) = [e for e in evs if e[0] == "slice/drop"]
    assert drop[3] == {"task": 71, **args}
    steps = [e for e in evs if e[0] == "slice/step"]
    assert sum(len(_inside(evs, st, "slice/drop")) for st in steps) == 1
    sched_spans = [e for e in evs if e[0] == "slice/schedule"]
    assert sched_spans[0][3]["replanned"] == 1          # a bool, as 1
    slack = [e[3]["rt_slack_min_ms"] for e in sched_spans
             if "rt_slack_min_ms" in e[3]]
    assert slack and 0 < min(slack) <= max(slack) < 1_500
    plain, plain_res, pctl, _, _ = _hopeless_run(None)
    assert plain.drops_by_reason == plain_res.drops_by_reason \
        == {"deadline": 1}
    assert pctl.drop_args == args


def test_page_infeasible_drop_names_pages():
    """A task whose peak residency exceeds the whole pool is dropped for
    its pages, and its drop row says so."""
    big = qa_task(arrival_ms=0.0, prompt_len=64, output_len=64)
    big.task_id = 81
    ex = PagedSimExecutor(LAT, total_pages=4, page_size=16)
    sched = SliceScheduler(LAT, page_budget=ex.budget)
    tr = TraceRecorder()
    res = run_serving_loop(sched, ex, [big], trace=tr)
    assert res.tasks[0].dropped
    assert sched.drops_by_reason == res.drops_by_reason == {"pages": 1}
    (row,) = [e for e in tr.events if e.kind == "drop"]
    assert row.args["reason"] == "pages" and row.args["pages"] > 4
