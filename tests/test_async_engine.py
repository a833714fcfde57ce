"""Sync/async equivalence harness (DESIGN.md §10): the dispatch-ahead
pipelined engine must be byte-identical to the synchronous reference —
same greedy token streams, same logits (helpers.ATOL), same pool/cache
bookkeeping, same LoopResult decision metrics — across every feature
composition: plain decode, chunked prefill, prefix sharing, host-swap
suspend/resume mid-stream, speculative depths, and the 4-way sharded
mesh leg. Timing floats (schedule/dispatch/wait/swap-overlap ms) and
``pipeline_stalls`` are explicitly OUTSIDE the contract: they are what
the async mode exists to change.

Engines are built with pinned ``async_dispatch`` (oracle False,
candidate True), so this module tests the same contract on both CI
matrix legs regardless of REPRO_ASYNC_PIPELINE. Both engines are fed
the SAME Task objects (executor ops never mutate tasks — the
test_sharded idiom), except the loop-level test, which needs two
mutable workloads and pins task ids so the derived prompts match."""
import numpy as np
import pytest

from repro.core.schedulers import OrcaScheduler
from repro.core.task import SLOSpec, Task, qa_task
from repro.serving.loop import run_serving_loop

from helpers import (assert_logits_close, drive_async, drive_plain,
                     make_paged_engine, reduced_cfg, sharded_test_cfg)


@pytest.fixture(scope="module")
def setup():
    """(cfg, params) shared by the module so every pair is weight-equal."""
    import jax
    from repro.models import model as M

    cfg = reduced_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pair(cfg, params, **kw):
    """(sync oracle, async candidate) with shared params and sizing that
    fits every scenario here (suspend/resume needs free-page slack)."""
    kw.setdefault("n_pages", 64)
    kw.setdefault("max_seq", 128)
    exA = make_paged_engine(cfg, params=params, async_dispatch=False, **kw)
    exB = make_paged_engine(cfg, params=params, async_dispatch=True, **kw)
    return exA, exB


# ------------------------------------------------------------ plain decode

def test_plain_decode_streams_and_logits_match(setup):
    cfg, params = setup
    exA, exB = _pair(cfg, params)
    tasks = [qa_task(prompt_len=ln, output_len=32) for ln in (5, 23, 17)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits,
                            err_msg=f"prefill {t.task_id}")
    streams_a = drive_plain(exA, tasks, 10)
    streams_b = drive_async(exB, tasks, 10)
    assert streams_a == streams_b
    assert_logits_close(exB.last_logits, exA.last_logits)
    assert exB.gap_stats.cycles > 0
    exB.pool.check()


def test_async_drive_matches_per_step_observation(setup):
    """Observing every cycle (drive_plain) forces per-step commits; the
    pipelined drive must produce the same stream anyway — observation
    frequency is not allowed to change results."""
    cfg, params = setup
    exB1, exB2 = _pair(cfg, params)
    exB1.async_dispatch = True          # both async; different drivers
    tasks = [qa_task(prompt_len=9, output_len=24) for _ in range(2)]
    for t in tasks:
        exB1.prefill(t)
        exB2.prefill(t)
    assert drive_plain(exB1, tasks, 8) == drive_async(exB2, tasks, 8)


def test_batch_bucket_change_mid_stream(setup):
    """Dropping from 3 live tasks to 1 crosses a compiled batch bucket;
    the in-flight chain must survive the re-bucketing."""
    cfg, params = setup
    exA, exB = _pair(cfg, params)
    tasks = [qa_task(prompt_len=7, output_len=32) for _ in range(3)]
    for ex in (exA, exB):
        for t in tasks:
            ex.prefill(t)
        for _ in range(3):
            ex.decode(tasks)
        for _ in range(3):
            ex.decode(tasks[:1])        # bucket 4 -> 1
        for _ in range(2):
            ex.decode(tasks)            # and back
        if hasattr(ex, "drain"):
            ex.drain()
    assert [exA.generated_tokens(t) for t in tasks] == \
           [exB.generated_tokens(t) for t in tasks]


# ------------------------------------------------------------ chunked prefill

def test_chunked_prefill_streams_match(setup):
    cfg, params = setup
    exA, exB = _pair(cfg, params, prefill_chunk_size=8)
    tasks = [qa_task(prompt_len=20, output_len=16) for _ in range(2)]
    for ex in (exA, exB):
        for t in tasks:
            done = False
            while not done:
                _, done = ex.prefill_chunk(t, 8)
    assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    assert drive_plain(exA, tasks, 8) == drive_async(exB, tasks, 8)


# ------------------------------------------------------------ prefix sharing

def test_prefix_sharing_streams_and_pages_match(setup):
    cfg, params = setup
    exA, exB = _pair(cfg, params, prefix_cache=True)
    psz = exA.page_size
    tasks = [qa_task(prompt_len=3 * psz + 5, output_len=16)
             for _ in range(3)]
    for t in tasks:
        t.prefix_group, t.prefix_len = 1, 2 * psz
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    # sharing actually happened, identically on both engines
    assert exA.pool.free_pages == exB.pool.free_pages
    assert drive_plain(exA, tasks, 8) == drive_async(exB, tasks, 8)
    exB.pool.check()


# ------------------------------------------------------- suspend / resume

def test_suspend_resume_mid_stream_matches(setup):
    cfg, params = setup
    exA, exB = _pair(cfg, params)
    tasks = [qa_task(prompt_len=12, output_len=48) for _ in range(2)]
    for ex in (exA, exB):
        for t in tasks:
            ex.prefill(t)
        for _ in range(4):
            ex.decode(tasks)
        ex.suspend(tasks[0])
        held = list(ex.pages.values())
        for _ in range(3):
            ex.decode(tasks[1:])
        # the decodes consumed the arena the suspend's (possibly still
        # materializing) page slabs were gathered from
        assert all(a.is_deleted() for a in held)
        ex.resume(tasks[0])
        for _ in range(3):
            ex.decode(tasks)
        if hasattr(ex, "drain"):
            ex.drain()
    assert [exA.generated_tokens(t) for t in tasks] == \
           [exB.generated_tokens(t) for t in tasks]
    assert_logits_close(exB.last_logits, exA.last_logits)
    assert exB.ledger.outstanding() == 0
    exB.ledger.check()
    exB.arena.check()
    exB.pool.check()


def test_suspend_during_in_flight_decode_lands_after_commit(setup):
    """The ISSUE's ordering contract: a suspend issued while a decode is
    in flight must observe that decode first — the suspended KV includes
    the in-flight token, and the committed stream shows it."""
    cfg, params = setup
    _, exB = _pair(cfg, params)
    tasks = [qa_task(prompt_len=12, output_len=48) for _ in range(2)]
    for t in tasks:
        exB.prefill(t)
    pre_len = exB.pool.length(tasks[0].task_id)
    held = list(exB.pages.values())
    for _ in range(3):
        exB.decode(tasks)
    assert len(exB._queue) > 0          # decodes genuinely in flight
    # each in-flight step consumed its predecessor's arena at dispatch;
    # what the queue holds for commit is logits and tokens, never pages
    assert all(a.is_deleted() for a in held)
    exB.suspend(tasks[0])
    assert len(exB._queue) == 0         # suspend committed them first
    # every dispatched decode landed in history BEFORE the pages left
    assert len(exB.generated_tokens(tasks[0])) == 1 + 3
    exB.resume(tasks[0])
    # the resumed length includes all three committed tokens
    assert exB.pool.length(tasks[0].task_id) == pre_len + 3
    exB.decode(tasks)
    exB.drain()
    assert len(exB.generated_tokens(tasks[0])) == 1 + 4


# ------------------------------------------------------- speculative decode

def test_spec_decode_depths_match(setup):
    cfg, params = setup
    exA, exB = _pair(cfg, params, spec_decode=True, max_spec_depth=4)
    tasks = [qa_task(prompt_len=10, output_len=40) for _ in range(2)]
    for ex in (exA, exB):
        for t in tasks:
            ex.prefill(t)
        # mixed per-request depths, varied across iterations
        for depths in ([2, 3], [0, 4], [3, 1], [4, 4], [1, 0]):
            ex.decode(tasks, depths=depths)
        if hasattr(ex, "drain"):
            ex.drain()
    assert [exA.generated_tokens(t) for t in tasks] == \
           [exB.generated_tokens(t) for t in tasks]
    assert exA.last_commits == exB.last_commits
    assert exA.accepted_tokens == exB.accepted_tokens
    assert exA.drafted_tokens == exB.drafted_tokens
    assert_logits_close(exB.last_logits, exA.last_logits)


# ------------------------------------------------------------ mesh leg

def test_sharded_async_streams_match(mesh4):
    """Async pipelining composes with tensor-parallel sharding: the
    4-way async engine equals the single-device sync oracle."""
    import jax
    from repro.models import model as M

    cfg = sharded_test_cfg(ways=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    exA = make_paged_engine(cfg, params=params, async_dispatch=False,
                            n_pages=64, max_seq=128)
    exB = make_paged_engine(cfg, params=params, async_dispatch=True,
                            n_pages=64, max_seq=128, mesh=mesh4)
    tasks = [qa_task(prompt_len=ln, output_len=16) for ln in (5, 17)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    assert drive_plain(exA, tasks, 6) == drive_async(exB, tasks, 6)
    assert_logits_close(exB.last_logits, exA.last_logits)


# ------------------------------------------------------- loop-level metrics

def _loop_workload():
    """Fresh Task objects per run (the loop mutates them), but with
    PINNED ids so both engines derive identical prompt tokens."""
    return [Task(slo=SLOSpec(tpot_ms=100.0, ttft_ms=2000.0), utility=1.0,
                 prompt_len=8 + 3 * i, output_len=10, arrival_ms=float(i),
                 task_id=9000 + i, kind="qa") for i in range(4)]


def test_loop_metrics_equivalence_under_orca(setup):
    """Full serving loop under Orca: every decision-metric field of
    LoopResult (counts, not timings) and every per-task outcome must be
    identical across modes — the pipeline may only change WHEN results
    are observed, never WHAT the policy decides."""
    cfg, params = setup
    exA, exB = _pair(cfg, params)
    resA = run_serving_loop(OrcaScheduler(max_batch=4), exA, _loop_workload())
    resB = run_serving_loop(OrcaScheduler(max_batch=4), exB, _loop_workload())
    for field in ("decode_iterations", "prefills", "prefill_chunks",
                  "suspends", "resumes", "spec_extra_tokens",
                  "drafted_tokens", "accepted_tokens"):
        assert getattr(resA, field) == getattr(resB, field), field
    for a, b in zip(resA.tasks, resB.tasks):
        assert a.finished == b.finished
        assert a.tokens_done == b.tokens_done
        assert len(a.token_times_ms) == len(b.token_times_ms)
    # the async run measured its gap breakdown; the host was dispatching
    assert resB.dispatch_ms > 0.0
