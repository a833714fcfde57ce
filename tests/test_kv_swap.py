"""Host-offload KV swap subsystem tests (DESIGN.md §7): pool swap_out /
swap_in bookkeeping (composes with refcounts: shared pages never swap),
KVSwapArena error paths, swap pricing, victim selection, scheduler-level
preemption on the sim executor, and suspend/resume logit equivalence on
the real paged engine."""
import numpy as np
import pytest

from repro.core.latency_model import paper_fig1_model
from repro.core.selection import PageBudget, select_swap_victims
from repro.core.task import SLOSpec, Task, control_task, qa_task
from repro.serving.kv_pool import KVPagePool, OutOfPages
from repro.serving.kv_swap import HostArenaFull, KVSwapArena

from helpers import assert_logits_close, make_paged_engine, reduced_cfg

LAT = paper_fig1_model()


# ------------------------------------------------------------ pool swap

def test_swap_out_in_roundtrip_preserves_length():
    pool = KVPagePool(n_pages=8, page_size=4)
    pool.alloc(1, 10)                      # 3 pages
    released = pool.swap_out(1)
    assert [li for li, _ in released] == [0, 1, 2]   # all private
    assert pool.free_pages == 8 and not pool.holds(1)
    assert pool.is_swapped(1) and pool.length(1) == 10
    assert pool.resident_page_count(1) == 0
    pool.check()
    restored = pool.swap_in(1)
    assert [li for li, _ in restored] == [0, 1, 2]
    assert pool.holds(1) and not pool.is_swapped(1)
    assert pool.length(1) == 10 and len(pool.page_table(1)) == 3
    pool.check()


def test_swap_out_keeps_shared_pages_resident():
    """Shared prefix pages are never swapped (their contents were never
    copied to host and another owner still reads them): only the private
    tail is released, and the other owner is untouched."""
    pool = KVPagePool(n_pages=8, page_size=4)
    pool.alloc(1, 12)                      # 3 pages
    shared = pool.page_table(1)[:2]
    pool.share(2, shared, 8)               # owner 2 rides pages 0-1
    released = pool.swap_out(1)
    assert [li for li, _ in released] == [2]         # only the private tail
    assert pool.resident_page_count(1) == 2          # shared pages kept
    assert pool.page_table(2) == shared              # owner 2 unaffected
    pool.check()
    restored = pool.swap_in(1)
    assert [li for li, _ in restored] == [2]
    assert pool.page_table(1)[:2] == shared          # same physical prefix
    pool.check()
    pool.free(1)
    pool.free(2)
    assert pool.used_pages == 0


def test_swap_out_pinned_pages_stay_resident():
    """An index pin (prefix cache) also blocks swapping the page."""
    pool = KVPagePool(n_pages=4, page_size=4)
    pool.alloc(1, 8)                       # 2 pages
    pinned = pool.page_table(1)[0]
    pool.retain_page(pinned)
    released = pool.swap_out(1)
    assert [li for li, _ in released] == [1]
    assert pool.ref_count(pinned) == 2     # owner ref + pin both intact
    pool.check()
    pool.swap_in(1)
    pool.release_page(pinned)
    pool.free(1)
    assert pool.used_pages == 0


def test_swap_error_paths_state_preserving():
    pool = KVPagePool(n_pages=4, page_size=4)
    with pytest.raises(ValueError):        # unknown owner
        pool.swap_out(7)
    pool.alloc(1, 8)
    pool.swap_out(1)
    with pytest.raises(ValueError):        # double swap_out
        pool.swap_out(1)
    with pytest.raises(ValueError):        # resident-only ops while swapped
        pool.extend(1, 12)
    with pytest.raises(ValueError):
        pool.alloc(1, 4)                   # swapped owner still "holds"
    with pytest.raises(ValueError):
        pool.fork(1, 0)
    with pytest.raises(ValueError):        # swap_in of a resident owner
        pool.alloc(2, 4)
        pool.swap_in(2)
    pool.check()


def test_swap_in_out_of_pages_leaves_pool_unchanged():
    pool = KVPagePool(n_pages=2, page_size=4)
    pool.alloc(1, 8)
    pool.swap_out(1)
    pool.alloc(2, 8)                       # steal both pages
    with pytest.raises(OutOfPages):
        pool.swap_in(1)
    assert pool.is_swapped(1) and pool.length(1) == 8
    pool.check()
    pool.free(2)                           # pages return...
    assert len(pool.swap_in(1)) == 2       # ...and the swap_in succeeds
    pool.check()


def test_free_of_swapped_owner_clears_swap_state():
    pool = KVPagePool(n_pages=4, page_size=4)
    pool.alloc(1, 8)
    pool.share(2, pool.page_table(1)[:1], 4)
    pool.swap_out(1)                       # keeps 1 shared page resident
    assert pool.free(1) == 0               # shared page survives via owner 2
    assert not pool.is_swapped(1)
    pool.check()
    pool.free(2)
    assert pool.used_pages == 0


# ------------------------------------------------------------ host arena

def test_arena_roundtrip_and_accounting():
    arena = KVSwapArena(page_size=4)
    blob = {"k": np.zeros((2, 4), np.float32), "v": np.zeros((2, 4), np.float32)}
    size = arena.put(1, [(0, blob), (1, blob)])
    # 2 entries x 2 arrays x 8 f32 elements = 128 B
    assert size == 128 and arena.bytes_held == size
    assert arena.holds(1) and arena.pages_held(1) == 2
    arena.check()
    entries = arena.take(1)
    assert [li for li, _ in entries] == [0, 1]
    assert arena.bytes_held == 0 and not arena.holds(1)
    assert arena.swap_outs == 1 and arena.swap_ins == 1
    assert arena.bytes_out == size and arena.bytes_in == size
    arena.check()


def test_arena_error_paths():
    arena = KVSwapArena(page_size=4, capacity_bytes=64)
    blob = {"k": np.zeros((8,), np.float32)}          # 32 B
    arena.put(1, [(0, blob)])
    with pytest.raises(ValueError):                   # double stash
        arena.put(1, [(0, blob)])
    with pytest.raises(HostArenaFull):                # capacity exceeded
        arena.put(2, [(0, blob), (1, blob)])
    assert not arena.holds(2) and arena.bytes_held == 32   # state unchanged
    with pytest.raises(ValueError):                   # take of unknown owner
        arena.take(9)
    assert arena.drop(1) == 1
    assert arena.drop(1) == 0                         # idempotent
    arena.check()
    with pytest.raises(ValueError):
        KVSwapArena(page_size=0)


# ------------------------------------------------------- pricing / policy

def test_latency_model_swap_pricing():
    lat = paper_fig1_model()
    lat.swap_bw_gbps = 8.0
    # 512 tokens x 28 KiB / 8 GB/s ~ 1.8 ms + overhead; monotone in tokens
    assert lat.swap_ms(0) == 0.0
    assert 0.0 < lat.swap_ms(1) < lat.swap_ms(512) < 10.0
    lat.swap_bw_gbps = 0.0                 # disabled -> free transfers
    assert lat.swap_ms(512) == 0.0


def test_sim_executor_prices_and_counts_swaps():
    from repro.serving.executor import SimExecutor

    ex = SimExecutor(LAT)
    t = qa_task(prompt_len=100, output_len=50)
    ms = ex.suspend(t)
    assert ms == pytest.approx(LAT.swap_ms(100))
    with pytest.raises(RuntimeError):      # double suspend
        ex.suspend(t)
    assert ex.resume(t) == pytest.approx(LAT.swap_ms(100))
    with pytest.raises(RuntimeError):      # resume without suspend
        ex.resume(t)
    assert ex.suspend_count == 1 and ex.resume_count == 1
    assert ex.swapped_bytes == pytest.approx(2 * 100 * LAT.kv_bytes_per_token)


def _mk(tpot_ms, utility, rt=False, prompt=64, out=64):
    return Task(SLOSpec(tpot_ms=tpot_ms, realtime=rt, deadline_ms=1e9),
                utility=utility, prompt_len=prompt, output_len=out)


def test_select_swap_victims_lowest_marginal_utility_first():
    held = {}
    budget = PageBudget(total_pages=8, page_size=64,
                        held_pages=lambda t: held.get(t.task_id, 0))
    rt = _mk(100.0, 50.0, rt=True)
    lo = _mk(200.0, 1.0)
    hi = _mk(200.0, 10.0)
    held[lo.task_id] = 2
    held[hi.task_id] = 2
    victims = select_swap_victims(2, [rt, hi, lo], budget, protect=[rt])
    assert [v.task_id for v in victims] == [lo.task_id]
    # needing more pages pulls in the next-cheapest resident
    victims = select_swap_victims(4, [rt, hi, lo], budget, protect=[rt])
    assert [v.task_id for v in victims] == [lo.task_id, hi.task_id]
    # realtime residents and empty holders are never victims; an
    # uncoverable shortfall selects nobody (no pointless thrashing)
    assert select_swap_victims(5, [rt, hi, lo], budget, protect=[rt]) == []


# --------------------------------------------------- scheduler preemption

def _pressure_run(kv_swap):
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import PagedSimExecutor
    from repro.serving.loop import run_serving_loop

    ex = PagedSimExecutor(LAT, total_pages=4, page_size=64)
    nrt = [qa_task(arrival_ms=float(i), prompt_len=32, output_len=80)
           for i in range(2)]              # 2 pages each -> pool full
    rt = control_task(arrival_ms=500.0, prompt_len=32, output_len=10,
                      deadline_ms=8000.0)
    sched = SliceScheduler(LAT, page_budget=ex.budget, kv_swap=kv_swap,
                           drop_expired_realtime=False)
    res = run_serving_loop(sched, ex, nrt + [rt])
    return res, rt


def test_slice_swap_admits_realtime_under_pressure():
    """The tentpole contract: defer-only admission makes the RT arrival
    wait for a resident to finish; kv_swap suspends a low-utility resident
    and admits it immediately. Everybody still finishes, and the
    suspend/resume counters surface in LoopResult."""
    res_defer, rt_defer = _pressure_run(False)
    res_swap, rt_swap = _pressure_run(True)
    assert res_defer.suspends == 0 and res_swap.suspends >= 1
    assert res_swap.resumes >= 1
    assert res_swap.swapped_bytes > 0 and res_defer.swapped_bytes == 0
    assert rt_swap.ttft_ms < rt_defer.ttft_ms / 5
    assert all(t.finished for t in res_defer.tasks)
    assert all(t.finished for t in res_swap.tasks)
    assert not any(t.suspended for t in res_swap.tasks)   # all resumed


def test_fastserve_proactive_swap_and_bookkeeping_cleanup():
    """Faithful FastServe: arrivals that do not fit swap out the most
    demoted resident and get admitted; suspended tasks swap back in by
    priority; queue_of/tokens_in_queue never leak entries."""
    from repro.core.schedulers import FastServeScheduler
    from repro.serving.executor import PagedSimExecutor
    from repro.serving.loop import run_serving_loop

    for kv_swap in (False, True):
        ex = PagedSimExecutor(LAT, total_pages=4, page_size=64)
        tasks = [qa_task(arrival_ms=50.0 * i, prompt_len=32, output_len=40)
                 for i in range(4)]        # 2 pages each, pool fits 2
        sched = FastServeScheduler(max_batch=8, page_budget=ex.budget,
                                   kv_swap=kv_swap)
        res = run_serving_loop(sched, ex, tasks)
        assert all(t.finished for t in res.tasks)
        # satellite fix: MLFQ bookkeeping is cleaned up on finish
        assert sched.queue_of == {} and sched.tokens_in_queue == {}
        if kv_swap:
            assert res.suspends >= 1 and res.resumes >= 1
            late_ttft = res.tasks[2].ttft_ms
        else:
            assert res.suspends == 0
            assert res.tasks[2].ttft_ms > 5 * 75.0   # deferred behind pool
    assert late_ttft < 5 * 75.0                      # admitted via swap


def test_fastserve_charges_peak_not_current_holdings():
    """Admission must reserve each resident's PEAK pages: a short-prompt /
    long-output task holds 1 page after prefill but grows to 5 — charging
    current holdings would over-promise the pool and crash the engine
    mid-decode (the rule SLICE's task_selection already applies)."""
    from repro.core.schedulers import FastServeScheduler, PrefillAction

    held = {}
    budget = PageBudget(total_pages=6, page_size=16,
                        held_pages=lambda t: held.get(t.task_id, 0))
    a = qa_task(prompt_len=16, output_len=64)     # 1 page held, 5 peak
    b = qa_task(prompt_len=16, output_len=64)
    sched = FastServeScheduler(max_batch=8, page_budget=budget)
    sched.on_arrival(a, 0.0)
    sched.on_arrival(b, 0.0)
    assert isinstance(sched.next_action(0.0), PrefillAction)
    sched.note_prefilled(a)
    held[a.task_id] = 1                           # current table: 1 page
    act = sched.next_action(1.0)                  # b must NOT be admitted:
    assert not isinstance(act, PrefillAction)     # 5 (peak a) + 5 > 6
    assert sched.waiting == [b]


def test_loop_survives_host_arena_full_on_suspend():
    """HostArenaFull during a suspension must not kill the run: the
    executor rolled the swap back, the scheduler blocks the victim, and
    the run completes defer-only."""
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import PagedSimExecutor
    from repro.serving.loop import run_serving_loop

    class _FullArena(PagedSimExecutor):
        def suspend(self, task):
            raise HostArenaFull("host arena full")

    ex = _FullArena(LAT, total_pages=4, page_size=64)
    nrt = [qa_task(arrival_ms=float(i), prompt_len=32, output_len=80)
           for i in range(2)]
    rt = control_task(arrival_ms=500.0, prompt_len=32, output_len=10,
                      deadline_ms=8000.0)
    sched = SliceScheduler(LAT, page_budget=ex.budget, kv_swap=True,
                           drop_expired_realtime=False)
    res = run_serving_loop(sched, ex, nrt + [rt])
    assert res.suspends == 0                      # nothing actually swapped
    assert all(t.finished for t in res.tasks)     # degraded to defer-only


def test_fastserve_resume_failure_blocks_until_finish():
    from repro.core.schedulers import FastServeScheduler

    held = {}
    budget = PageBudget(total_pages=6, page_size=16,
                        held_pages=lambda t: held.get(t.task_id, 0))
    sched = FastServeScheduler(max_batch=8, page_budget=budget, kv_swap=True)
    t = qa_task(prompt_len=16, output_len=16)
    sched.note_prefilled(t)
    t.suspended = True
    assert sched._resume_action() is not None
    sched.note_resume_failed(t)                   # pool rejected the swap-in
    assert sched._resume_action() is None         # no zero-time retry loop
    done = qa_task(prompt_len=16, output_len=16)
    sched.on_finish(done, 10.0)                   # a completion frees space
    assert sched._resume_action() is not None


def test_fastserve_prunes_dropped_task_bookkeeping():
    from repro.core.schedulers import FastServeScheduler

    sched = FastServeScheduler(max_batch=4)
    t = qa_task(prompt_len=16, output_len=8)
    sched.on_arrival(t, 0.0)
    act = sched.next_action(0.0)
    assert act.task is t
    sched.note_prefilled(t)
    assert t.task_id in sched.queue_of
    t.dropped = True
    sched.next_action(1.0)                 # prune path
    assert t.task_id not in sched.queue_of
    assert t.task_id not in sched.tokens_in_queue
    assert sched.running == []


# ----------------------------------------------------------- real engine

@pytest.fixture(scope="module")
def tiny_cfg():
    return reduced_cfg()


def test_paged_executor_suspend_resume_matches_logits(tiny_cfg):
    """Acceptance: decode across a suspend/resume cycle reproduces the
    never-suspended executor's logits to < 1e-5; zero pages and zero host
    bytes leaked afterwards; HostArenaFull rolls a suspension back."""
    exA = make_paged_engine(tiny_cfg, page_size=16)
    exB = make_paged_engine(tiny_cfg, params=exA.params, page_size=16)
    tasks = [qa_task(output_len=8, prompt_len=18) for _ in range(2)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)

    def step(subset):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        assert_logits_close(exA.last_logits, exB.last_logits)

    step([0, 1])
    exA.suspend(tasks[0])
    assert exA.arena.bytes_held > 0
    held = list(exA.pages.values())
    step([1])
    # the step consumed the arena the suspend read its pages from; the
    # host copies are what resume restores
    assert all(a.is_deleted() for a in held)
    exA.resume(tasks[0])
    step([0, 1])
    step([0])
    # HostArenaFull: suspension is rolled back, the task stays decodable
    exA.arena.capacity_bytes = 0
    with pytest.raises(HostArenaFull):
        exA.suspend(tasks[1])
    assert exA.pool.holds(tasks[1].task_id)
    step([0, 1])
    for t in tasks:
        exA.release(t)
        exB.release(t)
    exA.pool.check()
    assert exA.pool.used_pages == 0
    assert exA.arena.bytes_held == 0 and exA.arena.owners_held == 0
