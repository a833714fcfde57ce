"""Paged KV-cache subsystem tests: pool invariants, paged kernel vs oracle,
paged-vs-slot executor logit equivalence, leak-freedom across a full serving
run, and memory-aware SLICE admission (deferral, never drops)."""
import numpy as np
import pytest

from repro.core.latency_model import paper_fig1_model
from repro.core.selection import PageBudget, task_selection
from repro.core.task import SLOSpec, Task, qa_task
from repro.serving.kv_pool import KVPagePool, OutOfPages

LAT = paper_fig1_model()


# ------------------------------------------------------------------- pool

def test_pool_alloc_extend_free_invariants():
    pool = KVPagePool(n_pages=8, page_size=16)
    a = pool.alloc(1, 20)                 # 2 pages
    assert len(a) == 2 and pool.used_pages == 2
    b = pool.alloc(2, 1)                  # 1 page
    assert len(b) == 1 and pool.free_pages == 5
    assert set(a).isdisjoint(b)
    # extend within the last page allocates nothing
    assert pool.extend(1, 32) == []
    # crossing the boundary allocates exactly one page
    fresh = pool.extend(1, 33)
    assert len(fresh) == 1 and fresh[0] not in a + b
    assert pool.page_table(1) == a + fresh
    pool.check()
    assert pool.free(1) == 3
    assert pool.free(1) == 0              # idempotent
    assert pool.free_pages == 7
    pool.check()


def test_pool_exhaustion_raises_and_preserves_state():
    pool = KVPagePool(n_pages=4, page_size=16)
    pool.alloc(1, 48)                     # 3 pages
    with pytest.raises(OutOfPages):
        pool.alloc(2, 32)                 # needs 2, only 1 free
    assert pool.free_pages == 1 and not pool.holds(2)
    with pytest.raises(OutOfPages):
        pool.extend(1, 80)                # needs 2 more
    assert pool.length(1) == 48
    pool.check()


def test_pool_rejects_double_alloc_and_unknown_extend():
    pool = KVPagePool(n_pages=4, page_size=8)
    pool.alloc(7, 8)
    with pytest.raises(ValueError):
        pool.alloc(7, 8)
    with pytest.raises(ValueError):
        pool.extend(99, 16)


# -------------------------------------------------------- memory admission

def _mk_task(tpot_ms, utility, prompt=64, out=64):
    return Task(SLOSpec(tpot_ms=tpot_ms), utility=utility,
                prompt_len=prompt, output_len=out)


def test_selection_defers_on_page_exhaustion_never_drops():
    """Three tasks, pool fits only two: the lowest-utility-rate task is
    deferred (returned with the pool), not dropped; utility ordering decides
    who gets pages."""
    budget = PageBudget(total_pages=4, page_size=64)   # 2 pages per task
    hi = _mk_task(200.0, 10.0)
    mid = _mk_task(200.0, 5.0)
    lo = _mk_task(200.0, 1.0)
    sel, rest = task_selection([lo, hi, mid], LAT, page_budget=budget)
    assert {t.task_id for t in sel} == {hi.task_id, mid.task_id}
    assert [t.task_id for t in rest] == [lo.task_id]
    assert not lo.dropped


def test_selection_memory_deferral_skips_to_smaller_task():
    """A task too big for the remaining pages is deferred while a smaller,
    lower-rate task further down the ordering still gets them."""
    budget = PageBudget(total_pages=4, page_size=64)
    big = _mk_task(200.0, 10.0, prompt=128, out=64)    # 3 pages
    huge = _mk_task(200.0, 5.0, prompt=192, out=64)    # 4 pages -> can't join
    small = _mk_task(200.0, 1.0, prompt=32, out=16)    # 1 page -> fits
    sel, rest = task_selection([big, huge, small], LAT, page_budget=budget)
    assert {t.task_id for t in sel} == {big.task_id, small.task_id}
    assert [t.task_id for t in rest] == [huge.task_id]


def test_selection_counts_held_pages_of_unselected_tasks():
    """Pages physically held by a running task are committed up front, so a
    newcomer cannot be promised them; re-admitting the holder itself costs
    nothing extra (its holdings == its peak)."""
    runner = _mk_task(200.0, 0.1)      # 2 pages peak, 2 held, lowest rate
    held = {runner.task_id: 2}
    budget = PageBudget(total_pages=4, page_size=64,
                        held_pages=lambda t: held.get(t.task_id, 0))
    a = _mk_task(200.0, 10.0)          # 2 pages
    b = _mk_task(200.0, 5.0)           # 2 pages -> must NOT fit (2 held + 2)
    sel, rest = task_selection([runner, a, b], LAT, page_budget=budget)
    assert {t.task_id for t in sel} == {a.task_id, runner.task_id}
    assert [t.task_id for t in rest] == [b.task_id]


def test_selection_without_budget_unchanged():
    tasks = [_mk_task(100.0, float(u)) for u in range(1, 6)]
    sel_a, rest_a = task_selection(tasks, LAT)
    sel_b, rest_b = task_selection(tasks, LAT, page_budget=None)
    assert [t.task_id for t in sel_a] == [t.task_id for t in sel_b]
    assert [t.task_id for t in rest_a] == [t.task_id for t in rest_b]


def test_scheduler_defers_then_admits_after_finish():
    """SimExecutor run: pool fits one task at a time; SLICE serializes the
    two tasks instead of dropping either."""
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import SimExecutor
    from repro.serving.loop import run_serving_loop

    budget = PageBudget(total_pages=2, page_size=64)   # 1 task at a time
    t1 = _mk_task(200.0, 10.0, prompt=64, out=4)
    t2 = _mk_task(200.0, 1.0, prompt=64, out=4)
    t2.arrival_ms = 1.0
    sched = SliceScheduler(LAT, page_budget=budget)
    res = run_serving_loop(sched, SimExecutor(LAT), [t1, t2])
    assert all(t.finished for t in res.tasks)
    assert not any(t.dropped for t in res.tasks)
    # serialized: t2's first decode token comes after t1's last
    assert t2.token_times_ms[1] > t1.token_times_ms[-1]


def test_selection_respects_max_tasks():
    """Admission never composes a batch larger than the engine's compiled
    bucket ceiling, even when time and pages both allow it."""
    budget = PageBudget(total_pages=100, page_size=64, max_tasks=2)
    tasks = [_mk_task(200.0, float(u)) for u in (5, 4, 3, 2, 1)]
    sel, rest = task_selection(tasks, LAT, page_budget=budget)
    assert len(sel) == 2 and len(rest) == 3
    assert {t.utility for t in sel} == {5.0, 4.0}


def test_scheduler_drops_page_infeasible_task():
    """A task whose peak residency exceeds the engine's seq cap can never
    run — it is dropped visibly, not deferred forever, and does not block
    feasible tasks."""
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import SimExecutor
    from repro.serving.loop import run_serving_loop

    budget = PageBudget(total_pages=8, page_size=16, prompt_cap=32,
                        seq_cap=64)
    ok = _mk_task(200.0, 5.0, prompt=16, out=16)        # peak 32 <= 64
    too_big = _mk_task(200.0, 10.0, prompt=64, out=64)  # 32 + 64 > 64
    res = run_serving_loop(SliceScheduler(LAT, page_budget=budget),
                           SimExecutor(LAT), [ok, too_big])
    assert too_big.dropped and not too_big.finished
    assert ok.finished
    assert all(t.finished or t.dropped for t in res.tasks)


def test_loop_releases_kv_of_dropped_tasks():
    """Dropped tasks never reach the finish path, so the serving loop must
    reclaim their KV (slots or pages) itself."""
    from repro.core.schedulers import (DecodeAction, PrefillAction,
                                       Scheduler)
    from repro.serving.executor import SimExecutor
    from repro.serving.loop import run_serving_loop

    victim = _mk_task(200.0, 1.0, out=8)

    class _DropAfterOneDecode(Scheduler):
        def __init__(self):
            self.q = []
            self.decoded = False

        def on_arrival(self, task, now):
            self.q.append(task)

        def next_action(self, now):
            if self.q:
                return PrefillAction(self.q.pop(0))
            if not self.decoded:
                self.decoded = True
                return DecodeAction([victim])
            victim.dropped = True          # mid-run preemption drop
            return None

        def unfinished(self):
            return 0

    class _RecExec(SimExecutor):
        def __init__(self, lat):
            super().__init__(lat)
            self.released = []

        def release(self, task):
            self.released.append(task.task_id)

    ex = _RecExec(LAT)
    run_serving_loop(_DropAfterOneDecode(), ex, [victim])
    assert ex.released == [victim.task_id]


# ------------------------------------------------------------ paged kernel

def test_paged_kernel_matches_ref():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    P, Hkv, psz, hd, Hq, B, maxp = 12, 2, 8, 32, 4, 3, 4
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, hd))
    kp = jax.random.normal(ks[1], (P, Hkv, psz, hd))
    vp = jax.random.normal(ks[2], (P, Hkv, psz, hd))
    pt = jnp.array([[3, 5, -1, -1], [0, -1, -1, -1], [7, 2, 9, 1]], jnp.int32)
    qpos = jnp.array([12, 4, 30], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, pt, qpos, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.isnan(np.asarray(out)).any()


def test_paged_kernel_page_boundary_masking():
    """q_pos mid-page: tokens past q_pos in the same page must be masked."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(1)
    P, Hkv, psz, hd, Hq = 6, 1, 16, 32, 2
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, Hq, hd))
    kp = jax.random.normal(ks[1], (P, Hkv, psz, hd))
    vp = jax.random.normal(ks[2], (P, Hkv, psz, hd))
    pt = jnp.array([[2, 4, 1]], jnp.int32)
    for qpos in (0, 7, 16, 33, 47):
        out = ops.paged_decode_attention(q, kp, vp, pt,
                                         jnp.array([qpos], jnp.int32),
                                         interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, pt,
                                              jnp.array([qpos], jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["decode", "verify"])
@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("width", [32, 128], ids=["unpadded", "lane_padded"])
def test_paged_kernels_read_one_layer_of_the_stacked_arena(kind, layer,
                                                           width):
    """Given the whole [L,P,Hkv,psz,W] arena and a layer index, the paged
    kernels read that layer's pages: equal to the oracle on pages[l]. With
    W past head_dim (the arena's lane padding, here holding garbage in V
    and zeros in K, as written) only the first head_dim lanes count."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    L, P, Hkv, psz, hd, Hq, B, C = 5, 12, 2, 8, 32, 4, 3, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    kp = jax.random.normal(ks[1], (L, P, Hkv, psz, width))
    kp = kp.at[..., hd:].set(0.0)
    vp = jax.random.normal(ks[2], (L, P, Hkv, psz, width))
    pt = jnp.array([[3, 5, -1, -1], [0, -1, -1, -1], [7, 2, 9, 1]], jnp.int32)
    pos = jnp.array([12, 4, 27], jnp.int32)
    lyr = jnp.int32(layer)
    if kind == "decode":
        q = jax.random.normal(ks[0], (B, Hq, hd))
        out = ops.paged_decode_attention(q, kp, vp, pt, pos, lyr,
                                         interpret=True)
        want = ref.paged_decode_attention_ref(q, kp[layer, ..., :hd],
                                              vp[layer, ..., :hd], pt, pos)
    else:
        q = jax.random.normal(ks[0], (B, C, Hq, hd))
        out = ops.paged_verify_attention(q, kp, vp, pt, pos, lyr,
                                         interpret=True)
        want = ref.paged_verify_attention_ref(q, kp[layer, ..., :hd],
                                              vp[layer, ..., :hd], pt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- paged executor

@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs import get_config
    return get_config("smollm-360m").reduced()


def test_paged_executor_matches_slot_logits(tiny_cfg):
    """Acceptance: PagedJaxExecutor logits == JaxExecutor logits (atol 1e-5)
    on a shared workload of irregular decode subsets."""
    from repro.serving.executor import JaxExecutor, PagedJaxExecutor

    exA = JaxExecutor(tiny_cfg, max_slots=4, max_seq=64, seed=0)
    exB = PagedJaxExecutor(tiny_cfg, params=exA.params, n_pages=16,
                           page_size=16, max_seq=64, seed=0, max_batch=4)
    tasks = [qa_task(output_len=6, prompt_len=8) for _ in range(3)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
    for subset in ([0], [0, 2], [1], [0, 1, 2], [2], [1, 2]):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        np.testing.assert_allclose(exB.last_logits, exA.last_logits,
                                   atol=1e-5, rtol=0)
    for t in tasks:
        exB.release(t)
    exB.pool.check()
    assert exB.pool.used_pages == 0


def test_paged_executor_kernel_path_matches_jnp_path(tiny_cfg):
    """use_paged_kernel=True (Pallas scalar-prefetch, interpret on CPU) must
    reproduce the pure-jnp gather path."""
    from repro.serving.executor import PagedJaxExecutor

    exA = PagedJaxExecutor(tiny_cfg, n_pages=8, page_size=16, max_seq=64,
                           seed=0, max_batch=2)
    exB = PagedJaxExecutor(tiny_cfg, params=exA.params, n_pages=8,
                           page_size=16, max_seq=64, seed=0, max_batch=2,
                           use_paged_kernel=True)
    tasks = [qa_task(output_len=4, prompt_len=8) for _ in range(2)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
    for subset in ([0, 1], [0], [1]):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        np.testing.assert_allclose(exB.last_logits, exA.last_logits,
                                   atol=1e-4, rtol=0)


def test_paged_executor_no_leaks_across_serving_run(tiny_cfg):
    """Full SLICE serving-loop run over the paged engine: every task finishes
    and the pool returns to empty (release() frees every page)."""
    from repro.core.schedulers import SliceScheduler
    from repro.core.task import control_task
    from repro.serving.executor import PagedJaxExecutor
    from repro.serving.loop import run_serving_loop

    ex = PagedJaxExecutor(tiny_cfg, n_pages=8, page_size=16, max_seq=64,
                          max_batch=4)
    before = list(ex.pages.values())
    lat = ex.latency_model()
    assert ex.pool.used_pages == 0       # latency probes released their pages
    # the probe's decodes consumed the arena they were given; the engine
    # serves on from the one they returned
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in ex.pages.values())
    tasks = [control_task(output_len=6, prompt_len=12),
             qa_task(arrival_ms=1.0, output_len=8, prompt_len=16),
             qa_task(arrival_ms=2.0, output_len=8, prompt_len=16),
             qa_task(arrival_ms=3.0, output_len=8, prompt_len=16)]
    res = run_serving_loop(SliceScheduler(lat, page_budget=ex.page_budget()),
                           ex, tasks)
    assert all(t.finished for t in res.tasks)
    assert ex.pool.used_pages == 0
    ex.pool.check()


def test_paged_executor_admits_more_than_slot_at_equal_bytes(tiny_cfg):
    """The point of paging: at equal KV bytes (n_pages*page_size ==
    max_slots*max_seq tokens), short tasks admit a strictly larger batch."""
    from repro.serving.executor import PagedJaxExecutor

    # slot layout: 2 slots x 64 tokens; paged: 8 pages x 16 tokens
    ex = PagedJaxExecutor(tiny_cfg, n_pages=8, page_size=16, max_seq=64,
                          max_batch=8)
    tasks = [qa_task(output_len=4, prompt_len=8) for _ in range(4)]
    for t in tasks:
        ex.prefill(t)                    # 8+4 tokens -> 1 page each
    ex.decode(tasks)                     # all 4 concurrent; slots would cap at 2
    assert ex.pool.used_pages == 4
    budget = ex.page_budget()
    assert budget.fits(tasks)


def test_paged_executor_gates_ssm_feature_combos():
    """SSM archs are first-class now (DESIGN.md §12) — but features that
    rewind/share/shard per-token KV must still raise for them, and the
    engine must come up with the state-kind store wired."""
    from repro.configs import get_config
    from repro.serving.executor import PagedJaxExecutor

    cfg = get_config("mamba2-780m").reduced()
    ex = PagedJaxExecutor(cfg, n_pages=4, page_size=16, max_seq=64)
    assert ex.states is not None and ex.store.kinds == ("state",)
    for kw in ({"spec_decode": True}, {"prefix_cache": True},
               {"prefill_chunk_size": 16}):
        with pytest.raises(ValueError, match="DESIGN.md"):
            PagedJaxExecutor(cfg, n_pages=4, page_size=16, max_seq=64, **kw)
