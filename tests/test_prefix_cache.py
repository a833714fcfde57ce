"""Prefix-sharing subsystem tests (DESIGN.md §6): refcounted pool
share/fork/free invariants (incl. the OutOfPages error-path regression),
radix index match/insert/evict semantics, sharing-aware admission, and the
executor-level contract — prefix-shared paged prefill/decode reproduces
the unshared paged path's logits to < 1e-5 with zero page leaks."""
import numpy as np
import pytest

from repro.core.latency_model import paper_fig1_model
from repro.core.selection import PageBudget, task_selection
from repro.core.task import SLOSpec, Task, qa_task
from repro.serving.kv_pool import KVPagePool, OutOfPages
from repro.serving.prefix_cache import RadixPrefixCache

LAT = paper_fig1_model()


# ------------------------------------------------------- refcounted pool

def test_share_refcounts_and_free_order_independent():
    pool = KVPagePool(n_pages=8, page_size=4)
    a = pool.alloc(1, 8)                    # 2 pages
    pool.share(2, a, 8)                     # owner 2 rides the same pages
    assert pool.page_table(2) == a
    assert all(pool.ref_count(p) == 2 for p in a)
    assert pool.used_pages == 2
    pool.extend(2, 9)                       # private growth page
    grown = pool.page_table(2)[-1]
    assert pool.ref_count(grown) == 1
    pool.check()
    assert pool.free(1) == 0                # pages still shared -> not freed
    assert pool.used_pages == 3
    assert pool.free(2) == 3                # last reference frees all
    assert pool.used_pages == 0
    pool.check()


def test_share_requires_page_alignment_and_allocated_pages():
    pool = KVPagePool(n_pages=4, page_size=4)
    a = pool.alloc(1, 8)
    with pytest.raises(ValueError):
        pool.share(2, a, 7)                 # not page-aligned
    with pytest.raises(ValueError):
        pool.share(2, [3], 4)               # page 3 is free
    pool.check()


def test_fork_copy_on_write_bookkeeping():
    pool = KVPagePool(n_pages=4, page_size=4)
    a = pool.alloc(1, 8)
    pool.share(2, a, 8)
    assert pool.is_shared(2, 0)
    old, new = pool.fork(2, 0)
    assert old == a[0] and new not in a
    assert pool.page_table(2) == [new, a[1]]
    assert pool.ref_count(a[0]) == 1 and pool.ref_count(new) == 1
    assert pool.fork(2, 0) is None          # already private
    pool.check()
    pool.free(1)
    pool.free(2)
    assert pool.used_pages == 0


def test_fork_out_of_pages_leaves_state_unchanged():
    pool = KVPagePool(n_pages=2, page_size=4)
    a = pool.alloc(1, 8)                    # whole pool
    pool.share(2, a, 8)
    before = (pool.page_table(1), pool.page_table(2),
              [pool.ref_count(p) for p in a], pool.free_pages)
    with pytest.raises(OutOfPages):
        pool.fork(2, 1)
    after = (pool.page_table(1), pool.page_table(2),
             [pool.ref_count(p) for p in a], pool.free_pages)
    assert before == after
    pool.check()


def test_extend_out_of_pages_preserves_refcounts_and_free_list():
    """Satellite regression (ISSUE 3): once refcounting lands, the extend
    error path must leave refcounts, the free list, and every page table
    exactly as they were."""
    pool = KVPagePool(n_pages=4, page_size=4)
    a = pool.alloc(1, 12)                   # 3 pages
    pool.share(2, a[:2], 8)                 # shared prefix
    pool.extend(2, 12)                      # private third page -> pool full
    snap = (list(pool._free), pool.page_table(1), pool.page_table(2),
            {p: pool.ref_count(p) for p in range(4)},
            pool.length(1), pool.length(2))
    with pytest.raises(OutOfPages):
        pool.extend(2, 17)                  # needs a 5th page
    assert snap == (list(pool._free), pool.page_table(1), pool.page_table(2),
                    {p: pool.ref_count(p) for p in range(4)},
                    pool.length(1), pool.length(2))
    pool.check()


def test_retain_release_page_pins():
    pool = KVPagePool(n_pages=2, page_size=4)
    (p,) = pool.alloc(1, 4)
    pool.retain_page(p)
    assert pool.ref_count(p) == 2 and pool.owner_refs(p) == 1
    pool.free(1)
    assert pool.used_pages == 1             # pin keeps it resident
    assert pool.release_page(p)             # last reference -> freed
    assert pool.used_pages == 0
    with pytest.raises(ValueError):
        pool.release_page(p)
    pool.check()


# ------------------------------------------------------------ radix index

def _toks(*blocks):
    out = []
    for b in blocks:
        out.extend(b)
    return out


def test_radix_match_is_page_aligned_longest_prefix():
    pool = KVPagePool(n_pages=8, page_size=2)
    cache = RadixPrefixCache(pool)
    pages = pool.alloc(1, 6)                # 3 pages: [1,2],[3,4],[5,6]
    cache.insert([1, 2, 3, 4, 5, 6], pages)
    assert cache.pages_indexed == 3
    n, got = cache.match([1, 2, 3, 4, 5, 6])
    assert n == 6 and got == pages
    n, got = cache.match([1, 2, 3, 4, 9, 9])     # diverges at block 3
    assert n == 4 and got == pages[:2]
    n, got = cache.match([1, 2, 3])              # partial block never matches
    assert n == 2 and got == pages[:1]
    assert cache.match([9, 9]) == (0, [])
    pool.free(1)
    assert pool.used_pages == 3             # index pins survive the owner
    assert cache.clear() == 3
    assert pool.used_pages == 0
    pool.check()


def test_radix_divergent_suffixes_never_alias():
    """Two prompts sharing one block then diverging: the shared block maps
    to ONE page, the divergent blocks to distinct pages."""
    pool = KVPagePool(n_pages=8, page_size=2)
    cache = RadixPrefixCache(pool)
    pa = pool.alloc(1, 4)
    cache.insert([7, 7, 1, 1], pa)
    pb = pool.alloc(2, 4)
    cache.insert([7, 7, 2, 2], pb)
    n1, g1 = cache.match([7, 7, 1, 1])
    n2, g2 = cache.match([7, 7, 2, 2])
    assert g1[0] == g2[0] == pa[0]          # shared block: first writer wins
    assert g1[1] == pa[1] and g2[1] == pb[1]
    assert g1[1] != g2[1]
    pool.free(1), pool.free(2)
    cache.clear()
    assert pool.used_pages == 0
    pool.check()


def test_radix_acquire_caps_below_full_prompt():
    """acquire(max_tokens=L-1) always leaves at least the final block to
    recompute — its logits seed the first output token."""
    pool = KVPagePool(n_pages=8, page_size=2)
    cache = RadixPrefixCache(pool)
    pages = pool.alloc(1, 6)
    toks = [1, 2, 3, 4, 5, 6]
    cache.insert(toks, pages)
    n, got = cache.acquire(owner=2, tokens=toks, max_tokens=5)
    assert n == 4 and got == pages[:2]
    assert pool.page_table(2) == pages[:2] and pool.length(2) == 4
    pool.free(1), pool.free(2)
    cache.clear()
    pool.check()


def test_radix_lru_eviction_leaf_first_under_max_pages():
    pool = KVPagePool(n_pages=8, page_size=2)
    cache = RadixPrefixCache(pool, max_pages=2)
    pa = pool.alloc(1, 4)
    assert cache.insert([1, 1, 2, 2], pa) == 2
    pb = pool.alloc(2, 2)
    cache.match([1, 1])                     # touch the interior path
    assert cache.insert([9, 9], pb) == 1    # evicts the LRU leaf [2,2]
    assert cache.pages_indexed == 2
    n, _ = cache.match([1, 1, 2, 2])
    assert n == 2                           # leaf gone, root block remains
    assert cache.match([9, 9])[0] == 2
    pool.free(1), pool.free(2)
    cache.clear()
    assert pool.used_pages == 0
    pool.check()


def test_radix_reclaimable_counts_unowned_pins_only():
    pool = KVPagePool(n_pages=8, page_size=2)
    cache = RadixPrefixCache(pool)
    pages = pool.alloc(1, 4)
    cache.insert([1, 1, 2, 2], pages)
    assert cache.reclaimable_pages() == 0   # owner 1 still holds them
    pool.free(1)
    assert cache.reclaimable_pages() == 2
    cache.acquire(owner=2, tokens=[1, 1], max_tokens=2)
    assert cache.reclaimable_pages() == 1
    pool.free(2)
    cache.clear()
    pool.check()


# ------------------------------------------------- sharing-aware admission

def _mk(tpot_ms, utility, prompt=64, out=64, group=None, prefix=0):
    t = Task(SLOSpec(tpot_ms=tpot_ms), utility=utility,
             prompt_len=prompt, output_len=out)
    t.prefix_group, t.prefix_len = group, prefix
    return t


def test_selection_counts_shared_prefix_once():
    """Pool of 8 pages, page 64: three group-g tasks at peak 2 pages each
    with a 1-page shared prefix cost 1 + 3 = 4 pages, not 6 — a fourth,
    private task still fits where naive accounting would defer it."""
    def prefix_pages(t):
        if t.prefix_group is None:
            return None, 0
        return ("g", t.prefix_group), t.prefix_len // 64
    budget = PageBudget(total_pages=6, page_size=64,
                        free_pages_now=lambda: 6, prefix_pages=prefix_pages)
    shared = [_mk(200.0, 10.0 - i, prompt=64, out=64, group=1, prefix=64)
              for i in range(3)]            # 2 pages peak, 1 shared
    private = _mk(200.0, 1.0, prompt=64, out=64)
    sel, rest = task_selection(shared + [private], LAT, page_budget=budget)
    assert {t.task_id for t in sel} == {t.task_id for t in shared + [private]}
    assert rest == []
    # without the sharing-aware budget the same pool defers two tasks
    naive = PageBudget(total_pages=6, page_size=64)
    sel2, rest2 = task_selection(shared + [private], LAT, page_budget=naive)
    assert len(sel2) == 3 and len(rest2) == 1


def test_selection_first_sharer_pays_prefix():
    """The first admitted task of a group pays the full prefix, so a group
    never fits 'for free': 2 tasks x (1 shared + 1 private) in 2 pages is
    rejected."""
    def prefix_pages(t):
        return (("g", t.prefix_group), t.prefix_len // 64) \
            if t.prefix_group is not None else (None, 0)
    budget = PageBudget(total_pages=2, page_size=64,
                        free_pages_now=lambda: 2, prefix_pages=prefix_pages)
    tasks = [_mk(200.0, 5.0, prompt=64, out=64, group=1, prefix=64),
             _mk(200.0, 4.0, prompt=64, out=64, group=1, prefix=64)]
    sel, rest = task_selection(tasks, LAT, page_budget=budget)
    assert len(sel) == 1 and len(rest) == 1


def test_selection_live_free_count_matches_static_accounting():
    """free_pages_now == total - holdings reproduces the static path's
    decisions when nothing is shared."""
    held = {}
    tasks = [_mk(200.0, float(u)) for u in (5, 4, 3, 2, 1)]   # 2 pages each
    held[tasks[0].task_id] = 2               # running task holds its peak
    static = PageBudget(total_pages=6, page_size=64,
                        held_pages=lambda t: held.get(t.task_id, 0))
    live = PageBudget(total_pages=6, page_size=64,
                      held_pages=lambda t: held.get(t.task_id, 0),
                      free_pages_now=lambda: 6 - 2)
    sel_a, rest_a = task_selection(tasks, LAT, page_budget=static)
    sel_b, rest_b = task_selection(tasks, LAT, page_budget=live)
    assert [t.task_id for t in sel_a] == [t.task_id for t in sel_b]
    assert [t.task_id for t in rest_a] == [t.task_id for t in rest_b]


# --------------------------------------------------------- executor level

@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs import get_config
    return get_config("smollm-360m").reduced()


def _grouped_tasks(n, group=5, prompt=24, prefix=16, out=4):
    tasks = [qa_task(output_len=out, prompt_len=prompt) for _ in range(n)]
    for t in tasks:
        t.prefix_group, t.prefix_len = group, prefix
    return tasks


def test_prefix_shared_prefill_decode_logits_match_unshared(tiny_cfg):
    """Acceptance: cache-hit prefill + decode over shared pages reproduce
    the unshared paged path's logits to < 1e-5, and the shared engine
    holds strictly fewer pages."""
    from repro.serving.executor import PagedJaxExecutor

    exA = PagedJaxExecutor(tiny_cfg, n_pages=16, page_size=8, max_seq=64,
                           seed=0, max_batch=4)
    exB = PagedJaxExecutor(tiny_cfg, params=exA.params, n_pages=16,
                           page_size=8, max_seq=64, seed=0, max_batch=4,
                           prefix_cache=True)
    tasks = _grouped_tasks(3)
    for t in tasks:
        exA.prefill(t)
        la = exA.last_prefill_logits.copy()
        exB.prefill(t)
        np.testing.assert_allclose(exB.last_prefill_logits, la,
                                   atol=1e-5, rtol=0)
    # the two cache-hit tasks share the first 2 pages with the first task
    t0_pages = exB.pool.page_table(tasks[0].task_id)[:2]
    for t in tasks[1:]:
        assert exB.pool.page_table(t.task_id)[:2] == t0_pages
    assert exB.pool.used_pages < exA.pool.used_pages
    for subset in ([0, 1, 2], [0], [1, 2], [2]):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        np.testing.assert_allclose(exB.last_logits, exA.last_logits,
                                   atol=1e-5, rtol=0)
    for t in tasks:
        exB.release(t)
    exB.prefix_cache.clear()
    assert exB.pool.used_pages == 0
    exB.pool.check()


def test_copy_on_write_fork_before_a_donated_step(tiny_cfg):
    """A decode that writes into a shared page forks it first: the page is
    copied eagerly (out of place) and the arena re-pinned to its canonical
    format, then the compiled step consumes that new arena. The sharer
    decodes as an engine holding the same tokens privately does, and the
    owner's page is left as it was."""
    from repro.serving.executor import PagedJaxExecutor

    kw = dict(n_pages=16, page_size=8, max_seq=64, seed=0, max_batch=4)
    ex = PagedJaxExecutor(tiny_cfg, **kw)
    solo = PagedJaxExecutor(tiny_cfg, params=ex.params, **kw)
    ref = PagedJaxExecutor(tiny_cfg, params=ex.params, **kw)
    owner = qa_task(output_len=4, prompt_len=16)
    sharer = qa_task(output_len=4, prompt_len=12)
    ex.prefill(owner)
    solo.prefill(owner)
    prompt = ex._task_tokens(owner)
    # the sharer holds the owner's first 12 tokens in the owner's pages,
    # so its next token lands inside a shared page
    ex.pool.share(sharer.task_id, ex.pool.page_table(owner.task_id), 16)
    ex.pool.truncate(sharer.task_id, 12)
    ex._last_tok[sharer.task_id] = int(prompt[0, 12])
    ref._toks_memo[sharer.task_id] = prompt[:, :12]
    ref.prefill(sharer)
    ref._last_tok[sharer.task_id] = int(prompt[0, 12])
    owner_pages = ex.pool.page_table(owner.task_id)

    before = list(ex.pages.values())
    ex.decode([sharer])
    ref.decode([sharer])
    # the fork's copy is out of place: the step consumed the forked arena,
    # not the one held here
    assert not any(a.is_deleted() for a in before)
    assert ex.pool.page_table(sharer.task_id)[1] != owner_pages[1]
    np.testing.assert_allclose(ex.last_logits, ref.last_logits,
                               atol=1e-5, rtol=0)
    ex.decode([owner])
    solo.decode([owner])
    np.testing.assert_allclose(ex.last_logits, solo.last_logits,
                               atol=1e-5, rtol=0)
    for t in (owner, sharer):
        ex.release(t)
    ex.pool.check()
    assert ex.pool.used_pages == 0


def test_prefix_shared_chunked_prefill_starts_at_first_uncached_chunk(tiny_cfg):
    from repro.serving.executor import PagedJaxExecutor

    exA = PagedJaxExecutor(tiny_cfg, n_pages=24, page_size=8, max_seq=64,
                           seed=0, max_batch=4, prefill_chunk_size=8)
    exB = PagedJaxExecutor(tiny_cfg, params=exA.params, n_pages=24,
                           page_size=8, max_seq=64, seed=0, max_batch=4,
                           prefill_chunk_size=8, prefix_cache=True)
    t0, t1 = _grouped_tasks(2)
    for ex in (exA, exB):
        done = False
        while not done:
            _, done = ex.prefill_chunk(t0, 8)
    chunks = [0, 0]
    for i, ex in enumerate((exA, exB)):
        done = False
        while not done:
            _, done = ex.prefill_chunk(t1, 8)
            chunks[i] += 1
    assert chunks[1] < chunks[0]             # cached chunks skipped
    assert exB.prompt_progress(t1) == 24
    np.testing.assert_allclose(exB.last_prefill_logits,
                               exA.last_prefill_logits, atol=1e-5, rtol=0)
    exA.decode([t0, t1])
    exB.decode([t0, t1])
    np.testing.assert_allclose(exB.last_logits, exA.last_logits,
                               atol=1e-5, rtol=0)
    for t in (t0, t1):
        exA.release(t)
        exB.release(t)
    exB.prefix_cache.clear()
    assert exB.pool.used_pages == 0
    exB.pool.check()


def test_prefix_shared_kernel_path_matches_jnp_path(tiny_cfg):
    """The Pallas scalar-prefetch kernel reads shared pages through the
    same page-table indirection as the jnp gather — sharing must not
    perturb either engine path."""
    from repro.serving.executor import PagedJaxExecutor

    exA = PagedJaxExecutor(tiny_cfg, n_pages=16, page_size=8, max_seq=64,
                           seed=0, max_batch=2, prefix_cache=True)
    exB = PagedJaxExecutor(tiny_cfg, params=exA.params, n_pages=16,
                           page_size=8, max_seq=64, seed=0, max_batch=2,
                           prefix_cache=True, use_paged_kernel=True)
    tasks = _grouped_tasks(2, group=2)
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        np.testing.assert_allclose(exB.last_prefill_logits,
                                   exA.last_prefill_logits, atol=1e-4, rtol=0)
    for subset in ([0, 1], [1]):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        np.testing.assert_allclose(exB.last_logits, exA.last_logits,
                                   atol=1e-4, rtol=0)
    for t in tasks:
        exA.release(t)
        exB.release(t)
    for ex in (exA, exB):
        ex.prefix_cache.clear()
        assert ex.pool.used_pages == 0
        ex.pool.check()


def test_prefix_cache_eviction_under_pool_pressure(tiny_cfg):
    """A full pool evicts idle cached prefixes instead of failing: the
    cache is reclaimable headroom."""
    from repro.serving.executor import PagedJaxExecutor

    ex = PagedJaxExecutor(tiny_cfg, n_pages=8, page_size=8, max_seq=64,
                          seed=0, max_batch=4, prefix_cache=True)
    a = _grouped_tasks(1, group=1, prompt=24, prefix=16)[0]
    ex.prefill(a)                            # 3 pages, all indexed or held
    ex.release(a)                            # pages now pinned by cache only
    assert ex.pool.used_pages == 3
    b = qa_task(output_len=4, prompt_len=56)  # needs 7 pages > 5 free
    ex.prefill(b)                            # evicts cached pages to fit
    assert ex.pool.holds(b.task_id)
    ex.release(b)
    ex.prefix_cache.clear()
    assert ex.pool.used_pages == 0
    ex.pool.check()


def test_serving_loop_with_prefix_cache_no_leaks(tiny_cfg):
    """Full SLICE run over the sharing engine: everything finishes, pages
    shared during the run, pool empty after release + cache clear."""
    from repro.core.schedulers import SliceScheduler
    from repro.serving.executor import PagedJaxExecutor
    from repro.serving.loop import run_serving_loop

    ex = PagedJaxExecutor(tiny_cfg, n_pages=24, page_size=8, max_seq=64,
                          max_batch=4, prefix_cache=True)
    lat = ex.latency_model()
    assert ex.pool.used_pages == 0
    tasks = _grouped_tasks(4, prompt=24, prefix=16, out=6)
    for i, t in enumerate(tasks):
        t.arrival_ms = 1.0 * i
    res = run_serving_loop(
        SliceScheduler(lat, page_budget=ex.page_budget(),
                       prefix_hint=ex.cached_prompt_tokens), ex, tasks)
    assert all(t.finished for t in res.tasks)
    assert ex.prefix_cache.hits >= 1
    ex.prefix_cache.clear()
    assert ex.pool.used_pages == 0
    ex.pool.check()
