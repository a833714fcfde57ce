"""Sharding-equivalence harness (DESIGN.md §9): the tensor-parallel paged
engine must be bit-for-bit *behaviourally* identical to the single-device
one — logits < 1e-5 (the repo-wide engine contract, helpers.ATOL) for
every executor op, byte-identical greedy token streams, and the same pool
bookkeeping — across every feature composition: atomic + chunked prefill,
decode (including batch-bucket changes), speculative verify, prefix
sharing, and the suspend/resume host-swap round trip.

All tests take the session ``mesh4`` fixture (tests/conftest.py) and skip
on single-device runs, so a 1-device CI leg still collects cleanly."""
import dataclasses

import numpy as np
import pytest

from repro.core.task import qa_task

from helpers import (assert_logits_close, drive_plain, make_paged_engine,
                     reduced_cfg, sharded_test_cfg)


@pytest.fixture(scope="module")
def shard_setup(mesh4):
    """(cfg, params) pair shared by the module: MHA so KV heads shard."""
    import jax
    from repro.models import model as M

    cfg = sharded_test_cfg(ways=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pair(cfg, params, mesh, **kw):
    """(single-device oracle, sharded candidate) with shared params."""
    exA = make_paged_engine(cfg, params=params, **kw)
    exB = make_paged_engine(cfg, params=params, mesh=mesh, **kw)
    return exA, exB


# ------------------------------------------------------------ layout

def test_page_arena_sharded_over_kv_heads(mesh4, shard_setup):
    """Structural check: the arena really is split into per-device head
    slabs — each device holds Hkv/4 heads of every page, and the four
    shards cover four distinct devices (no aliasing)."""
    cfg, params = shard_setup
    exB = make_paged_engine(cfg, params=params, mesh=mesh4)
    sh = exB.pages["k_pages"].sharding
    assert sh.spec[2] == "model"
    shards = exB.pages["k_pages"].addressable_shards
    assert len(shards) == 4
    assert len({s.device for s in shards}) == 4
    L, n_pages = exB.pages["k_pages"].shape[:2]
    width = -(-cfg.head_dim // 128) * 128     # head_dim, lane-padded
    for s in shards:
        assert s.data.shape == (L, n_pages, cfg.n_kv_heads // 4,
                                exB.page_size, width)


def test_mesh_engine_inits_params_in_their_shardings(mesh4, shard_setup):
    """An engine given no params generates them directly in their
    shardings: every weight that the rules split over 'model' lands as
    four quarter-size shards (no device ever holds the full array), and
    the values are the single-device init for the same seed."""
    import jax
    from repro.launch import sharding as shard_rules

    cfg, params = shard_setup                 # PRNGKey(0) == seed 0
    exB = make_paged_engine(cfg, mesh=mesh4, seed=0)
    specs = shard_rules.param_specs(cfg, mesh4, train=False)
    got = jax.tree.leaves(exB.params)
    want = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    n_split = 0
    for g, w, spec in zip(got, want, spec_leaves):
        assert g.sharding.spec == spec
        # jit fuses the scale multiply into the draw: equal to an ulp
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=0)
        if "model" in spec:
            n_split += 1
            assert all(s.data.size * 4 == g.size
                       for s in g.addressable_shards)
    assert n_split > 0
    assert exB.pages["k_pages"].sharding.spec[2] == "model"


def test_mesh_rejects_pallas_kernel(mesh4, shard_setup):
    cfg, params = shard_setup
    with pytest.raises(ValueError, match="shard_map"):
        make_paged_engine(cfg, params=params, mesh=mesh4,
                          use_paged_kernel=True)


# ------------------------------------------------- op-level equivalence

def test_sharded_prefill_and_decode_match(mesh4, shard_setup):
    """Atomic prefill logits + a decode stream across a batch-bucket
    change (3 tasks -> 1) match the single-device engine."""
    cfg, params = shard_setup
    exA, exB = _pair(cfg, params, mesh4)
    tasks = [qa_task(prompt_len=ln, output_len=16) for ln in (5, 23, 17)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits,
                            err_msg=f"prefill {t.task_id}")
    for step in range(4):
        live = tasks if step < 2 else tasks[:1]     # bucket 4 -> 1
        exA.decode(live)
        exB.decode(live)
        assert_logits_close(exB.last_logits, exA.last_logits,
                            err_msg=f"decode step {step}")
    for t in tasks:
        exA.release(t)
        exB.release(t)
    exB.pool.check()
    assert exB.pool.used_pages == 0


def test_sharded_chunked_prefill_matches(mesh4, shard_setup):
    """prefill_chunk_paged under sharding == monolithic single-device
    prefill, chunk boundaries and all."""
    cfg, params = shard_setup
    exA = make_paged_engine(cfg, params=params)
    exB = make_paged_engine(cfg, params=params, mesh=mesh4,
                            prefill_chunk_size=8)
    t = qa_task(prompt_len=21, output_len=8)
    exA.prefill(t)
    done = False
    while not done:
        _, done = exB.prefill_chunk(t, 8)
    assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    exA.decode([t])
    exB.decode([t])
    assert_logits_close(exB.last_logits, exA.last_logits)


def test_sharded_spec_verify_stream_matches(mesh4, shard_setup):
    """Speculative decode (verify_step_paged) under sharding: greedy
    streams across a cycle of ragged depths == plain single-device decode
    (the draft model itself stays single-device by design)."""
    cfg, params = shard_setup
    exA, exB = _pair(cfg, params, mesh4, n_pages=32, max_seq=96,
                     spec_decode=True, draft_cfg=cfg, draft_params=params,
                     max_spec_depth=4)
    tasks = [qa_task(prompt_len=11, output_len=32) for _ in range(3)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
    cycle = [[4, 0, 2], [1, 3, 0], [2, 2, 2]]
    for it in range(6):
        d = cycle[it % len(cycle)]
        exA.decode(tasks, depths=d)
        exB.decode(tasks, depths=d)
        exB.pool.check()
    for t in tasks:
        assert exA.generated_tokens(t) == exB.generated_tokens(t), t.task_id
    assert exB.accepted_tokens > 0


def test_sharded_suspend_resume_roundtrip_matches(mesh4, shard_setup):
    """suspend gathers per-device slabs to one host blob; resume scatters
    it back across the mesh. Decode across the round trip must match the
    never-suspended single-device engine, with zero leaks either side."""
    cfg, params = shard_setup
    exA, exB = _pair(cfg, params, mesh4)
    tasks = [qa_task(prompt_len=18, output_len=8) for _ in range(2)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)

    def step(subset):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
        assert_logits_close(exB.last_logits, exA.last_logits)

    step([0, 1])
    exB.suspend(tasks[0])
    assert exB.arena.bytes_held > 0
    step([1])
    exB.resume(tasks[0])
    # the restored pages must carry canonical sharding — a replicated
    # scatter result would silently break the AOT input contract
    assert exB.pages["k_pages"].sharding.spec[2] == "model"
    step([0, 1])
    for t in tasks:
        exA.release(t)
        exB.release(t)
    exB.pool.check()
    assert exB.pool.used_pages == 0
    assert exB.arena.bytes_held == 0


def test_sharded_prefix_sharing_composes(mesh4, shard_setup):
    """Prefix cache hit under sharding: the second sharer's suffix prefill
    rides replicated page tables over sharded slabs and still matches."""
    cfg, params = shard_setup
    exA, exB = _pair(cfg, params, mesh4, n_pages=32, max_seq=96,
                     prefix_cache=True)
    tasks = []
    for _ in range(2):
        t = qa_task(prompt_len=20, output_len=8)
        t.prefix_group, t.prefix_len = 9, 16
        tasks.append(t)
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
        assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    assert exB.pool.used_pages == exA.pool.used_pages  # pages shared alike
    for _ in range(3):
        exA.decode(tasks)
        exB.decode(tasks)
        assert_logits_close(exB.last_logits, exA.last_logits)
    for t in tasks:
        exA.release(t)
        exB.release(t)
    exB.prefix_cache.clear()
    exB.pool.check()
    assert exB.pool.used_pages == 0


def test_gqa_fallback_replicated_pages_still_match(mesh4):
    """n_kv_heads=1 over a 4-way axis: page_specs falls back to replicated
    slabs (divisibility rule). The engine must still run and match — the
    fallback degrades layout, never correctness."""
    import jax
    from repro.models import model as M

    cfg = reduced_cfg()                   # GQA: n_kv_heads == 1
    assert cfg.n_kv_heads == 1
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    exA, exB = _pair(cfg, params, mesh4)
    assert exB.pages["k_pages"].sharding.spec[2] is None
    t = qa_task(prompt_len=13, output_len=8)
    exA.prefill(t)
    exB.prefill(t)
    for _ in range(3):
        exA.decode([t])
        exB.decode([t])
        assert_logits_close(exB.last_logits, exA.last_logits)


def test_two_way_mesh_matches(shard_setup):
    """A (1, 2) mesh built from the same forced device pool: divisibility
    4 % 2 == 0 holds, so heads shard 2-way and equivalence must hold."""
    import jax
    from repro.launch.mesh import make_serving_mesh

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg, params = shard_setup
    mesh2 = make_serving_mesh(model=2)
    exA, exB = _pair(cfg, params, mesh2)
    t = qa_task(prompt_len=9, output_len=8)
    exA.prefill(t)
    exB.prefill(t)
    assert_logits_close(exB.last_prefill_logits, exA.last_prefill_logits)
    exA.decode([t])
    exB.decode([t])
    assert_logits_close(exB.last_logits, exA.last_logits)


# -------------------------------------- satellite: depth-0 sync path

def test_sharded_depth0_byte_identical_to_plain_decode(mesh4, shard_setup):
    """depths=[0,...] and depths=None must hit the SAME sync decode path
    under sharding — byte-identical logits (np.array_equal, not atol),
    mirroring the single-device regression. The perf gates assume the
    sync path never silently reroutes through the verify kernel."""
    cfg, params = shard_setup
    ex0 = make_paged_engine(cfg, params=params, mesh=mesh4, n_pages=32,
                            max_seq=96, spec_decode=True, draft_cfg=cfg,
                            draft_params=params, max_spec_depth=4)
    ex1 = make_paged_engine(cfg, params=params, mesh=mesh4, n_pages=32,
                            max_seq=96)
    tasks = [qa_task(prompt_len=11, output_len=16) for _ in range(2)]
    for t in tasks:
        ex0.prefill(t)
        ex1.prefill(t)
    assert np.array_equal(ex0.last_prefill_logits, ex1.last_prefill_logits)
    for _ in range(3):
        ex0.decode(tasks, depths=[0, 0])
        ex1.decode(tasks, depths=None)
        assert np.array_equal(ex0.last_logits, ex1.last_logits)
        assert ex0.last_commits == [1, 1]


def test_sharded_greedy_streams_byte_identical(mesh4, shard_setup):
    """End-to-end: greedy token streams (argmax chains through 8 decode
    steps) are exactly equal — the integer-level consequence of the
    logits contract, and what users actually observe."""
    cfg, params = shard_setup
    exA, exB = _pair(cfg, params, mesh4)
    tasks = [qa_task(prompt_len=ln, output_len=10) for ln in (7, 15)]
    for t in tasks:
        exA.prefill(t)
        exB.prefill(t)
    assert drive_plain(exA, tasks, 8) == drive_plain(exB, tasks, 8)
