"""End-to-end serving tests: static Table II reproduction, dynamic workloads,
and the real-JAX-engine path."""
import numpy as np
import pytest

from repro.core.latency_model import paper_fig1_model
from repro.core.schedulers import (FastServeScheduler, OrcaScheduler,
                                   SliceScheduler, sjf_decay_adaptor)
from repro.data.workload import poisson_workload, static_table2_workload
from repro.serving.executor import SimExecutor
from repro.serving.loop import run_serving_loop
from repro.serving.metrics import per_kind_tpot, summarize

LAT = paper_fig1_model()


def _run(scheduler, tasks):
    return run_serving_loop(scheduler, SimExecutor(LAT), tasks)


def test_static_table2_slice_meets_all():
    """Paper Table II: SLICE achieves 100% SLO attainment on the 9-task mix."""
    tasks = static_table2_workload()
    res = _run(SliceScheduler(LAT), tasks)
    rows = per_kind_tpot(res.tasks)
    for kind in ("A", "B", "C"):
        assert rows[kind]["tpot_satisfied"], (kind, rows[kind])
    s = summarize(res.tasks)["all"]
    assert s.slo == 1.0, rows


@pytest.mark.parametrize("sched_cls", [OrcaScheduler, FastServeScheduler])
def test_static_table2_baselines_violate(sched_cls):
    """Orca/FastServe batch all 9 tasks -> uniform TPOT ~ l(9) = 128.6 ms:
    A (100 ms) and B (120 ms) violate, C (250 ms) meets -> 2/9 ~ 22%."""
    tasks = static_table2_workload()
    res = _run(sched_cls(), tasks)
    rows = per_kind_tpot(res.tasks)
    assert not rows["A"]["tpot_satisfied"]
    assert not rows["B"]["tpot_satisfied"]
    assert rows["C"]["tpot_satisfied"]
    s = summarize(res.tasks)["all"]
    assert s.slo == pytest.approx(2.0 / 9.0, abs=0.01)
    # uniform decode rate ~ l(9)
    assert rows["A"]["actual_tpot_ms"] == pytest.approx(128.6, rel=0.05)
    assert rows["A"]["actual_tpot_ms"] == pytest.approx(
        rows["C"]["actual_tpot_ms"], rel=0.05)


def test_dynamic_slice_beats_baselines():
    """Paper Fig. 7: at arrival rate ~1, 7:3 RT mix, SLICE >> Orca/FastServe."""
    results = {}
    for name, mk in [("slice", lambda: SliceScheduler(LAT)),
                     ("orca", OrcaScheduler),
                     ("fastserve", FastServeScheduler)]:
        tasks = poisson_workload(rate_per_s=1.5, duration_s=90, seed=7)
        res = _run(mk(), tasks)
        results[name] = summarize(res.tasks)
    assert results["slice"]["all"].slo > results["orca"]["all"].slo
    assert results["slice"]["all"].slo > results["fastserve"]["all"].slo
    assert results["slice"]["realtime"].slo >= 0.8
    # baselines: RT tasks suffer (paper: ~26% deadline attainment at rate 1)
    assert results["orca"]["realtime"].slo < results["slice"]["realtime"].slo


def test_slice_decode_level_rate_differentiation():
    """SLICE allocates distinct rates per SLO class (Fig. 6): actual TPOT of
    a lax-SLO task must exceed that of a strict-SLO task (it decodes less
    often), while both meet their own SLOs."""
    tasks = static_table2_workload()
    res = _run(SliceScheduler(LAT), tasks)
    rows = per_kind_tpot(res.tasks)
    assert (rows["C"]["actual_tpot_ms"] > rows["B"]["actual_tpot_ms"]
            > rows["A"]["actual_tpot_ms"])
    assert rows["C"]["actual_tpot_ms"] > rows["A"]["actual_tpot_ms"] * 1.2
    # and matches the paper's Table II SLICE row within ~10%
    assert rows["A"]["actual_tpot_ms"] == pytest.approx(94.03, rel=0.10)
    assert rows["B"]["actual_tpot_ms"] == pytest.approx(106.65, rel=0.10)
    assert rows["C"]["actual_tpot_ms"] == pytest.approx(121.11, rel=0.10)


def test_slice_under_overload_prioritizes_realtime():
    """Paper Fig. 11a: under heavy load SLICE keeps RT attainment high by
    spending its budget on high-utility RT tasks."""
    tasks = poisson_workload(rate_per_s=3.0, duration_s=60, seed=3)
    res = _run(SliceScheduler(LAT), tasks)
    s = summarize(res.tasks)
    assert s["realtime"].slo > 0.7
    assert s["realtime"].slo > s["non_realtime"].slo


def test_sjf_adaptor_runs():
    tasks = poisson_workload(rate_per_s=1.0, duration_s=20, seed=1)
    res = _run(SliceScheduler(LAT, utility_adaptor=sjf_decay_adaptor()), tasks)
    assert summarize(res.tasks)["all"].n == len(tasks)


def test_loop_conservation():
    """Every finished task has exactly output_len token timestamps, strictly
    increasing, all after arrival."""
    tasks = poisson_workload(rate_per_s=0.8, duration_s=30, seed=5)
    res = _run(SliceScheduler(LAT), tasks)
    for t in res.tasks:
        if t.finished:
            assert len(t.token_times_ms) == t.output_len
            tt = np.asarray(t.token_times_ms)
            assert (np.diff(tt) > 0).all()
            assert tt[0] >= t.arrival_ms


def test_jax_executor_end_to_end():
    """Real engine: tiny model, SLICE schedules real decode steps."""
    from helpers import make_slot_engine, reduced_cfg
    from repro.core.task import qa_task, control_task

    cfg = reduced_cfg()
    ex = make_slot_engine(cfg, max_seq=128)
    lat = ex.latency_model()
    tasks = [control_task(output_len=6, prompt_len=12),
             qa_task(arrival_ms=1.0, output_len=8, prompt_len=16),
             qa_task(arrival_ms=2.0, output_len=8, prompt_len=16)]
    res = run_serving_loop(SliceScheduler(lat), ex, tasks)
    assert all(t.finished for t in res.tasks)
    assert res.decode_iterations > 0
    s = summarize(res.tasks)["all"]
    assert s.n == 3


def test_paged_executor_end_to_end():
    """Real paged engine through the full serving loop (mode follows the
    REPRO_ASYNC_PIPELINE matrix leg): every task finishes and the
    LoopResult gap breakdown is populated from the engine's GapStats."""
    from helpers import make_paged_engine, reduced_cfg
    from repro.core.task import qa_task, control_task

    cfg = reduced_cfg()
    ex = make_paged_engine(cfg, n_pages=64, max_seq=128)
    lat = ex.latency_model()
    tasks = [control_task(output_len=6, prompt_len=12),
             qa_task(arrival_ms=1.0, output_len=8, prompt_len=16),
             qa_task(arrival_ms=2.0, output_len=8, prompt_len=16)]
    res = run_serving_loop(
        SliceScheduler(lat, page_budget=ex.page_budget()), ex, tasks)
    assert all(t.finished for t in res.tasks)
    assert res.decode_iterations > 0
    # the gap breakdown is measured, not defaulted: real decode cycles
    # must book host time somewhere (dispatch in async mode, wait in sync)
    assert res.dispatch_ms + res.wait_ms > 0.0
    assert ex.gap_stats.cycles > 0
    if ex.async_dispatch:
        assert len(ex._queue) == 0      # loop drained the pipeline


def test_jax_executor_compaction_matches_masked():
    """Bucketed compaction (gather->decode->scatter) must produce the same
    engine state evolution as masked full-array decode."""
    from helpers import assert_logits_close, make_slot_engine, reduced_cfg
    from repro.core.task import qa_task

    cfg = reduced_cfg()
    exA = make_slot_engine(cfg, compact_buckets=False)
    exB = make_slot_engine(cfg, compact_buckets=True)
    tasks = [qa_task(output_len=6, prompt_len=8) for _ in range(3)]
    for ex in (exA, exB):
        for t in tasks:
            ex.prefill(t)
    # decode irregular subsets (mask columns)
    for subset in ([0], [0, 2], [1], [0, 1, 2], [2]):
        exA.decode([tasks[i] for i in subset])
        exB.decode([tasks[i] for i in subset])
    np.testing.assert_array_equal(exA.cache["length"], exB.cache["length"])
    # the repo-wide engine contract (helpers.ATOL): compaction reorders
    # the f32 reductions, so equality holds to 1e-5, not bit-for-bit
    assert_logits_close(exA.cache["k"], exB.cache["k"])
    np.testing.assert_array_equal(exA.tokens, exB.tokens)


def test_serve_main_returns_what_it_served(monkeypatch, tmp_path):
    """serve.main(argv) runs the CLI path in-process and hands back its
    engines and tasks (chip_smoke.py checks them the same way). With
    JAX_COMPILATION_CACHE_DIR set it leaves the cache to JAX."""
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # Orca: SLICE prices its cycles with l(b) probed on this host, and on
    # a loaded host a noisy probe can leave every request unschedulable;
    # this test is about what main returns, not about the policy
    run = serve.main(["--executor", "paged", "--scheduler", "orca",
                      "--slots", "2", "--max-seq", "64", "--rate", "2",
                      "--duration", "1"])
    assert run.result.tasks
    assert all(t.finished for t in run.result.tasks)
    assert run.summary["all"].n == len(run.result.tasks)
    (ex,) = run.executors
    assert ex.cfg.name.endswith("-smoke")        # --reduced is the default
    ex.pool.check()
    assert ex.pool.used_pages == 0


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    import jax

    from repro.launch import serve

    assert (serve.CHECKOUT / "src" / "repro" / "launch" / "serve.py").is_file()
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        serve.configure_compile_cache(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        serve.configure_compile_cache(tmp_path)
        assert (jax.config.jax_compilation_cache_dir
                == str(tmp_path / ".jax_cache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
