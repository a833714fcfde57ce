import jax
import pytest

import devtrace
from devtrace import Ev, TraceView


def ev(name, a, b):
    return Ev(name, float(a), float(b - a))


def small_view():
    devices = {
        0: [ev("%paged_decode_attention.4 = f32[2] custom-call(x)", 120, 170),
            ev("%fusion.1 = f32[2] fusion(y)", 160, 250),
            ev("%flash_prefill_chunk.7 = f32[2] custom-call(z)", 420, 500),
            ev("%all-reduce.3 = f32[2] all-reduce(w)", 510, 530),
            ev("%copy.9 = f32[2] copy(v)", -50, 10)],
        1: [ev("%fusion.1 = f32[2] fusion(y)", 130, 230)],
    }
    host = [ev("window.0", 0, 1000), ev("decode.1", 100, 300),
            ev("schedule.2", 300, 320), ev("prefill_chunk.3", 400, 700),
            ev("wait.4", 720, 990), ev("decode.5", 995, 1200),
            ev("PjitFunction(step)", 110, 290)]
    return TraceView(devices, [e for e in host
                               if devtrace.span_kind(e.name) is not None])


def test_busy_is_the_union_clipped_to_the_window():
    v = small_view()
    assert v.window_ns == 1000
    assert v.busy_ns(0) == 10 + 130 + 80 + 20
    assert v.busy_ns(1) == 100
    assert v.mean_busy_ns() == 170
    assert v.chips == 2


def test_steps_hold_the_ops_inside_them():
    v = small_view()
    kinds = [(s.kind, s.index) for s in v.steps]
    assert kinds == [("decode", 1), ("prefill_chunk", 3)]   # decode.5 ends
    dec, pre = v.steps                                       # past the window
    assert [devtrace.op_base(e.name) for e in dec.ops[0]] == \
        ["paged_decode_attention", "fusion"]
    assert TraceView.span_busy_ns(dec) == (130 + 100) / 2
    assert TraceView.span_op_ns(
        dec, lambda n: n == "paged_decode_attention") == 50 / 2
    assert TraceView.span_op_ns(
        pre, lambda n: n == "flash_prefill_chunk") == 80
    assert TraceView.span_op_ns(
        pre, lambda n: any(c in n for c in devtrace.COLLECTIVES)) == 20


def test_idle_gaps_are_labelled_by_host_activity():
    gaps = small_view().idle_gaps(10, dev=0)
    assert gaps[0] == ["wait", 470 / 1e9]
    assert ["host_other", 170 / 1e9] in gaps        # 250..420: none holds it
    assert ["prefill_chunk", 10 / 1e9] in gaps      # 500..510
    assert len(gaps) == 4


def test_top_ops_by_time():
    top = small_view().top_ops(2)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(
        (90 + 100) / 2 / 1e9)


def test_op_base_and_span_kind():
    assert devtrace.op_base("%copy.56 = f32[2] copy(%x)") == "copy"
    assert devtrace.op_base("%paged_decode_attention.4 = ...") == \
        "paged_decode_attention"
    assert devtrace.span_kind("prefill_chunk.17") == ("prefill_chunk", 17)
    assert devtrace.span_kind("decode") is None
    assert devtrace.span_kind("jit_step(123)") is None


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        TraceView({0: []}, [ev("decode.1", 0, 1)])


def test_load_reads_annotations_from_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window.0"):
        with jax.profiler.TraceAnnotation("decode.1"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("unrelated"):
            pass
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    assert path is not None
    devices, host = devtrace.load(path)
    assert devices == {}                    # no TPU on this machine
    names = sorted(e.name for e in host)
    assert names == ["decode.1", "window.0"]
    w = next(e for e in host if e.name == "window.0")
    d = next(e for e in host if e.name == "decode.1")
    assert w.start <= d.start and d.end <= w.end


def recorded_view():
    """One prefill chunk call (four pieces) and the decode step after it,
    recorded on a TPU v5 lite chip serving smollm-360m.slice_mix."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).parent / "data" / "v5e_steps.json.gz"
    fx = json.loads(gzip.open(path, "rt").read())
    lo, hi = fx["window"]
    host = [Ev("window.0", lo, hi - lo)] + [Ev(*h) for h in fx["host"]]
    return TraceView({0: [Ev(*o) for o in fx["ops"]]}, host)


def test_recorded_trace_attributes_kernels_to_their_steps():
    v = recorded_view()
    assert [(s.kind, s.index) for s in v.steps] == [("prefill_chunk", 320),
                                                     ("decode", 322)]
    chunk, dec = v.steps
    count = lambda s, n: sum(devtrace.op_base(e.name) == n  # noqa: E731
                             for e in s.ops[0])
    assert count(dec, "paged_decode_attention") == 32       # one per layer
    assert count(chunk, "flash_prefill_chunk") == 4 * 32    # four pieces
    assert count(dec, "flash_prefill_chunk") == 0
    for s in v.steps:
        assert 0 < TraceView.span_busy_ns(s) <= s.end - s.start
    assert 0 < v.mean_busy_ns() <= v.window_ns
    assert all(name != "while" for name, _ in v.top_ops(10))


def test_readers_on_the_recorded_trace():
    import json
    from pathlib import Path
    import layer
    from references.llama_dense import Arch
    here = Path(__file__).resolve().parents[1]
    cfg = json.loads((here / "configs" / "smollm-360m.json").read_text())
    v = recorded_view()
    chunk, dec = v.steps
    calls = {chunk.index: ("prefill_chunk", [(0, 128), (128, 64), (192, 32),
                                             (224, 16)]),
             dec.index: ("decode", [300] * 20)}
    ctx = layer.Ctx(trace=v, calls=calls, arch=Arch.from_config(cfg),
                    peak_flops=197e12, peak_bw=819e9, schedule_ms=10.0,
                    actions=4)
    for name in ("paged_attn_roofline", "flash_prefill_roofline",
                 "decode_step_mfu", "prefill_chunk_mfu", "step_mfu",
                 "device_idle_share", "decode_step_device_ms"):
        x = layer.reader("metrics", name)(ctx)
        assert x is not None and 0 < x < 100, (name, x)
    assert layer.reader("metrics", "sched_host_ms_per_step")(ctx) == 2.5
    assert layer.reader("metrics", "collective_share")(ctx) is None
