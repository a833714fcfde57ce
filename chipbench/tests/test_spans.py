"""The readers of the program's ``slice/`` spans (``spans.py``) and the
three metrics that read them: on a synthetic trace, on a CPU profiler
trace found the way a traced run finds its own, on a chip trace cut with
``spans.py --cut``, and on a trace without program spans."""
import gzip
import json
from pathlib import Path

import jax
import pytest

import devtrace
import layer
import spans
from devtrace import Ev, TraceView
from spans import PSpan

DATA = Path(__file__).parent / "data"
METRICS = ("exec_host_ms_per_step", "chunk_piece_device_ms",
           "decode_sync_idle_ms")


def ev(name, a, b):
    return Ev(name, float(a), float(b - a))


def ps(name, t0, t1, /, **args):
    return PSpan(name, float(t0), float(t1 - t0), args)


def ctx_of(view):
    return layer.Ctx(trace=view, calls={}, arch=None, peak_flops=1.0,
                     peak_bw=1.0, schedule_ms=0.0, actions=0)


def small():
    """Window 0..10000 ns: a chunk step (one piece) then a decode step,
    and an idle poll with no action."""
    devices = {0: [ev("%flash_prefill_chunk.1 = x", 1300, 2300),
                   ev("%fusion.2 = y", 2250, 2400),
                   ev("%paged_decode_attention.3 = z", 5200, 5600),
                   ev("%fusion.4 = w", 6000, 6500)],
               1: [ev("%fusion.2 = y", 1200, 2200),
                   ev("%fusion.4 = w", 5100, 6700)]}
    view = TraceView(devices, [ev("window.0", 0, 10000)])
    prog = [ps("slice/step", 1000, 3000, step=0, action="prefill_chunk"),
            ps("slice/schedule", 1000, 1040, replanned=1,
               rt_slack_min_ms=12.5),
            ps("slice/prefill_chunk/piece", 1050, 2900, start=0, n=64,
               bucket=64),
            ps("slice/prefill_chunk/piece/prep", 1060, 1160),
            ps("slice/prefill_chunk/piece/launch", 1160, 1200),
            ps("slice/prefill_chunk/piece/sync", 1200, 2450),
            ps("slice/step", 5000, 7000, step=1, action="decode"),
            ps("slice/schedule", 5000, 5005, replanned=0,
               rt_slack_min_ms=8.0),
            ps("slice/decode", 5010, 6990, b=2, rows=2, ctx_max=70),
            ps("slice/decode/prep", 5010, 5060),
            ps("slice/decode/launch", 5060, 5100),
            ps("slice/decode/sync", 5100, 6900),
            ps("slice/decode/readback", 6900, 6980),
            ps("slice/step", 8000, 8010, step=2),
            ps("slice/schedule", 8000, 8004, replanned=1),
            ps("slice/drop", 8005, 8005, task=7, reason="deadline",
               slack_ms=-3.5),
            ps("slice/schedule", 11000, 11004, replanned=1,
               rt_slack_min_ms=-1.0),
            ps("slice/drop", 11005, 11005, task=8, reason="pages",
               pages=9)]
    spans.attach(view, prog)
    return view


def test_readers_on_a_synthetic_trace():
    view = small()
    got = {m: layer.reader("metrics", m)(ctx_of(view)) for m in METRICS}
    # host phases: piece prep 100 + launch 40; decode prep 50 + launch
    # 40 + readback 80; over the two steps that ran an action
    assert got["exec_host_ms_per_step"] == pytest.approx(310 / 2 / 1e6)
    # device 0 busy 1300..2400 inside the piece (1100), device 1 1200..2200
    assert got["chunk_piece_device_ms"] == pytest.approx(
        (1100 + 1000) / 2 / 1e6)
    # sync 5100..6900: device 0 idle 1800 - 900, device 1 1800 - 1600
    assert got["decode_sync_idle_ms"] == pytest.approx(
        (900 + 200) / 2 / 1e6)


def test_plan_and_drops_on_a_synthetic_trace():
    """Replans and the least real-time slack, in the stretch and in the
    whole trace, and the drops by reason."""
    view = small()
    prog = view.program
    assert spans.plan(spans.in_window(view, prog, "slice/schedule")) == {
        "schedules": 3, "replans": 2, "rt_slack_min_ms": 8.0}
    assert spans.plan(prog) == {"schedules": 4, "replans": 3,
                                "rt_slack_min_ms": -1.0}
    assert spans.plan([]) == {"schedules": 0, "replans": 0,
                              "rt_slack_min_ms": None}
    assert spans.drops_by_reason(prog) == {"deadline": 1, "pages": 1}


def recorded_view(name):
    fx = json.loads(gzip.open(DATA / name, "rt").read())
    lo, hi = fx["window"]
    host = [Ev("window.0", lo, hi - lo)] + [Ev(*h) for h in fx["host"]]
    view = TraceView({0: [Ev(*o) for o in fx["ops"]]}, host)
    return view, fx


def test_readers_read_none_without_program_spans(tmp_path, monkeypatch):
    view, _ = recorded_view("v5e_steps.json.gz")
    monkeypatch.setattr("sys.argv", ["run.py", "--trace-dir",
                                     str(tmp_path)])
    for m in METRICS:
        assert layer.reader("metrics", m)(ctx_of(view)) is None
    assert view.program == []
    for m in METRICS:
        assert layer.reader("metrics", m)(ctx_of(None)) is None


def test_a_run_reads_the_spans_of_its_own_trace(tmp_path, monkeypatch):
    """The readers find the trace under run.py's --trace-dir, and only a
    trace whose window annotation is the view's own."""
    from repro.serving.trace import span
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window.0"):
        with span("step", lambda: {"step": 0}) as sp:
            with span("decode"):
                with span("decode/sync"):
                    pass
            sp.set(lambda: {"action": "decode"})
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    _, host = devtrace.load(path)
    w = next(e for e in host if e.name == "window.0")

    def view_at(lo):
        return TraceView({0: [ev("%fusion.1 = x", lo, lo + 1)]},
                         [ev("window.0", lo, lo + w.dur)])

    monkeypatch.setattr("sys.argv", ["run.py", "--trace", "1",
                                     f"--trace-dir={tmp_path}"])
    assert spans.run_trace_dir() == tmp_path
    got = spans.program(view_at(w.start))
    assert [s.name for s in got] == ["slice/step", "slice/decode",
                                     "slice/decode/sync"]
    assert got[0].args == {"step": 0, "action": "decode"}
    assert spans.program(view_at(w.start + 1)) is None
    assert spans.run_trace_dir([]) == spans.HERE / "out" / "trace"


def test_readers_on_a_recorded_chip_trace():
    """The last piece of a chunk step and the two decode steps after it,
    recorded on a TPU v5 lite chip serving smollm-360m.slice_mix with
    the program's spans (``spans.py --cut``): each phase nests in its
    span and the readers read numbers within the spans' own lengths."""
    view, fx = recorded_view("v5e_program_steps.json.gz")
    prog = [PSpan(*p) for p in fx["program"]]
    spans.attach(view, prog)
    steps = [s for s in prog
             if s.name == "slice/step" and "action" in s.args]
    assert [s.args["action"] for s in steps] == ["decode", "decode"]
    decodes = spans.in_window(view, prog, "slice/decode")
    (piece,) = spans.in_window(view, prog, "slice/prefill_chunk/piece")
    assert len(decodes) == 2
    for d in decodes:
        for child in ("prep", "launch", "sync", "readback"):
            assert len(spans.inside(prog, d, (f"slice/decode/{child}",))) \
                == 1
    for child in ("prep", "launch", "sync"):
        assert len(spans.inside(
            prog, piece, (f"slice/prefill_chunk/piece/{child}",))) == 1
    got = {m: layer.reader("metrics", m)(ctx_of(view)) for m in METRICS}
    host = sum(s.dur for st in steps
               for s in spans.inside(prog, st, spans.DECODE_HOST))
    assert got["exec_host_ms_per_step"] == pytest.approx(host / 2 / 1e6)
    assert 0 < got["chunk_piece_device_ms"] <= piece.dur / 1e6
    sync = [s for d in decodes
            for s in spans.inside(prog, d, ("slice/decode/sync",))]
    assert 0 <= got["decode_sync_idle_ms"] <= max(s.dur for s in sync) / 1e6
