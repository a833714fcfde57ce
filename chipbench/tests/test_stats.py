import math
from types import SimpleNamespace as NS

import stats


def task(arrival, times, out_len, realtime=False, ttft=2000.0, tpot=100.0,
         deadline=None, dropped=False):
    slo = NS(realtime=realtime, ttft_ms=ttft, tpot_ms=tpot,
             deadline_ms=deadline)
    return NS(arrival_ms=arrival, token_times_ms=list(times),
              output_len=out_len, dropped=dropped, slo=slo)


def test_unserved_request_is_in_the_tail_not_dropped():
    ts = [task(0, [100 * (i + 1), 100 * (i + 2)], 2) for i in range(9)]
    ts.append(task(0, [], 2))                       # never got a token
    xs = [stats.ttft_ms(t, 5000.0) for t in ts]
    assert xs[-1] == 5000.0                         # its wait so far
    assert math.isinf(stats.ttft_ms(ts[-1]))
    assert stats.percentile(xs, 50) == 550.0
    assert stats.percentile(xs, 100) == 5000.0      # the tail is unserved
    assert stats.failed(ts) == 1


def test_attainment_counts_failures_as_misses():
    ok = task(0, [100, 200, 300], 3)
    slow = task(0, [100, 400, 700], 3)              # TPOT 300 > 100
    late = task(0, [2500, 2550, 2600], 3)           # TTFT 2500 > 2000
    short = task(0, [100, 200], 3)                  # unfinished
    gone = task(0, [100, 200, 300], 3, dropped=True)
    rt_ok = task(0, [50, 100], 2, realtime=True, deadline=1500.0)
    rt_late = task(0, [50, 1600], 2, realtime=True, deadline=1500.0)
    ts = [ok, slow, late, short, gone, rt_ok, rt_late]
    assert [stats.slo_met(t) for t in ts] == [True, False, False, False,
                                               False, True, False]
    assert stats.attainment_pct(ts) == 100.0 * 2 / 7
    assert stats.failed(ts) == 2


def test_tpot_excludes_the_first_gap():
    t = task(0, [1000, 1500, 1600, 1700], 4)
    assert stats.tpot_ms(t) == 100.0
    assert stats.tpot_ms(task(0, [10, 30], 2)) == 20.0
    assert stats.tpot_ms(task(0, [10], 1)) is None


def test_percentile_is_numpy_linear():
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 90) == 3.7
    assert stats.percentile([], 90) is None


def test_token_gaps_are_the_steady_state_gaps():
    t = task(0, [1000, 1500, 1600, 1750], 4)
    assert stats.token_gaps(t, 9000.0) == [100, 150]       # first gap left out
    cut = task(0, [1000, 1500, 1600], 5)                   # unfinished
    assert stats.token_gaps(cut, 2000.0) == [100, 400]     # the wait so far
    assert stats.token_gaps(cut) == [100]
    assert stats.token_gaps(task(0, [10], 1), 50.0) == []


def test_tpot_tail_is_over_every_token_gap():
    import importlib.util
    from pathlib import Path
    path = Path(stats.__file__).parent / "e2e" / "tpot_p90_ms.py"
    spec = importlib.util.spec_from_file_location("tpot_p90_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    long = task(0, [100] + [200 + 10 * i for i in range(101)], 102)
    short = task(0, [100, 200, 500], 3)                    # one gap of 300
    run = NS(sent=[long, short], end_ms=5000.0)
    # 100 gaps of 10 and one of 300: the tail is the many short gaps'
    assert mod.read(run) == 10.0
    assert stats.percentile([stats.tpot_ms(long), stats.tpot_ms(short)],
                            90) > 250
