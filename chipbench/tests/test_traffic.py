import collections
import json
from pathlib import Path

import pytest

import traffic

MIX = json.loads((Path(__file__).resolve().parents[1] / "mixes" /
                  "slice_mix.json").read_text())


def _key(specs):
    return [(s["kind"], s["due_ms"], s["prompt_len"], s["output_len"])
            for s in specs]


def test_schedule_is_fixed():
    """Every seed gets the same classes at the same due times and the same
    lengths; the seed deals each class's lengths to its requests."""
    seed = 2**31 + 17
    a = traffic.schedule(MIX, 2.0, 30.0, seed)
    b = traffic.schedule(MIX, 2.0, 30.0, seed)
    assert _key(a) == _key(b)
    # the order is a permutation, not the quantile order
    assert [s["kind"] for s in a] != sorted(s["kind"] for s in a)
    c = traffic.schedule(MIX, 2.0, 30.0, seed + 1)
    assert _key(c) != _key(a)
    assert [(s["kind"], s["due_ms"]) for s in c] == \
        [(s["kind"], s["due_ms"]) for s in a]
    lengths = lambda xs: sorted((s["kind"], s["prompt_len"],  # noqa: E731
                                 s["output_len"]) for s in xs)
    assert lengths(c) == lengths(a)


@pytest.mark.parametrize("rate,seconds", [(0.5, 30.0), (2.0, 30.0),
                                          (1.3, 51.0)])
def test_count_shares_and_window(rate, seconds):
    specs = traffic.schedule(MIX, rate, seconds, 7)
    assert len(specs) == round(rate * seconds)
    kinds = collections.Counter(s["kind"] for s in specs)
    assert abs(kinds["control"] - 0.5 * len(specs)) <= 1
    assert abs(kinds["voice"] - kinds["qa"]) <= 1
    due = [s["due_ms"] for s in specs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < seconds * 1000
    for s in specs:
        lo, hi = s["cls"]["prompt"]
        assert lo <= s["prompt_len"] < hi
        assert s["output_len"] >= s["cls"]["output_min"]


def test_lengths_follow_the_class_laws():
    qa = next(c for c in MIX["classes"] if c["kind"] == "qa")
    pairs = traffic.class_lengths(qa, 1000)
    outs = [o for _, o in pairs]
    assert abs(sum(outs) / len(outs) - qa["output_mean"]) < 1.0
    prompts = [p for p, _ in pairs]
    assert min(prompts) == qa["prompt"][0]
    assert max(prompts) == qa["prompt"][1] - 1


def test_slos_are_absolute():
    specs = traffic.schedule(MIX, 2.0, 10.0, 7)
    for s in specs:
        t = traffic.make_task(s, 1, 5.0)
        assert t.arrival_ms == 5.0
        if s["cls"]["realtime"]:
            assert t.slo.deadline_ms == s["cls"]["deadline_ms"]
            assert t.slo.ttft_ms == pytest.approx(
                s["cls"]["deadline_ms"] * s["cls"]["ttft_share"])
        else:
            assert t.slo.tpot_ms == s["cls"]["tpot_ms"]
            assert t.slo.ttft_ms == s["cls"]["ttft_ms"]


def test_max_budget_is_the_voice_budget():
    assert traffic.max_budget_ms(MIX) == pytest.approx(2000.0 + 256 * 125.0)
