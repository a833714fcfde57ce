"""A run with the served path broken underneath must come out not correct.

Each case drives the whole of a run at the configuration's reduced size on
the CPU (the harness's look for a chip is skipped; the Pallas kernels are
interpreted) with one fault planted in the program's timed path, and
checks that the comparison with the reference catches it. The first case
plants nothing and must come out correct. A fault must also fail the
limit the cell holds on the chip, not only the rehearsal's tighter one.
The mesh cases use the four-chip configuration, whose cell is not in
BENCHMARK.json yet, on four virtual CPU devices.
"""
import json
import time

import jax
import jax.numpy as jnp
import pytest

import cell as cellmod
import harness


class NoFault:
    pass


class TokenAltered:
    """Each decode step's token is changed where the step produces it."""

    def on_executor(self, ex):
        V = ex.cfg.vocab_size
        for b, fn in list(ex._step_jit.items()):
            def step(*a, fn=fn):
                logits, am, ln, pages = fn(*a)
                return logits, (am + 1) % V, ln, pages
            ex._step_jit[b] = step


class StateUnchanged:
    """Each decode step hands back the page arena it was given: the KV it
    computed is never written."""

    def on_executor(self, ex):
        for b, fn in list(ex._step_jit.items()):
            def step(*a, fn=fn):
                logits, am, ln, _ = fn(*a)
                return logits, am, ln, a[1]
            ex._step_jit[b] = step


class HalfBatch:
    """The second half of each decode batch is left out: its rows get the
    first half's tokens."""

    def on_executor(self, ex):
        for b, fn in list(ex._step_jit.items()):
            def step(*a, fn=fn, b=b):
                logits, am, ln, pages = fn(*a)
                return logits, jnp.concatenate([am[: b // 2],
                                                am[: b - b // 2]]), ln, pages
            ex._step_jit[b] = step


class ExchangeLeftOut:
    """The all-reduce after each row-parallel matrix is left out: every
    chip keeps only its own partial sum, as if the other three chips'
    rows of the attention output and MLP down projections were zero."""

    def on_params(self, params, arch):
        def cut(w):
            w = dict(w, blocks=dict(w["blocks"]))
            for k in ("wo", "wd"):
                x = w["blocks"][k]
                keep = x.shape[1] // 4
                w["blocks"][k] = x.at[:, keep:, :].set(0.0)
            return w
        return jax.jit(cut)(params)


def load(workload):
    if workload != "yi-6b-tp4.slice_mix":
        return cellmod.load(workload)
    config = json.loads((cellmod.HERE / "configs" / "yi-6b-tp4.json")
                        .read_text())
    mix = json.loads((cellmod.HERE / "mixes" / "slice_mix.json").read_text())
    return cellmod.Cell(name=workload, chips=4, config=config, mix=mix,
                        own={}, end_to_end=[], per_layer=[])


CASES = [("smollm-360m.slice_mix", NoFault, True),
         ("smollm-360m.slice_mix", TokenAltered, False),
         ("smollm-360m.slice_mix", StateUnchanged, False),
         ("smollm-360m.slice_mix", HalfBatch, False),
         ("yi-6b-tp4.slice_mix", NoFault, True),
         ("yi-6b-tp4.slice_mix", ExchangeLeftOut, False)]


@pytest.mark.parametrize("workload,fault,want", CASES,
                         ids=[f"{w.split('.')[0]}-{f.__name__}"
                              for w, f, _ in CASES])
def test_fault_is_caught(workload, fault, want):
    c = load(workload)
    run = harness.run_cell(c, 2**31 + 5, 3.0, None, time.perf_counter(),
                           rehearse=True, fault=fault())
    assert run.checked[1] > 0
    assert harness.correct(run) is want, run.checks
    chip = cellmod.load("smollm-360m.slice_mix").limits["mean_gap"]
    at_chip = dict(run.checks, mean_gap=dict(run.checks["mean_gap"],
                                             limit=chip))
    assert harness.judge(at_chip) is want, at_chip
