"""The control comes out not correct: the reference put in the program's
place in weight-only int8 (the precision below the program's bfloat16
matmul passes), read at the prompts and tokens the program served, fails
the comparison on every seed, while the program passes it.

At the configuration's reduced size on the CPU, where the program computes
in full float32, with the rehearsal's limits. The same readings at the
cell's own size on the chip set the cells' limits (PERF.md)."""
import time

import pytest

import cell as cellmod
import harness


@pytest.mark.parametrize("seed", [2**31 + 101, 2**31 + 102, 2**31 + 103])
def test_int8_control_fails_and_program_passes(seed):
    c = cellmod.load("smollm-360m.slice_mix")
    run = harness.run_cell(c, seed, 3.0, None, time.perf_counter(),
                           rehearse=True, control=True)
    lim = c.config["rehearsal"]["limits"]
    assert harness.correct(run), run.checks
    assert run.control["int8"]["mean_gap"] > lim["mean_gap"], run.control
