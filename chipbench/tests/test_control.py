"""The control, the reference in float8 in the program's place, reads far
above the program at the tiny size of ``tiny.py``, on three seeds: on the
chip the control fails the cells' ``loss_gap`` (among others) at 9 to 35
times the program's largest reading; here, at a hundredth of the widths,
its loss gap still reads at least 3 times the program's largest."""

import jax
import pytest

from chipbench import compare, control
from chipbench.runners.train import Job
from chipbench.tests.tiny import spec

SEEDS = (11, 12, 2**31 + 13)


@pytest.mark.parametrize("workload", ["qwen2-train", "granite-moe-train"])
def test_control_reads_above_the_program(workload):
    s = spec(workload)
    job = Job(s, jax.devices()[:1])
    try:
        prog, ctl = [], []
        for seed in SEEDS:
            nums, reference = control.program_reading(job, seed)
            prog.append(nums["loss_gap"])
            ctl.append(compare.numbers(job.follow(seed, 3, prec="fp8"), reference)["loss_gap"])
    finally:
        job.close()
    assert min(ctl) >= 3 * max(prog), (prog, ctl)
