"""A whole run after the look for a chip, with the timed path broken
underneath, comes out not correct. Each case runs ``faults.py`` in a
process of its own on four virtual CPU devices, at the tiny size of
``tiny.py``. The same run unbroken keeps its loss and gradient within the
cell's limits; its ``update_gap`` is not held here, because at this size a
leaf has a hundred-odd elements and the norm of an Adam step swings with
the sign that rounding gives each one."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent

CASES = [(w, f) for w in ("qwen2-dp4-sharded", "qwen2-dp4-auto") for f in
         ("none", "state_unchanged", "half_batch", "no_exchange")]
CASES += [(w, f) for w in ("qwen2-train", "granite-moe-train")
          for f in ("none", "state_unchanged", "half_batch")]


def _run(workload, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(HERE / "faults.py"), workload, fault],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    out = _run(workload, fault)
    checks = out["checks"]
    if fault == "none":
        for name in ("loss_gap", "grad_gap"):
            assert checks[name]["value"] <= checks[name]["limit"], checks
    else:
        assert out["correct"] is False, checks
