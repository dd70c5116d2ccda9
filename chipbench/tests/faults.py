"""Runs of a cell at the tiny size with the timed path broken underneath.

    python chipbench/tests/faults.py <workload> <fault>

Plants the fault in the program, then drives a whole run after the look
for a chip (``run.run_cell``) and prints its result's ``correct``. Faults:
``none``, ``state_unchanged`` (the optimizer returns its inputs),
``half_batch`` (the loss sees the first half of each chip's rows),
``no_exchange`` (the gradients are not synced between chips).
"""

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
    p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]

import jax  # noqa: E402

from repro.models import api as mapi  # noqa: E402
from repro.train import train_step  # noqa: E402


def plant(fault: str) -> None:
    if fault == "state_unchanged":
        train_step.adamw_update = lambda grads, opt, params, lr, tc: (
            params, opt, {"grad_norm": jax.numpy.zeros(())})
    elif fault == "half_batch":
        get_api = mapi.get_api

        def halved(*a, **kw):
            api = get_api(*a, **kw)
            half = lambda b: {k: v[: v.shape[0] // 2] for k, v in b.items()}
            return dataclasses.replace(api, loss=lambda p, b: api.loss(p, half(b)))

        train_step.mapi = dataclasses.make_dataclass("M", [])()
        train_step.mapi.get_api = halved
    elif fault == "no_exchange":
        # under ``auto`` XLA inserts the all-reduce: the step body then runs
        # per chip inside shard_map, as the manual modes do, and syncs nothing
        train_step.MANUAL_ALGOS += ("auto",)
        train_step.sync_gradients = lambda grads, tc, mesh, ef, **kw: (grads, None)
    elif fault != "none":
        raise ValueError(fault)


def main(workload: str, fault: str) -> None:
    from chipbench import run
    from chipbench.tests.tiny import spec

    plant(fault)
    s = spec(workload)
    out = run.run_cell(s, 2**31 + 7, 0.5, False, jax.devices()[: s["cell"]["chips"]],
                       time.perf_counter())
    print(json.dumps({"fault": fault, "correct": out["correct"], "checks": out["checks"]}))


if __name__ == "__main__":
    main(*sys.argv[1:])
