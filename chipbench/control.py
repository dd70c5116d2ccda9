#!/usr/bin/env python3
"""Readings that set the limits of a training cell's comparison.

    python3 chipbench/control.py --workload <cell> --program-seeds 1,2,... --control-seeds 7,8,9

In one process, for each program seed: the program's first steps, as a run
of the benchmark drives them, against the float32 reference (the lower
readings). For each control seed, against the same reference:

- ``control``: the reference computed in float8 (e4m3, per-tensor scales),
  the precision below the configuration's bfloat16, in the program's place;
- ``half_batch``: the reference on the first half of every chip's rows, the
  mean taken over those;
- ``no_exchange`` (cells on several chips): the reference on the first
  chip's rows alone, as a chip whose gradients are never exchanged sees
  them. Its loss is not read: the program averages the loss over the chips.

A state left unchanged reads 1 by ``update_gap`` and needs no run. The
benchmark's own runs never run this. Each reading is one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]


def fault_readings(job, seed: int, reference: dict) -> dict:
    """Numbers of the control and of each fault against ``reference``."""
    from chipbench import compare

    n = job.traffic["check_steps"]
    out = {"control": compare.numbers(job.follow(seed, n, prec="fp8"), reference)}
    half = job.traffic["batch_per_chip"] // 2
    out["half_batch"] = compare.numbers(job.follow(seed, n, rows=slice(0, half)), reference)
    if job.traffic["chips"] > 1:
        nums = compare.numbers(job.follow(seed, n, blocks=slice(0, 1)), reference)
        nums["loss_gap"] = None
        out["no_exchange"] = nums
    return out


def program_reading(job, seed: int):
    """(numbers of the program, the reference) for one seed."""
    from chipbench import compare

    n = job.traffic["check_steps"]
    state, prog, _ = job.first_steps(seed, n)
    del state
    reference = job.follow(seed, n)
    return compare.numbers(prog, reference), reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench.runners.train import Job
    from chipbench.run import load_spec

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = load_spec(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["cell"]["chips"]:
        print(f"needs {spec['cell']['chips']} TPU chips; found {devices}", file=sys.stderr)
        return 2
    job = Job(spec, devices[: spec["cell"]["chips"]])
    refs = {}
    for seed in seeds(args.program_seeds):
        t = time.perf_counter()
        nums, refs[seed] = program_reading(job, seed)
        print(json.dumps({"seed": seed, "kind": "program", **nums,
                          "seconds": time.perf_counter() - t}), flush=True)
    for seed in seeds(args.control_seeds):
        t = time.perf_counter()
        if seed not in refs:
            refs[seed] = job.follow(seed, job.traffic["check_steps"])
        for kind, nums in fault_readings(job, seed, refs[seed]).items():
            print(json.dumps({"seed": seed, "kind": kind, **nums}), flush=True)
        print(json.dumps({"seed": seed, "kind": "faults_seconds",
                          "seconds": time.perf_counter() - t}), flush=True)
    job.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
