#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name in
``BENCHMARK.json``; the traffic file names the runner that drives it
(``chipbench/runners/<runner>.py``), and each metric is read by
``chipbench/metrics/<metric>.py``. With ``--trace 0`` the cell's end-to-end
metrics are reported, with ``--trace 1`` its per-layer metrics, from a run
whose window is followed by a few traced steps.

The run needs a TPU and as many chips as the cell asks for; anywhere else it
exits with 2 and prints no result. JAX's persistent compilation cache lives
in ``.jax_cache`` at the root of the checkout. The last lines on standard
error give each number compared with the reference beside its limit; the
last line on standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
# the script's own directory is not a package root: its modules are
# imported as ``chipbench.*`` and must not shadow the standard library
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec(workload: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {', '.join(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    limits = BENCH / "limits" / f"{workload}.json"
    return {
        "bench": bench,
        "cell": cell,
        "config_file": json.loads((ROOT / config["file"]).read_text()),
        "traffic_file": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(limits.read_text()) if limits.exists() else {},
    }


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    name = spec["cell"]["name"]
    return [m for m in spec["bench"][kind] if name in m.get("workloads", [name])]


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> dict:
    """Everything of a run after the look for the chips: set-up, window,
    reference, comparison. Returns the result object."""
    from chipbench import compare

    runner = importlib.import_module(f"chipbench.runners.{spec['traffic_file']['runner']}")
    rec = runner.run(spec, seed, seconds, trace, devices, t_start, log)
    correct, checks = compare.judge(rec["numbers"], spec["limits"])
    metrics = {}
    for m in cell_metrics(spec, trace):
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": correct,
           "attempted": rec["steps_run"],
           "failed": sum(not math.isfinite(x) for x in rec["losses"]),
           "metrics": metrics,
           "device": device}
    if rec["trace"] is not None:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    out["checks"] = checks
    nums = rec["numbers"]
    log(f"worst leaves {nums['worst_leaf']}; left out of update_gap {nums['left_out']}")
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {str(correct).lower()}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number of at least 0")
    spec = load_spec(args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import repro  # noqa: F401  (the program under test; absent, the run fails)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu":
        log(f"the benchmark needs a TPU; JAX found {d0.platform!r}")
        return 2
    if len(devices) < chips:
        log(f"{args.workload} needs {chips} chips; JAX found {len(devices)}")
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
