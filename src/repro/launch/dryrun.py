import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the first two lines: jax locks the device count on first init.
# Everything below (including repro imports) may now touch jax freely.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  1. build the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. lower the jit'd step (train_step / prefill / serve_step) from
     ShapeDtypeStruct stand-ins with full NamedShardings — NO allocation,
  3. compile; record memory_analysis (fits/chip?), cost_analysis
     (flops/bytes), and collective bytes parsed from the per-device HLO,
  4. repeat at two reduced scan depths and extrapolate the depth-linear
     costs to full depth (XLA counts while bodies once — see hlo_analysis),
  5. add the analytic inner-scan corrections + MODEL_FLOPS, emit roofline
     terms into experiments/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.launch import analytic
from repro.launch.hlo_analysis import Roofline, parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.models import api as mapi
from repro.parallel import context as pctx
from repro.parallel.sharding import (
    batch_partition_specs,
    cache_partition_specs,
    param_partition_specs,
)
from repro.train.train_step import (
    abstract_train_state, make_train_step, train_state_specs)

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_axes(mesh, global_batch: int | None = None):
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if global_batch is not None:
        import math as _m
        if global_batch % _m.prod(mesh.shape[a] for a in ba):
            return ()  # e.g. long_500k batch=1: replicate over DP axes
    return ba


def _train_config(cfg: ModelConfig, overrides: dict | None = None) -> TrainConfig:
    kw = dict(
        remat="full",
        fsdp=True,
        sync_algorithm="auto",
        # grad accumulation: bounds activation temps (logits especially) so
        # every arch fits 16 GB/chip; also the production overlap unit
        microbatches=8,
        opt_state_dtype="bfloat16" if mapi.param_count(cfg) > 1e11 else "float32",
        grad_accum_dtype="bfloat16" if mapi.param_count(cfg) > 1e11 else "float32",
    )
    if mapi.param_count(cfg) > 1e11:
        kw["microbatches"] = 16
    if overrides:
        kw.update(overrides)
    return TrainConfig(**kw)


# ---------------------------------------------------------------------------
# lowering one cell at one depth
# ---------------------------------------------------------------------------

def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, tc: TrainConfig):
    """Returns (lowered, compiled).  Pure ShapeDtypeStruct inputs."""
    pctx.set_mesh(mesh)
    ba = _batch_axes(mesh, shape.global_batch)
    # serving shards params ZeRO-3 style over every DP axis (data AND pod)
    dp_all = tuple(a for a in ("data", "pod") if a in mesh.axis_names)

    if shape.kind == "train":
        state = abstract_train_state(cfg, tc)
        state_specs = train_state_specs(state, mesh, tc.fsdp)
        batch = mapi.train_batch_specs(cfg, shape)
        bspecs = batch_partition_specs(batch, ba)
        step = make_train_step(cfg, tc, mesh)
        jitted = jax.jit(
            step,
            in_shardings=(_shardings(mesh, state_specs), _shardings(mesh, bspecs)),
            out_shardings=(_shardings(mesh, state_specs), None),
            donate_argnums=(0,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state, batch)

    elif shape.kind == "prefill":
        api = mapi.get_api(cfg, remat="none")
        params = mapi.param_specs(cfg, jnp.bfloat16)
        # weight-stationary TP when the TP-sharded weights fit comfortably;
        # 2D (data×model) sharding only when forced by capacity (236B-class).
        # 2D costs a per-step all-gather of every weight — §Perf iteration 7.
        serve_fsdp = dp_all if mapi.param_count(cfg) * 2 / 16 > 12 * 2**30 else None
        pspecs = param_partition_specs(params, serve_fsdp)
        batch = mapi.prefill_batch_specs(cfg, shape)
        bspecs = batch_partition_specs(batch, ba)
        cache = mapi.cache_specs(cfg, shape)
        cspecs = cache_partition_specs(cfg, cache, ba, mesh.shape["model"])

        def prefill_step(params, batch, cache):
            return api.prefill(params, batch, cache)

        jitted = jax.jit(
            prefill_step,
            in_shardings=(_shardings(mesh, pspecs), _shardings(mesh, bspecs),
                          _shardings(mesh, cspecs)),
            out_shardings=(None, _shardings(mesh, cspecs)),
            donate_argnums=(2,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params, batch, cache)

    else:  # decode
        api = mapi.get_api(cfg, remat="none")
        params = mapi.param_specs(cfg, jnp.bfloat16)
        serve_fsdp = dp_all if mapi.param_count(cfg) * 2 / 16 > 12 * 2**30 else None
        pspecs = param_partition_specs(params, serve_fsdp)
        cache = mapi.cache_specs(cfg, shape)
        cspecs = cache_partition_specs(cfg, cache, ba, mesh.shape["model"])
        dec_in = mapi.decode_input_specs(cfg, shape)
        tok_spec = NamedSharding(mesh, P(ba))
        pos_spec = NamedSharding(mesh, P())

        def serve_step(params, token, pos, cache):
            return api.decode(params, token, pos, cache)

        jitted = jax.jit(
            serve_step,
            in_shardings=(_shardings(mesh, pspecs), tok_spec, pos_spec,
                          _shardings(mesh, cspecs)),
            out_shardings=(None, _shardings(mesh, cspecs)),
            donate_argnums=(3,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params, dec_in["token"], dec_in["pos"], cache)

    compiled = lowered.compile()
    return lowered, compiled


def _costs(compiled) -> dict:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    return {"flops": flops, "bytes": nbytes,
            "collective_bytes": stats.total_bytes,
            "collective_by_kind": dict(stats.bytes_by_kind),
            "collective_counts": dict(stats.count_by_kind)}


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    args = out.get("argument_size_in_bytes", 0)
    alias = out.get("alias_size_in_bytes", 0)
    temp = out.get("temp_size_in_bytes", 0)
    outb = out.get("output_size_in_bytes", 0)
    # live working set: arguments + temps + non-aliased outputs
    out["per_device_hbm_bytes"] = args + temp + max(outb - alias, 0)
    return out


# ---------------------------------------------------------------------------
# full cell analysis with depth extrapolation
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             tc_overrides: dict | None = None, verbose: bool = True) -> dict:
    cfg = registry.get(arch)
    shape = registry.get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tc = _train_config(cfg, tc_overrides)
    n_dev = mesh.devices.size
    t0 = time.time()

    # full-depth compile: exact memory analysis + baseline costs
    lowered, compiled = lower_cell(cfg, shape, mesh, tc)
    mem = _memory(compiled)
    raw = _costs(compiled)

    # depth-0/1 lowering for the while-body extrapolation.  XLA fully
    # unrolls a length-1 scan (body fully counted) and counts length>=2
    # bodies once, so  F(L) = F(0) + L*(F(1) - F(0))  is exact for costs
    # linear in depth (layer bodies, their collectives, per-layer optimizer).
    full = analytic.scan_depth(cfg)
    # cost lowerings run with microbatches=1: total flops/bytes are the same
    # as accumulated microbatches (same tokens), but nothing hides inside the
    # accumulation scan (whose body XLA cost analysis counts only once).
    tc_cost = dataclasses.replace(tc, microbatches=1)
    if full >= 2:
        c0 = _costs(lower_cell(analytic.with_depth(cfg, 0), shape, mesh, tc_cost)[1])
        c1 = _costs(lower_cell(analytic.with_depth(cfg, 1), shape, mesh, tc_cost)[1])
        flops = analytic.extrapolate(c0["flops"], c1["flops"], 0, 1, full)
        nbytes = analytic.extrapolate(c0["bytes"], c1["bytes"], 0, 1, full)
        coll = analytic.extrapolate(c0["collective_bytes"], c1["collective_bytes"],
                                    0, 1, full)
        # slope noise guard: per-layer costs are non-negative, so the
        # extrapolation can never go below the depth-1 measurement
        flops = max(flops, c1["flops"])
        nbytes = max(nbytes, c1["bytes"])
        coll = max(coll, c1["collective_bytes"])
    else:
        c1 = _costs(lower_cell(cfg, shape, mesh, tc_cost)[1])
        flops, nbytes, coll = c1["flops"], c1["bytes"], c1["collective_bytes"]

    # analytic corrections for inner sequence loops (global -> per device)
    corr = analytic.inner_scan_correction(cfg, shape) / n_dev
    flops += corr
    mf = analytic.model_flops(cfg, shape) / n_dev

    roof = Roofline(
        flops_per_device=flops,
        bytes_per_device=nbytes,
        collective_bytes_per_device=coll,
        model_flops_per_device=mf,
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": shape.kind,
        "ok": True,
        "seconds": round(time.time() - t0, 1),
        "memory": mem,
        "fits_16gb": mem["per_device_hbm_bytes"] < 16 * 2**30,
        "raw_cost_analysis": raw,
        "extrapolated": {"flops": flops, "bytes": nbytes,
                         "collective_bytes": coll,
                         "inner_scan_correction": corr},
        "roofline": roof.to_dict(),
        "train_config": {
            "sync": tc.sync_algorithm, "fsdp": tc.fsdp,
            "microbatches": tc.microbatches, "remat": tc.remat,
            "opt_state_dtype": tc.opt_state_dtype,
        } if shape.kind == "train" else None,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
              f"hbm/dev={mem['per_device_hbm_bytes']/2**30:.2f}GiB "
              f"compute={roof.compute_s*1e3:.2f}ms memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms -> {roof.bottleneck} "
              f"({result['seconds']}s)", flush=True)
    return result


def cell_path(arch: str, shape: str, multi_pod: bool) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return OUT_DIR / f"{arch}__{shape}__{mesh}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--shape", choices=list(registry.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--sync", default=None, help="TrainConfig.sync_algorithm override")
    ap.add_argument("--tag", default=None, help="suffix for the output json")
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    over = {"sync_algorithm": args.sync} if args.sync else None

    if args.all:
        cells = [(a, s) for a, s, skip in registry.cells() if not skip]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        out = cell_path(arch, shape, args.multi_pod)
        if args.tag:
            out = out.with_name(out.stem + f"__{args.tag}.json")
        if args.skip_existing and out.exists():
            print(f"[dryrun] skip {out.name}")
            continue
        try:
            result = run_cell(arch, shape, args.multi_pod, over)
        except Exception as e:  # record failures too — they are bugs to fix
            traceback.print_exc()
            result = {"arch": arch, "shape": shape,
                      "mesh": "2x16x16" if args.multi_pod else "16x16",
                      "ok": False, "error": f"{type(e).__name__}: {e}"}
        with open(out, "w") as f:
            json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
