#!/usr/bin/env python3
"""Smoke run of the trainer and the serving engine on a TPU.

    python chip_smoke.py             # one chip: train, then serve, qwen2-1.5b
    python chip_smoke.py --chips 4   # a v5e:2x2 host: the data-parallel
                                     # gradient-sync modes against ``auto``

One chip.  Trains qwen2-1.5b at its published widths, with only the depth
cut to fit one chip's memory, through ``Trainer`` with ``CorpusLM`` data (as
``repro.launch.train`` builds it), and checks that the losses are finite,
that the first is near ln(vocab) and that the last is below the first.  Then
serves 8 requests of 16 new tokens from the full-depth model through
``Engine`` (as ``repro.launch.serve`` builds it), and checks that each
finishes on its budget with every token inside the vocabulary.

Four chips (``--chips 4``).  Trains the same cut model for 3 steps over a
4-way ``data`` mesh under each gradient-sync mode still in use, and checks
that the losses of steps 2-3 match XLA's own all-reduce (``auto``) and that
the train state is replicated over all four devices.

Weights are random from a fixed seed.  The script needs a TPU: on any other
platform it exits non-zero before any phase runs.  Its last line of output
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  These are smoke numbers, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
# depth that fits one v5e chip (16 GB) with headroom, from the compiled
# step's memory analysis: 8 layers of 28 (see CHANGES.md)
TRAIN_LAYERS = 8
BATCH, SEQ = 4, 2048             # per chip
TRAIN_STEPS = 10
# per chip: the compressed mode also holds an error-feedback residual of
# the gradients' size, and at batch 4 its step would peak at 15.6 of 15.75
# GiB (memory_analysis of a described v5e:2x2 compile)
DP_BATCH = 2
SERVE_REQUESTS, SERVE_NEW_TOKENS = 8, 16
DP_STEPS = 3
DP_MODES = ("auto", "psum", "planned_sharded", "planned_pipelined",
            "planned_sharded_compressed")
# steps 2-3 against auto: summation order alone moves the uncompressed
# modes.  int8 error feedback moves the compressed one further: on 4
# virtual CPU devices it deviated 1.6e-6 (smoke widths) and 4.5e-5 (d_model
# 512, vocab 16384); the bound is 100x the larger, for full widths and the
# chip's bf16 reduction order
DP_RTOL = 1e-3
DP_RTOL_COMPRESSED = 5e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found platform {d0.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def train_config(steps: int, **over):
    from repro.configs.base import TrainConfig

    # the same schedule repro.launch.train builds from its flags
    return TrainConfig(lr=1e-3, total_steps=steps,
                       warmup_steps=min(20, steps // 5 + 1), **over)


def train_cfg(layers: int = TRAIN_LAYERS):
    from repro.configs import registry

    full = registry.get(ARCH)
    cfg = dataclasses.replace(full, n_layers=layers)
    print(f"reduced: n_layers {full.n_layers} -> {cfg.n_layers} "
          "(one chip's memory); widths as published")
    return cfg


def _state_bytes(tree) -> int:
    import jax

    return sum(math.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def report_step_memory(trainer, source) -> None:
    """Compile the trainer's step for the run's shapes and check that the
    state is donated: the aliased bytes cover the whole train state."""
    import jax

    from repro.train.train_step import abstract_train_state

    state = abstract_train_state(trainer.cfg, trainer.tc)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in source.batch(0).items()}
    t0 = time.perf_counter()
    mem = trainer._step_fn.lower(state, batch).compile().memory_analysis()
    compile_s = time.perf_counter() - t0
    state_b = _state_bytes(state)
    print(f"train step compile {compile_s:.1f}s; memory_analysis: "
          f"arguments {mem.argument_size_in_bytes} outputs "
          f"{mem.output_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} peak "
          f"{mem.peak_memory_in_bytes} bytes; train state {state_b} bytes")
    check(mem.alias_size_in_bytes >= 0.99 * state_b,
          "train state is not donated to the step")


def report_peak(label: str, devices) -> None:
    """Print each device's peak bytes in use so far, against its limit
    (a CPU rehearsal has no memory stats and prints None)."""
    stats = [d.memory_stats() or {} for d in devices]
    print(f"{label} peak_bytes_in_use "
          f"{[s.get('peak_bytes_in_use') for s in stats]} of bytes_limit "
          f"{[s.get('bytes_limit') for s in stats]}")


def train_phase(cfg, batch: int = BATCH, seq: int = SEQ,
                steps: int = TRAIN_STEPS) -> list[dict]:
    import jax

    from repro.data.pipeline import CorpusLM
    from repro.train import Trainer, TrainerOptions

    tc = train_config(steps, remat="full")
    source = CorpusLM(cfg.vocab_size, seq, batch)
    # a fresh directory: the trainer resumes from any checkpoint it finds
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        trainer = Trainer(cfg, tc, source, options=TrainerOptions(
            ckpt_dir=ckpt, ckpt_every=steps, log_every=1))
        report_step_memory(trainer, source)
        t0 = time.perf_counter()
        trainer.run(steps)
        wall = time.perf_counter() - t0
    hist = trainer.history
    for h in hist:
        print(f"train step {h['step']} loss {h['loss']:.6f} "
              f"time {h['sec_per_step']:.4f}s")
    losses = [h["loss"] for h in hist]
    print(f"train wall {wall:.1f}s for {steps} steps "
          f"(batch {batch}x{seq}, first step includes compile)")
    check(len(losses) == steps, f"{len(losses)} of {steps} steps logged")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    # random logits of std s raise the expected loss above ln(vocab) by
    # about s^2/2; at 8 layers of the published widths the first loss sat
    # 0.57 above it on a v5e, so a 1-nat band holds random init and still
    # refuses a broken one (std-1 tied embeddings start near 190)
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.4f} is not near ln(vocab) {ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    report_peak("train", jax.devices()[:1])
    return hist


def serve_phase(cfg, requests: int = SERVE_REQUESTS,
                new_tokens: int = SERVE_NEW_TOKENS, seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro.models import api as mapi
    from repro.serve import Engine

    # as repro.launch.serve builds it, with its default slots and max_seq
    api = mapi.get_api(cfg, remat="none")
    params = api.init(jax.random.key(seed))
    eng = Engine(cfg, params, batch_slots=4, max_seq=256)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for _ in range(requests):
        plen = int(rng.integers(2, 12))
        eng.submit(list(rng.integers(1, cfg.vocab_size, plen)),
                   max_new_tokens=new_tokens)
    done = eng.run()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in done)
    print(f"serve {cfg.name} ({cfg.n_layers} layers): {len(done)} requests, "
          f"{tokens} tokens in {dt:.2f}s (compiles included)")
    for r in done:
        print(f"  req {r.rid}: prompt {len(r.prompt)} tokens -> "
              f"{r.output} ({r.finish_reason})")
    check(len(done) == requests, f"{len(done)} of {requests} requests done")
    for r in done:
        check(r.finish_reason == "budget" and len(r.output) == new_tokens,
              f"req {r.rid} finished {r.finish_reason} after "
              f"{len(r.output)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.output),
              f"req {r.rid} emitted a token outside the vocabulary")
    check_greedy_against_forward(eng, done)
    report_peak("serve", jax.devices()[:1])


def check_greedy_against_forward(eng, done, tol: float = 0.05) -> None:
    """Replay every request through one full causal forward pass, without
    the KV cache: each token the engine emitted must be the argmax of the
    reference logits at its position, up to ``tol`` (bf16 reduction order
    differs between the cached decode and the full pass)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer

    @jax.jit
    def all_logits(params, tokens):
        hidden, _, _ = transformer.forward(params, tokens, eng.cfg,
                                           remat="none")
        return transformer.logits_fn(params, hidden, eng.cfg)

    # every replay right-padded to one length, so the forward compiles once;
    # causal attention keeps the padding out of the positions compared
    width = max(len(r.prompt) + len(r.output) for r in done)
    worst = 0.0
    for r0 in range(0, len(done), eng.batch_slots):
        rnd = done[r0:r0 + eng.batch_slots]
        plen = max(len(r.prompt) for r in rnd)
        for r in rnd:
            # the engine's round: left-padded to the round's longest prompt
            seq = ([eng.pad_id] * (plen - len(r.prompt)) + r.prompt
                   + r.output[:-1])
            seq += [eng.pad_id] * (width - len(seq))
            logits = np.asarray(all_logits(
                eng.params, jnp.asarray([seq], jnp.int32))[
                    0, plen - 1:plen - 1 + len(r.output)], np.float32)
            chosen = logits[np.arange(len(r.output)), r.output]
            gap = float(np.max(logits.max(axis=-1) - chosen))
            worst = max(worst, gap)
            check(gap <= tol, f"req {r.rid}: an emitted token is {gap:.4f} "
                  "below the reference argmax logit")
    print(f"serve greedy tokens vs full forward: worst logit gap to the "
          f"argmax {worst:.4f} (bound {tol:g})")


def replicated_on(state, devices) -> bool:
    import jax

    want = set(devices)
    return all(l.sharding.device_set == want and l.sharding.is_fully_replicated
               for l in jax.tree.leaves(state))


def dp_phase(cfg, n_dev: int = 4, batch: int = DP_BATCH, seq: int = SEQ,
             steps: int = DP_STEPS, modes=DP_MODES) -> dict:
    """Data-parallel training over an ``n_dev``-way ``data`` mesh, once per
    gradient-sync mode; returns each mode's losses."""
    import jax

    from repro.data.pipeline import CorpusLM
    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx
    from repro.train import Trainer, TrainerOptions

    mesh = make_mesh((n_dev,))
    devices = list(mesh.devices.flat)
    source = CorpusLM(cfg.vocab_size, seq, batch * n_dev)
    losses: dict[str, list[float]] = {}
    with jax.set_mesh(mesh):
        pctx.set_mesh(mesh)
        try:
            for mode in modes:
                tc = train_config(steps, remat="full", sync_algorithm=mode)
                if mode.endswith("_compressed"):
                    # int8 on every bucket: the planner's own sweep may
                    # decline compression, and a declined bucket never
                    # reaches the fused kernel
                    tc = dataclasses.replace(tc, compress_bits=(8,),
                                             compress_fused_kernel=True)
                t0 = time.perf_counter()
                # a fresh directory per mode, removed before the next: each
                # final checkpoint holds the whole train state
                with tempfile.TemporaryDirectory(
                        prefix="chip_smoke_ckpt_") as ckpt:
                    trainer = Trainer(cfg, tc, source, mesh=mesh,
                                      options=TrainerOptions(
                                          ckpt_dir=ckpt, ckpt_every=steps,
                                          log_every=1))
                    setup = time.perf_counter() - t0
                    state = trainer.run(steps)
                wall = time.perf_counter() - t0
                check(replicated_on(state, devices),
                      f"{mode}: train state is not replicated over "
                      f"{len(devices)} devices")
                del state
                losses[mode] = [h["loss"] for h in trainer.history]
                times = " ".join(f"{h['sec_per_step']:.3f}"
                                 for h in trainer.history)
                plans = (trainer.controller.plans
                         if trainer.controller is not None else None)
                bits = "" if plans is None or plans.bits is None else (
                    f" bucket bits {sorted(set(plans.bits))}")
                print(f"dp {mode}: losses {losses[mode]} step times {times}s"
                      f" setup {setup:.1f}s wall {wall:.1f}s{bits}; train "
                      f"state replicated on all {len(devices)} devices")
        finally:
            pctx.set_mesh(None)
    report_peak("dp", devices)
    ref = losses["auto"]
    for mode, got in losses.items():
        check(all(math.isfinite(l) for l in got), f"{mode}: non-finite loss")
        rtol = DP_RTOL_COMPRESSED if mode.endswith("_compressed") else DP_RTOL
        rel = max(abs(g - r) / abs(r) for g, r in zip(got[1:], ref[1:]))
        print(f"dp {mode}: max relative deviation from auto over steps "
              f"2-{steps}: {rel:.3e} (bound {rtol:g})")
        check(rel <= rtol, f"{mode}: losses {got} vs auto {ref}")
    return losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    devices = require_tpu()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found "
          f"{len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        dp_phase(train_cfg(), n_dev=4)
    else:
        from repro.configs import registry

        train_phase(train_cfg())
        serve_phase(registry.get(ARCH))
    print(f"total wall {time.perf_counter() - t0:.1f}s")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
