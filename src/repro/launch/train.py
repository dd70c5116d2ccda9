"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 100 --sync wrht --data corpus

--smoke selects the reduced same-family config, for a CPU run
(``JAX_PLATFORMS=cpu``).  On a TPU drop --smoke; everything else is identical
— mesh construction, sharding, WRHT sync, checkpointing and the
fault-tolerance runtime are the same code path.  ``python chip_smoke.py``
at the repo root drives this path once on one chip (``--chips 4``: the
data-parallel sync modes on a v5e:2x2).

A run resumes from the newest checkpoint in --ckpt-dir; point it at a fresh
directory to train from step 0.
"""

from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.pipeline import CorpusLM, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.parallel import context as pctx
from repro.runtime.fault_tolerance import FailureInjector
from repro.train import Trainer, TrainerOptions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sync", default="auto")
    ap.add_argument("--sync-m", type=int, default=17)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", choices=("corpus", "synthetic"), default="corpus")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="4 (axis data), 2x2 (data,model) or 2x2x2 "
                         "(pod,data,model); default: no mesh")
    ap.add_argument("--fail-at", type=int, nargs="*", default=(),
                    help="inject failures at these steps (recovery demo)")
    ap.add_argument("--remat", default="none")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = registry.get(args.arch, smoke=args.smoke)
    tc = TrainConfig(lr=args.lr, total_steps=args.steps, warmup_steps=min(20, args.steps // 5 + 1),
                     remat=args.remat, sync_algorithm=args.sync, sync_m=args.sync_m,
                     microbatches=args.microbatches)

    mesh = None
    if args.mesh:
        mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")))
        pctx.set_mesh(mesh)

    src_cls = CorpusLM if args.data == "corpus" else SyntheticLM
    source = src_cls(cfg.vocab_size, args.seq, args.batch)
    injector = FailureInjector(tuple(args.fail_at)) if args.fail_at else None

    trainer = Trainer(cfg, tc, source, mesh=mesh,
                      options=TrainerOptions(ckpt_dir=args.ckpt_dir,
                                             ckpt_every=args.ckpt_every),
                      injector=injector)
    if mesh is not None:
        with jax.set_mesh(mesh):
            trainer.run(args.steps)
    else:
        trainer.run(args.steps)
    for h in trainer.history[-5:]:
        print(h)


if __name__ == "__main__":
    main()
