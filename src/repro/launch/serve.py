"""Serving driver: batched requests against a (random- or checkpoint-) model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import load_latest
from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as mapi
from repro.serve import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = registry.get(args.arch, smoke=args.smoke)
    api = mapi.get_api(cfg, remat="none")
    params = api.init(jax.random.key(args.seed))
    if args.ckpt_dir:
        restored, step = load_latest(args.ckpt_dir, {"params": params})
        if restored is not None:
            params = restored["params"]
            print(f"loaded checkpoint step {step}")

    eng = Engine(cfg, params, batch_slots=args.batch_slots, max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for _ in range(args.requests):
        plen = int(rng.integers(2, 12))
        eng.submit(list(rng.integers(1, cfg.vocab_size, plen)),
                   max_new_tokens=args.max_new)
    done = eng.run()
    dt = time.time() - t0
    tokens = sum(len(r.output) for r in done)
    print(f"{len(done)} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt {r.prompt[:6]}... -> {r.output}")


if __name__ == "__main__":
    main()
