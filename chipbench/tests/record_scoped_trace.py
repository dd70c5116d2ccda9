"""Records the small scoped trace that ``test_scopes.py`` reads, on one v5e.

    python3 chipbench/tests/record_scoped_trace.py chipbench/tests/data/moe_small_scoped.xplane.pb

The program's ``Trainer`` trains granite-moe-1b-a400m at the program's smoke
widths (2 layers of 4 experts, 2 rows of 64 tokens a step) on the
benchmark's feed, and the benchmark's clock traces it as it traces a cell:
after two steps it starts the profiler, lets a lead-in step and three more
run, and stops. So the trace holds every named scope of the step but
``grad_sync``, and every span of the training loop.
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.feed import Feed  # noqa: E402
from chipbench.runners.train import Clock, WindowClosed, _NoCheckpoint  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.train import Trainer, TrainerOptions  # noqa: E402

TRAFFIC = {"batch_per_chip": 2, "chips": 1, "seq_len": 64,
           "token_law": {"kind": "zipf", "exponent": 1.0}}


def main(out: str) -> None:
    cfg = registry.get("granite-moe-1b-a400m", smoke=True)
    feed = Feed(TRAFFIC, cfg.vocab_size, seed=0)
    log_dir, ckpt_dir = tempfile.mkdtemp(), tempfile.mkdtemp()
    trainer = Trainer(cfg, TrainConfig(remat="full"), feed,
                      options=TrainerOptions(ckpt_dir=ckpt_dir))
    trainer.ckpt = _NoCheckpoint()
    feed.on_call = Clock(warmup=2, seconds=0.0, trace_steps=3, trace_dir=log_dir)
    try:
        trainer.run(100)
    except WindowClosed:
        pass
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(log_dir)
    shutil.rmtree(ckpt_dir)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
