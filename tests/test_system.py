"""End-to-end behaviour: train to decreasing loss, then serve; manual WRHT
sync path end-to-end on a multi-device mesh (subprocess)."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np

import jax

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.pipeline import CorpusLM
from repro.serve import Engine
from repro.train import Trainer, TrainerOptions

REPO = Path(__file__).resolve().parents[1]


def test_train_loss_decreases_then_serve(tmp_path):
    cfg = registry.get("qwen2-1.5b", smoke=True)
    # 60 steps: from the near-uniform start (loss ~ ln(vocab)) 30 steps do
    # not yet halve the loss
    tc = TrainConfig(lr=1e-3, total_steps=60, warmup_steps=5, remat="none")
    src = CorpusLM(cfg.vocab_size, 32, 8)
    tr = Trainer(cfg, tc, src, mesh=None,
                 options=TrainerOptions(ckpt_dir=tmp_path, ckpt_every=30,
                                        log_every=1))
    state = tr.run(60)
    losses = [h["loss"] for h in tr.history]
    # an untrained model predicts near-uniformly over the vocabulary
    assert abs(losses[0] - np.log(cfg.vocab_size)) < 0.5, losses
    assert losses[-1] < losses[0] * 0.5, losses

    eng = Engine(cfg, state["params"], batch_slots=2, max_seq=64)
    r = eng.submit([5, 6, 7], max_new_tokens=8)
    eng.run()
    assert len(r.output) == 8


WRHT_E2E = """
import jax, numpy as np
from jax.sharding import AxisType
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticLM, shard_batch
from repro.train import make_train_state, make_train_step
from repro.parallel import context as pctx

cfg = registry.get("granite-moe-1b-a400m", smoke=True)  # MoE exercises EP too
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,)*3)
src = SyntheticLM(cfg.vocab_size, 16, 8)
out = {}
with jax.set_mesh(mesh):
    pctx.set_mesh(mesh)
    for alg in ("auto", "wrht", "hier_scatter", "planned", "planned_sharded"):
        tc = TrainConfig(total_steps=2, remat="none", sync_algorithm=alg,
                         sync_m=3, bucket_bytes=1 << 20)
        state = make_train_state(cfg, tc, jax.random.key(0))
        step = jax.jit(make_train_step(cfg, tc, mesh))
        for k in range(2):
            state, metrics = step(state, shard_batch(src.batch(k), mesh))
        out[alg] = float(sum(jax.numpy.sum(jax.numpy.abs(l.astype(jax.numpy.float32)))
                             for l in jax.tree.leaves(state["params"])))
base = out["auto"]
for alg, v in out.items():
    assert abs(v - base) / base < 5e-4, (alg, v, base)
print("WRHT_E2E_OK")
"""


def test_wrht_sync_end_to_end_multidevice(subproc):
    assert "WRHT_E2E_OK" in subproc(WRHT_E2E, timeout=900)


def test_trainer_donates_state(tmp_path):
    """The jitted step updates the train state in place: XLA aliases every
    state byte to an output, so a step holds one copy of params and
    optimizer state, not two."""
    cfg = registry.get("qwen2-1.5b", smoke=True)
    tc = TrainConfig(total_steps=1, remat="none")
    src = CorpusLM(cfg.vocab_size, 32, 4)
    tr = Trainer(cfg, tc, src, options=TrainerOptions(ckpt_dir=tmp_path))
    state = tr.init_or_restore()
    batch = {k: jax.numpy.asarray(v) for k, v in src.batch(0).items()}
    mem = tr._step_fn.lower(state, batch).compile().memory_analysis()
    state_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes


def test_one_axis_mesh_is_data_parallel():
    from repro.launch.mesh import make_mesh

    assert make_mesh((1,)).axis_names == ("data",)
    assert make_mesh((1, 1)).axis_names == ("data", "model")


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache

    set_to = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent == REPO
    assert set_to == {"jax_compilation_cache_dir": path}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert set_to["jax_compilation_cache_dir"] == "/elsewhere/cache"


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok": true' not in proc.stdout


# chip_smoke's four-chip phase on 4 virtual devices at smoke size: each
# sync mode trains over a 1-D data mesh, keeps the state replicated on all
# devices and matches auto's losses (the fused quant kernel interpreted)
DP_SMOKE = f"""
import sys
sys.path.insert(0, {str(REPO)!r})
from jax.experimental.pallas import tpu as pltpu
import chip_smoke
from repro.configs import registry

with pltpu.force_tpu_interpret_mode():
    losses = chip_smoke.dp_phase(registry.get("qwen2-1.5b", smoke=True),
                                 n_dev=4, batch=2, seq=32)
assert set(losses) == set(chip_smoke.DP_MODES)
print("DP_SMOKE_OK")
"""


def test_chip_smoke_dp_phase_on_virtual_devices(subproc):
    assert "DP_SMOKE_OK" in subproc(DP_SMOKE, devices=4, timeout=900)
