"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jax lowers each kernel for a
chip that is described, not attached, and refuses what the chip would
refuse (tile shapes, scoped VMEM).  Interpret-mode tests cannot show that.
The quant kernels' shapes are the compressed gradient sync's: one 32 MiB
f32 bucket (the default ``TrainConfig.bucket_bytes``) and one length that
is not a multiple of the block.  The fused attention kernel compiles inside
the model's own loss gradient at the training cells' shapes (one layer
each): Qwen2-1.5B at 4x2048 on one chip and on each chip of a v5e:2x2 under
the trainer's ``shard_map``, Granite-MoE at 2x4096, and DeepSeek-V2-Lite's
latent attention (q/k 192, v 128, YaRN) at 4x4096 behind its dense layer.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.kernels import ops
from repro.models import transformer
from repro.models.layers import FUSED_KERNEL

BLOCK = TrainConfig().compress_block
SIZES = [TrainConfig().bucket_bytes // 4, 5 * BLOCK * 1000 + 7]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot load here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


@pytest.mark.parametrize("n", SIZES)
def test_ef_quantize_bucketize_compiles(one_chip, n):
    g = _spec((n,), jnp.float32, one_chip)
    _compiled_text(ops.ef_quantize_bucketize, g, g)


@pytest.mark.parametrize("n", SIZES)
def test_quantize_blocks_compiles(one_chip, n):
    _compiled_text(ops.quantize_blocks, _spec((n,), jnp.float32, one_chip))


@pytest.mark.parametrize("n", SIZES)
def test_dequant_add_compiles(one_chip, n):
    nb = -(-n // BLOCK)
    q = _spec((nb * BLOCK,), jnp.int8, one_chip)
    s = _spec((nb,), jnp.float32, one_chip)
    acc = _spec((nb * BLOCK,), jnp.float32, one_chip)
    _compiled_text(ops.dequant_add, q, s, acc)


# ------------------------------------------------------------ attention

# the training cells' configurations, one layer each, and rows a chip
CELLS = {"qwen2-1.5b": (4, 2048), "granite-moe-1b-a400m": (2, 4096),
         "deepseek-v2-lite": (4, 4096)}


def _one_layer(name):
    """One layer of the scan, behind the leading dense layers if any."""
    cfg = registry.get(name)
    return dataclasses.replace(cfg, n_layers=cfg.first_k_dense + 1)


@pytest.mark.parametrize("name", CELLS)
def test_attention_kernel_in_loss_grad(one_chip, name):
    cfg = _one_layer(name)
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                          jax.eval_shape(lambda: transformer.init_params(
                              jax.random.key(0), cfg)))
    tokens = _spec(CELLS[name], jnp.int32, one_chip)

    def loss(params, tokens):
        return transformer.loss_fn(params, {"tokens": tokens, "labels": tokens},
                                   cfg)[0]

    # the layer's forward, its remat recompute and the backward: no fallback
    assert FUSED_KERNEL in _compiled_text(jax.jit(jax.grad(loss)), params, tokens)


def test_canon_hlo_reads_kernels_without_their_source_lines(one_chip):
    """A kernel's serialized body carries the file and line of each of its
    ops: the same kernel called from two places compiles to different
    bytes, and ``tools/canon_hlo.py`` prints both as one program."""
    import importlib.util
    from pathlib import Path

    from repro.models.layers import fused_causal_attention

    path = Path(__file__).resolve().parents[1] / "tools" / "canon_hlo.py"
    spec = importlib.util.spec_from_file_location("canon_hlo", path)
    canon_hlo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(canon_hlo)
    x = _spec((1, 256, 2, 128), jnp.bfloat16, one_chip)
    first = _compiled_text(jax.jit(lambda q, k, v: fused_causal_attention(q, k, v)), x, x, x)
    second = _compiled_text(jax.jit(
        lambda q, k, v: fused_causal_attention(q, k, v)), x, x, x)
    assert first != second
    assert canon_hlo.canon(first) == canon_hlo.canon(second)


@pytest.mark.parametrize("sync", ["planned_sharded", "auto"])
def test_attention_kernel_in_dp4_step(topo, tmp_path, sync):
    """The data-parallel cell's step over the 2x2's four chips, the kernel
    inside, per chip: under the trainer's own ``shard_map`` with its planned
    sharded gradient sync, and under GSPMD (``auto``), where the layer puts
    the kernel in a ``shard_map`` over the batch axis so that GSPMD never
    gathers q, k or v to replicate it."""
    from repro.parallel import context as pctx
    from repro.train import Trainer, TrainerOptions
    from repro.train.train_step import abstract_train_state

    cfg = _one_layer("qwen2-1.5b")
    tc = TrainConfig(sync_algorithm=sync)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",), axis_types=(AxisType.Auto,))
    pctx.set_mesh(mesh)
    try:
        tr = Trainer(cfg, tc, None, mesh=mesh,
                     options=TrainerOptions(ckpt_dir=tmp_path))
        with jax.set_mesh(mesh):
            state = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                abstract_train_state(cfg, tc), tr._state_shardings)
            rows = NamedSharding(mesh, P("data", None))
            batch = {k: jax.ShapeDtypeStruct((4 * 4, 2048), jnp.int32, sharding=rows)
                     for k in ("tokens", "labels")}
            args = (state, batch)
            if tr._plan_codes is not None:
                args += (jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
                    tr._plan_codes),)
            text = _compiled_text(tr._step_fn, *args)
    finally:
        pctx.set_mesh(None)
    assert FUSED_KERNEL in text
    if sync == "auto":
        assert "all-gather" not in text
