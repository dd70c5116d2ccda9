"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jax lowers each kernel for a
chip that is described, not attached, and refuses what the chip would
refuse (tile shapes, scoped VMEM).  Interpret-mode tests cannot show that.
The shapes are the compressed gradient sync's: one 32 MiB f32 bucket (the
default ``TrainConfig.bucket_bytes``) and one length that is not a multiple
of the block.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import TrainConfig
from repro.kernels import ops

BLOCK = TrainConfig().compress_block
SIZES = [TrainConfig().bucket_bytes // 4, 5 * BLOCK * 1000 + 7]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # the TPU compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot load here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
