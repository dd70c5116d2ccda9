"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------- flash attn

@pytest.mark.parametrize("b,sq,skv,h,k,d", [
    (1, 64, 64, 2, 2, 32),
    (2, 96, 96, 4, 2, 32),     # GQA, non-divisible seq/block
    (1, 128, 128, 4, 1, 64),   # MQA
    (2, 33, 65, 2, 2, 16),     # ragged
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, sq, skv, h, k, d, causal):
    if causal and sq != skv:
        pytest.skip("causal requires sq == skv in this sweep")
    q, kk, v = _rand((b, sq, h, d)), _rand((b, skv, k, d)), _rand((b, skv, k, d))
    got = ops.flash_attention(q, kk, v, causal=causal, q_block=32,
                              kv_block=32, interpret=True)
    g = h // k
    qf = q.reshape(b, sq, k, g, d).transpose(0, 2, 3, 1, 4).reshape(b * h, sq, d)
    kf = jnp.broadcast_to(kk.transpose(0, 2, 1, 3)[:, :, None],
                          (b, k, g, skv, d)).reshape(b * h, skv, d)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (b, k, g, skv, d)).reshape(b * h, skv, d)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    want = want.reshape(b, k, g, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q, k, v = (_rand((1, 64, 2, 32), jnp.bfloat16) for _ in range(3))
    got = ops.flash_attention(q, k, v, q_block=32, kv_block=32,
                              interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    kf = k.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    vf = v.transpose(0, 2, 1, 3).reshape(2, 64, 32)
    want = ref.flash_attention_ref(qf, kf, vf).reshape(1, 2, 64, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2, atol=3e-2)


def test_flash_matches_model_layer_oracle():
    """kernel == models.layers.blocked_attention (the in-model jnp path)."""
    from repro.models.layers import blocked_attention

    q, k, v = _rand((2, 80, 4, 32)), _rand((2, 80, 2, 32)), _rand((2, 80, 2, 32))
    a = ops.flash_attention(q, k, v, causal=True, q_block=32, kv_block=32,
                            interpret=True)
    b = blocked_attention(q, k, v, causal=True, q_block=32, kv_block=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(7, 64), (3, 37, 128), (1, 1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x, w = _rand(shape, dtype), _rand(shape[-1:], dtype)
    got = ops.rmsnorm(x, w, rows_block=4, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------------ ssd scan

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 50, 3, 16, 8, 16),    # ragged chunks
    (1, 128, 1, 32, 16, 64),
])
def test_ssd_scan(b, s, h, p, n, chunk):
    x = _rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm, cm = _rand((b, s, n)), _rand((b, s, n))
    got = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    af = jnp.broadcast_to(a[None], (b, h)).reshape(-1)
    bf = jnp.broadcast_to(bm[:, None], (b, h, s, n)).reshape(b * h, s, n)
    cf = jnp.broadcast_to(cm[:, None], (b, h, s, n)).reshape(b * h, s, n)
    want = ref.ssd_ref(xf, dtf, af, bf, cf).reshape(b, h, s, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ssd_matches_model_oracle():
    """kernel == models.ssm.ssd_chunked (the in-model jnp path)."""
    from repro.models.ssm import ssd_chunked

    b, s, h, p, n = 2, 40, 2, 8, 4
    x = _rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm, cm = _rand((b, s, n)), _rand((b, s, n))
    got = ops.ssd_scan(x, dt, a, bm, cm, chunk=16, interpret=True)
    want, _ = ssd_chunked(x, dt, a, bm, cm, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------- quant

@pytest.mark.parametrize("n,block", [(3000, 256), (1024, 1024), (100, 64)])
def test_quantize_blocks(n, block):
    x = _rand((n,))
    q, s, n_out = ops.quantize_blocks(x, block=block, interpret=True)
    qr, sr, _ = ref.quantize_blocks_ref(x, block=block)
    assert n_out == n
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quant_roundtrip_error_bound():
    x = _rand((4096,))
    q, s, _ = ops.quantize_blocks(x, block=512, interpret=True)
    acc = jnp.zeros_like(q, jnp.float32)
    deq = ops.dequant_add(q, s, acc, block=512, interpret=True)
    err = np.abs(np.asarray(deq[:4096]) - np.asarray(x)).max()
    bound = float(np.abs(np.asarray(x)).max()) / 127 + 1e-6
    assert err <= bound
