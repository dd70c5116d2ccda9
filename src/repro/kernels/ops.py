"""jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU.  ``interpret=True`` runs them in the Pallas
interpreter instead; only a caller that wants that asks for it (the CPU
tests, which check every kernel against ``ref.py``).  Nothing here picks the
mode from the backend, so a kernel never falls back to the interpreter
silently, and a compile for a described TPU topology sees the real kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import quant as _q
from . import rmsnorm as _rn


@partial(jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, q_block=256, kv_block=256,
                    interpret=False):
    """q [B,Sq,H,D]; k/v [B,Skv,K,D] (GQA: K | H).  Returns [B,Sq,H,D]."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    # fold batch+kv-head, broadcast kv across the group dim
    qf = q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4).reshape(b * kh * g, sq, d)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kh, g, k.shape[1], d)).reshape(b * kh * g, -1, d)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kh, g, v.shape[1], v.shape[-1])).reshape(
                              b * kh * g, -1, v.shape[-1])
    out = _fa.flash_attention(qf, kf, vf, causal=causal, q_block=q_block,
                              kv_block=kv_block, interpret=interpret)
    return out.reshape(b, kh, g, sq, -1).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, -1)


@partial(jax.jit, static_argnames=("eps", "rows_block", "interpret"))
def rmsnorm(x, w, *, eps=1e-6, rows_block=256, interpret=False):
    return _rn.rmsnorm(x, w, eps=eps, rows_block=rows_block, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bm, cm, *, chunk=128, interpret=False):
    """x [B,S,H,P]; dt [B,S,H]; a [H]; bm/cm [B,S,N] (shared across heads)."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    af = jnp.broadcast_to(a[None], (b, h)).reshape(b * h)
    bf = jnp.broadcast_to(bm[:, None], (b, h, s, n)).reshape(b * h, s, n)
    cf = jnp.broadcast_to(cm[:, None], (b, h, s, n)).reshape(b * h, s, n)
    y = _ms.ssd_scan(xf, dtf, af, bf, cf, chunk=chunk, interpret=interpret)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block", "bits", "interpret"))
def quantize_blocks(x, *, block=1024, bits=8, interpret=False):
    return _q.quantize_blocks(x, block=block, bits=bits, interpret=interpret)


@partial(jax.jit, static_argnames=("block", "bits", "interpret"))
def ef_quantize_bucketize(grad, residual, *, block=1024, bits=8,
                          interpret=False):
    """Fused EF quantize+bucketize (one pass: t = grad + residual, per-block
    absmax scale, round/clip into the int8 wire buffer, dequantized value,
    new residual)."""
    return _q.ef_quantize_bucketize(grad, residual, block=block, bits=bits,
                                    interpret=interpret)


@partial(jax.jit, static_argnames=("block", "interpret"))
def dequant_add(q, scales, acc, *, block=1024, interpret=False):
    return _q.dequant_add(q, scales, acc, block=block, interpret=interpret)
