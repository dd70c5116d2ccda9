"""Pallas TPU int8 symmetric quantize / dequant-accumulate kernels.

The compute hot-spot of the compressed cross-pod all-reduce
(core.compression): quantize before the wire, fused dequant+add after.
Per-block scales ([block] f32 alongside the int8 payload) keep the VPU busy
and the error bounded; block size 1024 aligns with the lane width.

``ef_quantize_bucketize`` is the planned-compressed hot path (DESIGN.md §15):
one pass per block fuses the error-feedback add (grad + residual), the
absmax scan, the scale, round/clip into the bucket's int8 wire buffer, the
dequantized value the collective reduces, and the new EF residual — five
reads/writes that the unfused jnp path spreads over as many kernels.

Layout: a flat bucket is viewed as ``[nblocks, block]`` and the grid walks
tiles of whole rows, so every block shape's last dim is the full ``block``
and the scales are a ``[nblocks, 1]`` column — the shapes the TPU compiler
accepts for any ``block`` (a 1-D per-block scale spec of ``(1,)`` is not).
The row tile is a multiple of 32 (int8's sublane tile) or all rows; a
ragged last tile is masked by Pallas, and rows never mix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# ~128K elements per tile: 512 KiB per f32 operand, so the double-buffered
# EF kernel (2 f32 in, 2 f32 + 1 int8 out) stays well inside scoped VMEM
_TILE_ELEMS = 128 * 1024


def _row_tile(nb: int, block: int) -> int:
    rows = max(32, (_TILE_ELEMS // block) // 32 * 32)
    return nb if nb <= rows else rows


def _blocks(x: jax.Array, block: int) -> jax.Array:
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, block)


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)                  # [rows, block]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                        1e-30) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_add_kernel(q_ref, s_ref, acc_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (acc_ref[...].astype(jnp.float32)
                  + q * s_ref[...]).astype(o_ref.dtype)


def quantize_blocks(x: jax.Array, *, block: int = 1024, bits: int = 8,
                    interpret: bool = False):
    """x [n] -> (q int8 [n_pad], scales f32 [nblocks], n)."""
    qmax = float(2 ** (bits - 1) - 1)
    n = x.shape[0]
    xb = _blocks(x, block)
    nb = xb.shape[0]
    rows = _row_tile(nb, block)
    tile = pl.BlockSpec((rows, block), lambda i: (i, 0))
    col = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[tile],
        out_specs=[tile, col],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s.reshape(-1), n


def _ef_quant_kernel(g_ref, e_ref, q_ref, s_ref, d_ref, r_ref, *, qmax: float):
    t = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    # explicit reciprocal multiply, NOT `/ qmax`: XLA rewrites division by a
    # compile-time constant to a reciprocal multiply in some fusion contexts
    # but not others, which would break bit-equality with the reference
    scale = jnp.maximum(jnp.max(jnp.abs(t), axis=1, keepdims=True),
                        1e-30) * (1.0 / qmax)
    q = jnp.clip(jnp.round(t / scale), -qmax, qmax)
    deq = q * scale
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale
    d_ref[...] = deq
    r_ref[...] = t - deq


def ef_quantize_bucketize(grad: jax.Array, residual: jax.Array, *,
                          block: int = 1024, bits: int = 8,
                          interpret: bool = False):
    """Fused EF quantize+bucketize: grad/residual [n] ->
    (q int8 [n_pad], scales f32 [nblocks], deq f32 [n_pad],
    new_residual f32 [n_pad], n).

    q/scales/deq (the wire contract) are bit-equal to
    ``ref.ef_quantize_bucketize_ref``; the residual matches to 1 ulp because
    the fused ``t - q*scale`` contracts into an FMA here while the reference
    rounds the dequantized product first.
    """
    qmax = float(2 ** (bits - 1) - 1)
    n = grad.shape[0]
    gb, eb = _blocks(grad, block), _blocks(residual, block)
    nb = gb.shape[0]
    rows = _row_tile(nb, block)
    tile = pl.BlockSpec((rows, block), lambda i: (i, 0))
    col = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    q, s, deq, new_r = pl.pallas_call(
        functools.partial(_ef_quant_kernel, qmax=qmax),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[tile, tile],
        out_specs=[tile, col, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
        ],
        interpret=interpret,
    )(gb, eb)
    return q.reshape(-1), s.reshape(-1), deq.reshape(-1), new_r.reshape(-1), n


def dequant_add(q: jax.Array, scales: jax.Array, acc: jax.Array, *,
                block: int = 1024, interpret: bool = False) -> jax.Array:
    """acc [n_pad] += dequant(q) (fused); returns same length as acc."""
    nb = scales.shape[0]
    rows = _row_tile(nb, block)
    tile = pl.BlockSpec((rows, block), lambda i: (i, 0))
    col = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _dequant_add_kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[tile, col, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb, block), acc.dtype),
        interpret=interpret,
    )(q.reshape(nb, block), scales.reshape(nb, 1), acc.reshape(nb, block))
    return out.reshape(acc.shape)
