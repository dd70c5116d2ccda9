"""Gradient compression for cross-pod sync (distributed-optimization trick).

Cross-pod ICI/DCN links are the scarcest bandwidth at 512+ chips, so the
trainer can quantize the pod-axis exchange to int8 with per-bucket scales.
Error feedback (Seide et al. / EF-SGD) keeps SGD unbiased-in-the-limit: the
residual of each step's quantization is added back before the next step's
compression.  The EF accumulator lives in the train state (a pytree mirroring
the gradients).

Exchange pattern: recursive-doubling over the pod axis with quantized
payloads — log2(P) steps, each moving bytes/4 (fp32→int8) per chip, which the
planner's α–β model credits as a 4× β-term reduction on that axis.

Since PR 9 the planned stack consumes this module too: ``ef_compress_blocks``
is the per-bucket, per-block-scale EF step behind
``sync_algorithm="planned_compressed"`` (DESIGN.md §15), optionally backed by
the fused pallas quantize+bucketize kernel in ``kernels/quant.py``.  Bits per
element is a first-class plan axis (``PlanKey.bits``), so the planner — not
this module — decides where compression pays.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class QuantChunk(NamedTuple):
    q: jax.Array       # int8 payload
    scale: jax.Array   # f32 scalar scale


def quantize(x: jax.Array, bits: int = 8) -> QuantChunk:
    """Symmetric linear quantization with a per-tensor scale."""
    qmax = float(2 ** (bits - 1) - 1)
    if x.size == 0:  # zero-size leaves (e.g. depth-0 scan stacks)
        return QuantChunk(x.astype(jnp.int8), jnp.ones((), jnp.float32))
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return QuantChunk(q, scale.astype(jnp.float32))


def dequantize(c: QuantChunk, dtype=jnp.float32) -> jax.Array:
    return c.q.astype(dtype) * c.scale.astype(dtype)


def rd_axis_valid(axis_size: int) -> bool:
    """True iff recursive doubling is defined on this axis (power of two)."""
    return axis_size >= 1 and not (axis_size & (axis_size - 1))


def compressed_allreduce_rd(
    x: jax.Array, axis_name: str, axis_size: int, bits: int = 8
) -> jax.Array:
    """All-reduce with int8-quantized recursive-doubling exchanges.

    Every hop transmits (int8 payload, f32 scale); the local accumulator
    stays full precision.  Bytes on the wire per chip: log2(S) · n/4 of the
    fp32 cost (plus one scalar per hop).

    Only defined on power-of-two axes; callers should check
    :func:`rd_axis_valid` at plan time and route other sizes through
    :func:`compressed_allreduce` (which falls back to the ring RS+AG pass).
    """
    s = axis_size
    if s == 1:
        return x
    if s & (s - 1):
        raise ValueError(
            f"compressed_allreduce_rd requires a power-of-two axis size, "
            f"got {s}; use compressed_allreduce() to route non-power-of-two "
            f"axes through the ring RS+AG path"
        )
    acc = x.astype(jnp.float32)
    for k in range(int(math.log2(s))):
        bit = 1 << k
        perm = [(i, i ^ bit) for i in range(s)]
        q = quantize(acc, bits)
        recv_q = lax.ppermute(q.q, axis_name, perm)
        recv_scale = lax.ppermute(q.scale, axis_name, perm)
        acc = acc + recv_q.astype(jnp.float32) * recv_scale
    return acc.astype(x.dtype)


def compressed_allreduce(
    x: jax.Array, axis_name: str, axis_size: int, bits: int = 8
) -> jax.Array:
    """Compressed all-reduce with eager axis-size routing.

    Power-of-two axes take the quantized recursive-doubling exchange;
    everything else falls back to the ring RS+AG pass
    (:func:`collectives.allreduce_ring`) on the full-precision payload — the
    planned stack's shape, always defined.  The routing decision is made
    here, eagerly, from the static ``axis_size``, so no bare ValueError can
    fire mid-trace.
    """
    if axis_size == 1:
        return x
    if rd_axis_valid(axis_size):
        return compressed_allreduce_rd(x, axis_name, axis_size, bits)
    from . import collectives as C

    return C.allreduce_ring(x, axis_name, axis_size)


def ef_compress(grad: jax.Array, residual: jax.Array, bits: int = 8):
    """Error-feedback step: compress (grad + residual), return the quantized
    value to transmit and the new residual."""
    target = grad + residual
    c = quantize(target, bits)
    deq = dequantize(c, target.dtype)
    return c, target - deq


def init_ef_state(grads: jax.Array | dict) -> jax.Array | dict:
    return jax.tree.map(jnp.zeros_like, grads)


def ef_allreduce_tree(
    grads,
    ef_state,
    axis_name: str,
    axis_size: int,
    bits: int = 8,
):
    """Pytree-level error-feedback compressed all-reduce over one axis.

    Returns (synced_grads, new_ef_state).  Each leaf is compressed with EF,
    exchanged via quantized recursive doubling, and averaged.
    """
    def leaf(g, e):
        c, new_e = ef_compress(g, e, bits)
        deq = dequantize(c, jnp.float32)
        summed = compressed_allreduce(deq, axis_name, axis_size, bits)
        return (summed / axis_size).astype(g.dtype), new_e

    # Unzip over the flattened leaves instead of tree-mapping with
    # ``is_leaf=tuple``: model pytrees whose *leaves* are tuples (or whose
    # containers are) would otherwise be misparsed as (synced, residual)
    # pairs.  flatten/unflatten keeps arbitrary treedefs intact.
    g_leaves, treedef = jax.tree.flatten(grads)
    e_leaves = treedef.flatten_up_to(ef_state)
    outs = [leaf(g, e) for g, e in zip(g_leaves, e_leaves)]
    synced = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_ef = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return synced, new_ef


def ef_compress_blocks(
    flat: jax.Array,
    residual: jax.Array,
    *,
    bits: int = 8,
    block: int = 1024,
    fused: bool = False,
    interpret: bool = False,
):
    """Per-block-scale error-feedback compression of one flat bucket.

    The planned-compressed hot path (DESIGN.md §15): compresses
    ``flat + residual`` with one symmetric scale per ``block`` elements and
    returns ``(deq, new_residual)`` where ``deq`` is the dequantized wire
    value (what the planned collective actually reduces) and
    ``new_residual = target - deq`` feeds the next step's EF accumulator.

    ``fused=True`` routes through the pallas quantize+bucketize kernel
    (``kernels.ops.ef_quantize_bucketize``, compiled for the TPU unless the
    caller passes ``interpret=True``); the jnp path below is the
    bit-exact fallback and the kernel's oracle shape.  ``bits >= 32`` is the
    identity (no compression, residual zero).
    """
    if bits >= 32 or flat.size == 0:
        return flat, jnp.zeros_like(residual)
    if fused:
        from ..kernels import ops as kops

        # the length is sliced back statically: the jitted wrapper returns
        # its ``n`` as an array, which cannot bound a slice under a trace
        n = flat.shape[0]
        _q, _s, deq, new_r, _ = kops.ef_quantize_bucketize(
            flat, residual, block=block, bits=bits, interpret=interpret)
        return deq[:n].astype(flat.dtype), new_r[:n].astype(residual.dtype)
    qmax = float(2 ** (bits - 1) - 1)
    n = flat.shape[0]
    pad = (-n) % block
    target = flat.astype(jnp.float32) + residual.astype(jnp.float32)
    tp = jnp.pad(target, (0, pad)) if pad else target
    tb = tp.reshape(-1, block)
    # reciprocal multiply, matching the fused kernel bit-for-bit (quant.py)
    scales = jnp.maximum(jnp.max(jnp.abs(tb), axis=1), 1e-30) * (1.0 / qmax)
    q = jnp.clip(jnp.round(tb / scales[:, None]), -qmax, qmax)
    deq = (q * scales[:, None]).reshape(-1)[:n]
    return deq.astype(flat.dtype), (target - deq).astype(residual.dtype)
