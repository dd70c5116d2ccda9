"""Device-level all-reduce implementations (shard_map bodies).

The TPU-native port of the paper's algorithm zoo.  Every function here is a
*manual-collective* body: it must be called inside ``jax.shard_map`` with the
named axis in ``axis_names``.  All take the static ``axis_size`` explicitly
(the mesh is known at trace time; passing it avoids relying on
constant-folding of ``psum(1, axis)``).

Implemented algorithms and their optical-paper counterparts:

    allreduce_psum        XLA's native all-reduce (reference / baseline)
    allreduce_ring        Ring (Patarasuk-Yuan): RS + AG via ppermute,
                          2(S-1) steps of 1/S-chunks   <-> paper's O-Ring
    allreduce_rd          recursive doubling, log2 S full-vector steps
                          <-> paper's RD baseline
    allreduce_bt          binary tree reduce + broadcast  <-> paper's BT
    allreduce_wrht_tree   the paper's contribution: m-ary hierarchical tree
                          with optional single-step all-to-all finish among
                          the surviving representatives.  ``m`` plays the
                          2w+1 role; each of the m-1 member transfers per
                          level is an independent ppermute (parallel
                          wavelengths -> parallel ICI channels).
    hierarchical_allreduce WRHT adapted to a *factorized mesh* (production
                          path): per-level reduce-scatter down the axis list
                          then all-gather back up ("scatter" mode — WRHT's
                          step structure with ring's bandwidth optimality),
                          or per-level full psum ("faithful" mode — the
                          paper's constant-d accounting).

Since PR 5 every *scheduled* collective (DESIGN.md §11) also has its
device-level shard_map twin here, with matching ownership semantics:

    reduce_scatter_ring / all_gather_ring      the ring passes (device i
                          owns chunk i, like the scheduled collectives)
    broadcast_wrht_tree   the WRHT broadcast tree alone (root = device 0)
    alltoall_ppermute     single-phase personalized all-to-all, plus the
                          reduce_scatter_alltoall / all_gather_alltoall
                          single-step finisher variants the planner can pick

Correctness of each against ``allreduce_psum`` is enforced by
``tests/test_collectives.py`` on 8 simulated devices, including a hypothesis
sweep; the scheduled-vs-device conformance pairing lives in
``tests/test_collective_conformance.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _shift_perm(size: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(s, (s + shift) % size) for s in range(size)]


def _pad_to(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    """Flatten to 1-D and zero-pad so length % multiple == 0."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % multiple
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


# The TPU's lane width.  Payloads are indexed chunk by chunk below; viewed as
# [rows, 128] a chunk sits on the chip's native (8, 128) tiles.  As a
# [S, L/S] view of a reshaped multi-dimensional gradient instead, the TPU
# compiler lowers the dynamic chunk indexing into a relayout whose compile
# time grows with the payload (~40 s for one 110 MB bucket on v5e).
_LANES = 128


def _lane_shape(n: int) -> tuple[int, ...]:
    """``n`` elements as [n/128, 128] when 128 divides ``n``, else [n]."""
    return (n // _LANES, _LANES) if n % _LANES == 0 else (n,)


def _chunk_view(flat: jax.Array, s: int) -> jax.Array:
    """The padded 1-D payload's ``s`` chunks as rows: row ``i`` is chunk
    ``i`` in lane layout (the element order of ``flat`` is unchanged)."""
    return flat.reshape(s, *_lane_shape(flat.shape[0] // s))


def _unpad(flat: jax.Array, pad: int, shape: tuple[int, ...]) -> jax.Array:
    if pad:
        flat = flat[: flat.shape[0] - pad]
    return flat.reshape(shape)


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------


def allreduce_psum(x: jax.Array, axis_name: str, axis_size: int | None = None) -> jax.Array:
    """XLA-native all-reduce — the reference the others are tested against."""
    del axis_size
    return lax.psum(x, axis_name)


def allreduce_ring(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Bandwidth-optimal ring all-reduce: reduce-scatter then all-gather,
    2(S-1) ppermute steps carrying 1/S of the payload each."""
    s = axis_size
    if s == 1:
        return x
    shape = x.shape
    flat, pad = _pad_to(x, s)
    chunks = _chunk_view(flat, s)  # [S, L/S] in lane layout
    idx = lax.axis_index(axis_name)
    perm = _shift_perm(s)

    def chunk(c):
        return lax.dynamic_index_in_dim(chunks, c % s, axis=0, keepdims=False)

    # reduce-scatter: after S-1 hops node i owns fully-reduced chunk i
    send = chunk(idx + s - 1)
    for t in range(1, s):
        recv = lax.ppermute(send, axis_name, perm)
        send = recv + chunk(idx + s - 1 - t)

    # all-gather: circulate the owned chunk S-1 more hops
    out = jnp.zeros_like(chunks)
    out = lax.dynamic_update_index_in_dim(out, send, idx % s, axis=0)
    cur = send
    for t in range(1, s):
        cur = lax.ppermute(cur, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, cur, (idx - t) % s, axis=0)
    return _unpad(out.reshape(-1), pad, shape)


def reduce_scatter_ring(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Ring reduce-scatter only (returns this device's owned 1/S chunk of the
    padded flat payload; device ``i`` owns chunk ``i``, exactly the scheduled
    ``reduce_scatter`` collective's ownership map, DESIGN.md §11)."""
    s = axis_size
    if s == 1:
        return x.reshape(-1)
    flat, _ = _pad_to(x, s)
    chunks = _chunk_view(flat, s)
    idx = lax.axis_index(axis_name)
    perm = _shift_perm(s)

    def chunk(c):
        return lax.dynamic_index_in_dim(chunks, c % s, axis=0, keepdims=False)

    send = chunk(idx + s - 1)
    for t in range(1, s):
        recv = lax.ppermute(send, axis_name, perm)
        send = recv + chunk(idx + s - 1 - t)
    return send.reshape(-1)


def all_gather_ring(shard: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Ring all-gather: circulate this device's owned chunk ``S-1`` hops and
    return the concatenation (chunk ``i`` from device ``i``) — the device
    twin of the scheduled ``all_gather`` ring pass (DESIGN.md §11) and the
    inverse of :func:`reduce_scatter_ring`."""
    s = axis_size
    flat = shard.reshape(-1)
    if s == 1:
        return flat
    idx = lax.axis_index(axis_name)
    perm = _shift_perm(s)
    cur = flat.reshape(_lane_shape(flat.shape[0]))
    out = jnp.zeros((s, *cur.shape), flat.dtype)
    out = lax.dynamic_update_index_in_dim(out, cur, idx % s, axis=0)
    for t in range(1, s):
        cur = lax.ppermute(cur, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, cur, (idx - t) % s, axis=0)
    return out.reshape(-1)


def broadcast_wrht_tree(x: jax.Array, axis_name: str, axis_size: int,
                        m: int = 2) -> jax.Array:
    """WRHT broadcast tree alone: device 0's value propagated to every
    device down the m-ary levels — the device twin of the scheduled
    ``broadcast`` collective (DESIGN.md §11; the scheduled root is the
    tree's surviving representative, here canonicalized to device 0)."""
    s = axis_size
    if s == 1:
        return x
    if m < 2:
        raise ValueError("m must be >= 2")
    idx = lax.axis_index(axis_name)
    strides = []
    stride = 1
    while stride < s:
        strides.append(stride)
        stride *= m
    for stride in reversed(strides):
        span = stride * m
        for j in range(1, m):
            perm = [
                (h, h + j * stride)
                for h in range(0, s, span)
                if h + j * stride < s
            ]
            if not perm:
                continue
            recv = lax.ppermute(x, axis_name, perm)
            is_member = (idx % span) == (j * stride)
            x = jnp.where(is_member, recv, x)
    return x


def alltoall_ppermute(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Single-phase personalized all-to-all: row ``j`` of the ``[S, ...]``
    input is this device's message for device ``j``; row ``i`` of the output
    is the message received from device ``i`` — the device twin of the
    scheduled one-step ``alltoall`` collective (DESIGN.md §11), expressed as
    S-1 rotation ppermutes (parallel wavelengths → parallel ICI channels).
    """
    s = axis_size
    if x.shape[0] != s:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {s}")
    if s == 1:
        return x
    idx = lax.axis_index(axis_name)
    self_msg = lax.dynamic_index_in_dim(x, idx % s, axis=0, keepdims=False)
    out = jnp.zeros_like(x)
    out = lax.dynamic_update_index_in_dim(out, self_msg, idx % s, axis=0)
    for off in range(1, s):
        msg = lax.dynamic_index_in_dim(x, (idx + off) % s, axis=0,
                                       keepdims=False)
        perm = [(i, (i + off) % s) for i in range(s)]
        recv = lax.ppermute(msg, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, recv, (idx - off) % s,
                                              axis=0)
    return out


def reduce_scatter_alltoall(x: jax.Array, axis_name: str,
                            axis_size: int) -> jax.Array:
    """Reduce-scatter via the single-step all-to-all finisher: every device
    posts its local chunk ``j`` to device ``j`` and locally reduces what it
    received.  Same ownership map as :func:`reduce_scatter_ring` (device
    ``i`` owns chunk ``i``); the optical plan trades ``S-1``
    reconfigurations for ``⌈S²/8⌉`` wavelengths (DESIGN.md §11)."""
    s = axis_size
    if s == 1:
        return x.reshape(-1)
    flat, _ = _pad_to(x, s)
    recv = alltoall_ppermute(_chunk_view(flat, s), axis_name, s)
    return recv.sum(axis=0).reshape(-1)


def all_gather_alltoall(shard: jax.Array, axis_name: str,
                        axis_size: int) -> jax.Array:
    """All-gather via the single-step all-to-all finisher: every device
    posts its owned shard to every peer in one exchange.  Bit-compatible
    output with :func:`all_gather_ring`."""
    s = axis_size
    flat = shard.reshape(-1)
    if s == 1:
        return flat
    lanes = flat.reshape(_lane_shape(flat.shape[0]))
    msgs = jnp.broadcast_to(lanes[None], (s, *lanes.shape))
    recv = alltoall_ppermute(msgs, axis_name, s)
    return recv.reshape(-1)


def allreduce_rd(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Recursive doubling: log2(S) full-vector pairwise exchanges."""
    s = axis_size
    if s & (s - 1):
        raise ValueError("recursive doubling needs a power-of-two axis")
    for k in range(int(math.log2(s))):
        bit = 1 << k
        perm = [(i, i ^ bit) for i in range(s)]
        x = x + lax.ppermute(x, axis_name, perm)
    return x


def allreduce_bt(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Binary-tree: reduce to device 0 then mirrored broadcast (the paper's
    BT baseline, Fig. 2a) — 2⌈log2 S⌉ full-vector steps."""
    return allreduce_wrht_tree(x, axis_name, axis_size, m=2, alltoall_max=1)


# ---------------------------------------------------------------------------
# the paper's contribution, ported
# ---------------------------------------------------------------------------


def allreduce_wrht_tree(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    m: int,
    alltoall_max: int | None = None,
) -> jax.Array:
    """WRHT on one device axis: hierarchical m-ary tree reduce + broadcast.

    Level ``ℓ`` groups the surviving representatives (indices ≡ 0 mod
    ``m**ℓ``) in runs of ``m``; each member sends its full partial vector to
    the group head (m-1 ppermutes = the paper's ⌈m/2⌉-wavelength parallel
    drain).  When ≤ ``alltoall_max`` representatives survive, they finish
    with a single all-to-all exchange (paper Sec. III-C: saves one broadcast
    level); otherwise recursion reaches a single root.  Broadcast mirrors the
    reduce levels.
    """
    s = axis_size
    if s == 1:
        return x
    if m < 2:
        raise ValueError("m must be >= 2")
    idx = lax.axis_index(axis_name)

    tree_strides: list[int] = []
    stride = 1
    did_alltoall = False
    while True:
        active = list(range(0, s, stride))
        if len(active) == 1:
            break
        if alltoall_max is not None and 1 < len(active) <= alltoall_max:
            # single-step all-to-all among survivors: every rep sends its
            # pre-step partial to every other rep (paper's ⌈m*²/8⌉-wavelength
            # final step).
            x0 = x
            for j in range(1, len(active)):
                perm = [
                    (active[k], active[(k + j) % len(active)])
                    for k in range(len(active))
                ]
                x = x + lax.ppermute(x0, axis_name, perm)
            did_alltoall = True
            break
        # one m-ary reduce level: members j=1..m-1 drain into group heads
        span = stride * m
        for j in range(1, m):
            perm = [
                (h + j * stride, h)
                for h in range(0, s, span)
                if h + j * stride < s
            ]
            if perm:
                x = x + lax.ppermute(x, axis_name, perm)
        tree_strides.append(stride)
        stride = span

    if not did_alltoall and not tree_strides:
        return x  # degenerate (s == 1 handled above)

    # broadcast stage: reverse the tree levels (all-to-all level, if any,
    # already left every survivor with the full reduction)
    for stride in reversed(tree_strides):
        span = stride * m
        for j in range(1, m):
            perm = [
                (h, h + j * stride)
                for h in range(0, s, span)
                if h + j * stride < s
            ]
            if not perm:
                continue
            recv = lax.ppermute(x, axis_name, perm)
            is_member = (idx % span) == (j * stride)
            x = jnp.where(is_member, recv, x)
    return x


def hierarchical_allreduce(
    x: jax.Array,
    axis_names: tuple[str, ...],
    axis_sizes: tuple[int, ...],
    mode: str = "scatter",
) -> jax.Array:
    """WRHT adapted to a factorized device mesh (production gradient sync).

    ``axis_names`` lists the mesh axes innermost-first (e.g. ``("data",
    "pod")``): level ℓ of the paper's tree = axis ℓ.  Two modes:

    - ``"faithful"``: full-vector psum per level — the paper's constant-``d``
      accounting (minimum steps, redundant bytes).
    - ``"scatter"``: reduce-scatter down the hierarchy, all-gather back up —
      WRHT's tree structure with ring's bandwidth optimality (beyond-paper
      optimization; see EXPERIMENTS.md §Perf).
    """
    if mode == "faithful":
        for ax in axis_names:
            x = lax.psum(x, ax)
        return x
    if mode == "flat":
        return lax.psum(x, axis_names)
    if mode != "scatter":
        raise ValueError(f"unknown mode {mode!r}")
    shape = x.shape
    total = math.prod(axis_sizes)
    flat, pad = _pad_to(x, total)
    for ax in axis_names:
        flat = lax.psum_scatter(flat, ax, scatter_dimension=0, tiled=True)
    for ax in reversed(axis_names):
        flat = lax.all_gather(flat, ax, axis=0, tiled=True)
    return _unpad(flat, pad, shape)


ALGORITHMS = {
    "psum": allreduce_psum,
    "ring": allreduce_ring,
    "rd": allreduce_rd,
    "bt": allreduce_bt,
    "wrht": allreduce_wrht_tree,
}


def allreduce(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    algorithm: str = "psum",
    **kw,
) -> jax.Array:
    if algorithm == "psum":
        kw = {}  # the XLA reference takes no tuning knobs
    return ALGORITHMS[algorithm](x, axis_name, axis_size, **kw)


def make_sharded_allreduce(mesh, axis_name: str, algorithm: str = "psum", **kw):
    """Build a jit-able all-reduce over one mesh axis.

    Takes a stacked input of shape ``[axis_size, ...]`` (row i = device i's
    local contribution) and returns the same shape where every row equals the
    sum — so callers/tests can express *different* per-device operands
    without lying about replication.
    """
    from jax.sharding import PartitionSpec as P

    size = mesh.shape[axis_name]
    fn = ALGORITHMS[algorithm]

    def body(stacked):  # [1, ...] local slice
        local = stacked[0]
        out = fn(local, axis_name, size, **kw)
        return out[None]

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
        axis_names={axis_name},
    )
