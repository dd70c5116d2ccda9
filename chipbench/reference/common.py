"""What every decoder-family reference shares: float32 arithmetic at
``Precision.HIGHEST`` with the float8 control, RMSNorm, rotate-half rope,
chunked causal attention, the chunked loss over an output head, AdamW, and
the training loop that spreads one step's blocks of rows over the chips.

Nothing here knows a model's weights by name: a reference module gives its
block loss and its initial weights, and this module trains them.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = lax.Precision.HIGHEST
Q_CHUNK = 512        # query rows per attention chunk
LOSS_CHUNK = 512     # tokens per chunk of the output head
ADAM_EPS = 1e-8
FP8_MAX = 448.0      # largest finite float8_e4m3fn


@dataclass(frozen=True)
class Optim:
    lr: float
    warmup_steps: int
    weight_decay: float
    grad_clip: float
    b1: float
    b2: float

    @classmethod
    def from_traffic(cls, t: dict) -> "Optim":
        o = t["train_config"]
        return cls(o["lr"], o["warmup_steps"], o["weight_decay"],
                   o["grad_clip"], o["b1"], o["b2"])


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


# ---------------------------------------------------------------- arithmetic

def fake_fp8(x):
    scale = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def mm(spec, x, y, prec):
    """``einsum`` at ``HIGHEST``; with ``prec="fp8"`` (the control) both
    operands are rounded to float8 e4m3 with a per-tensor scale first,
    straight through in the backward pass."""
    if prec == "fp8":
        x, y = fake_fp8(x), fake_fp8(y)
    return jnp.einsum(spec, x, y, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotate-half rotary embedding of x [B, S, H, D] at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, prec, score_div):
    """Causal attention, one chunk of queries at a time: q and k [B, S, H or
    KV heads, Dqk], v [B, S, KV heads, Dv], KV heads dividing H. The scores
    are divided by ``score_div`` (the square root of Dqk for the plain
    softmax) and the output is [B, S, H, Dv]."""
    b, s, h, _ = q.shape
    dv = v.shape[-1]
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    qc = min(Q_CHUNK, s)
    nq = s // qc

    @jax.checkpoint
    def chunk(args):
        i, qb = args
        sc = mm("bqhd,bkhd->bhqk", qb, k, prec) / score_div
        causal = (i * qc + jnp.arange(qc))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, prec)

    qs = q.reshape(b, nq, qc, h, q.shape[-1]).swapaxes(0, 1)
    out = lax.map(chunk, (jnp.arange(nq), qs))
    return out.swapaxes(0, 1).reshape(b, s, h, dv)


def mean_nll(h, labels, head, prec):
    """Mean next-token cross entropy of hidden states h [T, d] through the
    output head [V, d], ``LOSS_CHUNK`` tokens at a time."""
    n, d = h.shape
    c = min(LOSS_CHUNK, n)

    @jax.checkpoint
    def nll(args):
        hc, lc = args
        logits = mm("td,vd->tv", hc, head, prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lc[:, None], -1)[:, 0])

    sums = lax.map(nll, (h.reshape(n // c, c, d), labels.reshape(n // c, c)))
    return jnp.sum(sums) / n


# ---------------------------------------------------------------- training

def make_grad_fn(loss, devices):
    """(weights, tokens [nb, rows, S], labels) -> (mean loss, mean grads)
    over the nb blocks of ``loss(w, tokens, labels)``, the blocks spread
    over ``devices`` and summed."""
    mesh = Mesh(np.array(devices), ("blocks",))
    vg = jax.value_and_grad(loss)

    def local(w, toks, labs):
        def body(acc, blk):
            l, g = vg(w, *blk)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        (l, g), _ = lax.scan(body, zero, (toks, labs))
        return lax.psum(l, "blocks"), lax.psum(g, "blocks")

    summed = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(), P("blocks"), P("blocks")),
                           out_specs=(P(), P()), check_vma=False)

    @jax.jit
    def fn(w, toks, labs):
        l, g = summed(w, toks, labs)
        nb = toks.shape[0]
        return l / nb, jax.tree.map(lambda x: x / nb, g)

    return fn, NamedSharding(mesh, P()), NamedSharding(mesh, P("blocks"))


@partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1, 2))
def adamw_step(w, m, v, g, o: Optim, t):
    """AdamW at step index t (0-based): clip by the global norm, linear
    warm-up of the learning rate, bias-corrected moments, decoupled weight
    decay on every leaf. Returns the clipped gradient's leaf norms too."""
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, o.grad_clip / jnp.maximum(gn, 1e-12)), g)
    lr = o.lr * t / o.warmup_steps
    n = t + 1.0
    bc1, bc2 = 1.0 - o.b1 ** n, 1.0 - o.b2 ** n
    m = jax.tree.map(lambda m, g: o.b1 * m + (1 - o.b1) * g, m, g)
    v = jax.tree.map(lambda v, g: o.b2 * v + (1 - o.b2) * g * g, v, g)
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS)
                                  + o.weight_decay * w), w, m, v)
    return w, m, v, leaf_norms(g)


def follow(loss, init, o: Optim, seed: int, batches, devices):
    """Train weights ``init(key)`` from the seed's key over ``batches`` (a
    list of (tokens, labels), each [blocks, rows, S]) with the block loss
    ``loss(w, tokens, labels)``. Returns the loss of each step, the per-leaf
    norms of the first clipped gradient, and the per-leaf norms of the
    change of the weights after the last step."""
    assert len(batches) <= o.warmup_steps
    grad_fn, rep, split = make_grad_fn(loss, devices)
    with jax.default_matmul_precision("highest"):
        w = jax.jit(init, out_shardings=rep)(seed_key(seed))
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        losses, g1 = [], None
        for t, (toks, labs) in enumerate(batches):
            toks = jax.device_put(toks, split)
            labs = jax.device_put(labs, split)
            loss_t, g = grad_fn(w, toks, labs)
            w, m, v, gn = adamw_step(w, m, v, g, o, float(t))
            del g
            losses.append(float(loss_t))
            if g1 is None:
                g1 = [float(x) for x in gn]
        del m, v
        dn = delta_norms(w, init, seed)
    return {"loss": losses, "grad": g1, "delta": dn}


def delta_norms(w, init, seed: int) -> list[float]:
    """Per-leaf norm of ``w`` minus ``init`` of the seed's key. A leaf of
    ``w`` larger than the reference's (a program pads its vocabulary) is cut
    to the reference's shape first."""
    @jax.jit
    def fn(w, key):
        w0 = init(key)
        cut = lambda x, y: x[tuple(slice(0, n) for n in y.shape)]
        return leaf_norms(jax.tree.map(
            lambda x, y: cut(x, y).astype(jnp.float32) - y, w, w0))

    return [float(x) for x in fn(w, seed_key(seed))]
