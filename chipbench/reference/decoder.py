"""Plain float32 reference of the decoders the benchmark trains.

Written from the published descriptions of Qwen2 and Granite 3.0 MoE and
from nothing in ``src/``: a GQA decoder with optional QKV bias, rotary
positions (rotate-half), RMSNorm, a SwiGLU MLP or a top-k mixture of SwiGLU
experts, and an embedding tied to the output head; the loss is the mean
next-token cross entropy plus the router's load-balancing term. One AdamW
step with global-norm clipping and linear warm-up follows the published
AdamW. Departures from the published models are listed in each
configuration file under ``departures``.

Everything runs in float32 at ``Precision.HIGHEST``. ``prec="fp8"`` is the
control: every matmul operand is rounded to float8 e4m3 with a per-tensor
scale (straight-through in the backward pass), the precision below the
bfloat16 the configurations compute in.

Weights are a nested dict named as the program names its parameters, with
the layers stacked on a leading axis, so the two can be compared leaf by
leaf. The reference is computed in blocks: one block is one chip's rows,
attention in query chunks, the loss in token chunks, and the experts one at
a time.
"""

from __future__ import annotations

import math
import zlib
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
class Arch:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    qkv_bias: bool
    rope_theta: float
    eps: float
    experts: int = 0
    top_k: int = 0
    capacity_factor: float = 0.0
    aux_weight: float = 0.0
    init_std: float = 0.02

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        moe = c.get("num_local_experts", 0)
        return cls(
            vocab=c["vocab_size"], d=c["hidden_size"],
            layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            ffn=c["intermediate_size"], qkv_bias=c["qkv_bias"],
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            experts=moe, top_k=c.get("num_experts_per_tok", 0),
            capacity_factor=float(c.get("capacity_factor", 0.0)),
            aux_weight=float(c.get("router_aux_loss_coef", 0.0)),
            init_std=float(c["initializer_range"]))


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


# ---------------------------------------------------------------- weights

def weight_shapes(a: Arch) -> dict:
    """Shape of every weight, by the program's parameter names."""
    L, d, hd = a.layers, a.d, a.head_dim
    attn = {"wq": (L, d, a.heads * hd), "wk": (L, d, a.kv_heads * hd),
            "wv": (L, d, a.kv_heads * hd), "wo": (L, a.heads * hd, d)}
    if a.qkv_bias:
        attn.update(bq=(L, a.heads * hd), bk=(L, a.kv_heads * hd),
                    bv=(L, a.kv_heads * hd))
    layer = {"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)}, "attn": attn}
    if a.experts:
        E, f = a.experts, a.ffn
        layer["moe"] = {"router": (L, d, E), "w_gate": (L, E, d, f),
                        "w_up": (L, E, d, f), "w_down": (L, E, f, d)}
    else:
        layer["mlp"] = {"w_gate": (L, d, a.ffn), "w_up": (L, d, a.ffn),
                        "w_down": (L, a.ffn, d)}
    return {"embed": {"tok": (a.vocab, d)}, "layers": layer,
            "final_norm": {"scale": (d,)}}


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_weights(a: Arch, key) -> dict:
    """Normal(0, initializer_range) matrices and biases, unit norm scales.
    Each leaf draws from its own key, folded in by its path, so a leaf's
    values do not depend on which other leaves exist."""
    shapes = weight_shapes(a)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for path, shape in flat:
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) >> 1)
            out.append(a.init_std * jax.random.normal(leaf_key, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


# ---------------------------------------------------------------- layers

def _fake_fp8(x):
    scale = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _mm(spec, x, y, prec):
    if prec == "fp8":
        x, y = _fake_fp8(x), _fake_fp8(y)
    return jnp.einsum(spec, x, y, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary embedding of x [B, S, H, D] at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, prec):
    """Causal GQA attention, one chunk of queries at a time."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    qc = min(Q_CHUNK, s)
    nq = s // qc

    @jax.checkpoint
    def chunk(args):
        i, qb = args
        sc = _mm("bqhd,bkhd->bhqk", qb, k, prec) / math.sqrt(d)
        causal = (i * qc + jnp.arange(qc))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, prec)

    qs = q.reshape(b, nq, qc, h, d).swapaxes(0, 1)
    out = lax.map(chunk, (jnp.arange(nq), qs))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def _mlp(w, x, prec):
    gate = _mm("bsd,df->bsf", x, w["w_gate"], prec)
    up = _mm("bsd,df->bsf", x, w["w_up"], prec)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"], prec)


def _moe(a: Arch, w, x, prec):
    """Top-k routing with gates renormalised over the k chosen experts.
    Expert capacity is a departure the program makes: each expert keeps
    its first ``int(capacity_factor * k * T / E) + 1`` assignments in token
    order (then slot order) and drops the rest."""
    b, s, d = x.shape
    t, e, k = b * s, a.experts, a.top_k
    xt = x.reshape(t, d)
    probs = jax.nn.softmax(_mm("td,de->te", xt, w["router"], prec), axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_e.reshape(t * k), e, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    cap = int(a.capacity_factor * k * t / e) + 1
    gates = jnp.where(slot.reshape(t, k) < cap, gates, 0.0)
    combine = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], top_e].add(gates)

    @jax.checkpoint
    def expert(y, ew):
        wg, wu, wd, c = ew
        h = jax.nn.silu(_mm("td,df->tf", xt, wg, prec)) * _mm("td,df->tf", xt, wu, prec)
        return y + c[:, None] * _mm("tf,fd->td", h, wd, prec), None

    y, _ = lax.scan(expert, jnp.zeros((t, d), jnp.float32),
                    (w["w_gate"], w["w_up"], w["w_down"], combine.T))
    density = onehot.sum(0).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(density * probs.mean(0)) * a.aux_weight
    return y.reshape(b, s, d), aux


def _layer(a: Arch, prec, x, w):
    b, s, _ = x.shape
    at = w["attn"]
    h = _rms(x, w["ln1"]["scale"], a.eps)
    q = _mm("bsd,de->bse", h, at["wq"], prec)
    k = _mm("bsd,de->bse", h, at["wk"], prec)
    v = _mm("bsd,de->bse", h, at["wv"], prec)
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = _rope(q.reshape(b, s, a.heads, a.head_dim), a.rope_theta)
    k = _rope(k.reshape(b, s, a.kv_heads, a.head_dim), a.rope_theta)
    v = v.reshape(b, s, a.kv_heads, a.head_dim)
    o = _attention(q, k, v, prec).reshape(b, s, a.heads * a.head_dim)
    x = x + _mm("bse,ed->bsd", o, at["wo"], prec)
    h = _rms(x, w["ln2"]["scale"], a.eps)
    if a.experts:
        m, aux = _moe(a, w["moe"], h, prec)
    else:
        m, aux = _mlp(w["mlp"], h, prec), jnp.zeros((), jnp.float32)
    return x + m, aux


def block_loss(a: Arch, prec, w, tokens, labels):
    """Mean next-token cross entropy over one block of rows, plus the
    load-balancing term summed over the layers."""
    emb = w["embed"]["tok"]
    x = jnp.take(emb, tokens, axis=0)

    @jax.checkpoint
    def layer(x, lw):
        x, aux = _layer(a, prec, x, lw)
        return x, aux

    x, aux = lax.scan(layer, x, w["layers"])
    h = _rms(x, w["final_norm"]["scale"], a.eps).reshape(-1, a.d)
    lab = labels.reshape(-1)
    n = h.shape[0]
    c = min(LOSS_CHUNK, n)

    @jax.checkpoint
    def nll(args):
        hc, lc = args
        logits = _mm("td,vd->tv", hc, emb, prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lc[:, None], -1)[:, 0])

    sums = lax.map(nll, (h.reshape(n // c, c, a.d), lab.reshape(n // c, c)))
    return jnp.sum(sums) / n + jnp.sum(aux)


# ---------------------------------------------------------------- training

def make_grad_fn(a: Arch, prec: str, devices):
    """(weights, tokens [nb, rows, S], labels) -> (mean loss, mean grads)
    over the nb blocks, the blocks spread over ``devices`` and summed."""
    mesh = Mesh(np.array(devices), ("blocks",))
    vg = jax.value_and_grad(partial(block_loss, a, prec))

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
    decay on every leaf. Returns the clipped gradient too."""
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


def follow(a: Arch, o: Optim, seed: int, batches, devices, prec="f32"):
    """Train the reference from the seed's weights over ``batches`` (a list
    of (tokens, labels), each [blocks, rows, S]). Returns the loss of each
    step, the per-leaf norms of the first clipped gradient, and the per-leaf
    norms of the change of the weights after the last step."""
    assert len(batches) <= o.warmup_steps
    grad_fn, rep, split = make_grad_fn(a, prec, devices)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(init_weights, static_argnums=0, out_shardings=rep)
        w = init(a, seed_key(seed))
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        losses, g1 = [], None
        for t, (toks, labs) in enumerate(batches):
            toks = jax.device_put(toks, split)
            labs = jax.device_put(labs, split)
            loss, g = grad_fn(w, toks, labs)
            w, m, v, gn = adamw_step(w, m, v, g, o, float(t))
            del g
            losses.append(float(loss))
            if g1 is None:
                g1 = [float(x) for x in gn]
        del m, v
        dn = delta_norms(w, a, seed)
    return {"loss": losses, "grad": g1, "delta": dn}


def delta_norms(w, a: Arch, seed: int) -> list[float]:
    """Per-leaf norm of ``w`` minus the seed's initial weights, the first
    ``a.vocab`` rows of the embedding only (a program may pad it)."""
    @jax.jit
    def fn(w, key):
        w0 = init_weights(a, key)
        w = dict(w, embed={"tok": w["embed"]["tok"][: a.vocab]})
        return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y, w, w0))

    return [float(x) for x in fn(w, seed_key(seed))]
