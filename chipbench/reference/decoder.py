"""Plain float32 reference of the GQA decoders the benchmark trains.

Written from the published descriptions of Qwen2 and Granite 3.0 MoE and
from nothing in ``src/``: a GQA decoder with optional QKV bias, rotary
positions (rotate-half), RMSNorm, a SwiGLU MLP or a top-k mixture of SwiGLU
experts, and an embedding tied to the output head; the loss is the mean
next-token cross entropy plus the router's load-balancing term. One AdamW
step with global-norm clipping and linear warm-up follows the published
AdamW (``common.adamw_step``). Departures from the published models are
listed in each configuration file under ``departures``.

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

import dataclasses
import math
import zlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import common
from chipbench.reference.common import mm


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


def program_config(cfg_file: dict):
    """The program's configuration of this file: its registry entry with the
    file's depth, after checking every width, and the tied head, against
    the file. The one function here that reads the program."""
    from repro.configs import registry

    full = registry.get(cfg_file["registry_id"])
    cfg = dataclasses.replace(full, n_layers=cfg_file["num_hidden_layers"])
    a = Arch.from_config(cfg_file)
    got = dict(vocab=cfg.vocab_size, d=cfg.d_model, heads=cfg.n_heads,
               kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
               qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
               eps=cfg.norm_eps,
               ffn=cfg.moe.d_expert if cfg.moe else cfg.d_ff,
               experts=cfg.moe.n_experts if cfg.moe else 0,
               top_k=cfg.moe.top_k if cfg.moe else 0)
    if cfg.moe:
        got.update(capacity_factor=cfg.moe.capacity_factor,
                   aux_weight=cfg.moe.router_aux_weight)
    want = {k: getattr(a, k) for k in got}
    got["tie_embeddings"] = cfg.tie_embeddings
    want["tie_embeddings"] = cfg_file["tie_word_embeddings"]
    differ = sorted(k for k in got if got[k] != want[k])
    if differ:
        raise ValueError(f"{cfg_file['registry_id']}: {', '.join(differ)} differ; "
                         f"the program runs {got}, the configuration file states {want}")
    if not cfg.tie_embeddings:
        raise ValueError(f"{cfg_file['registry_id']}: tie_embeddings is false; this "
                         "reference's output head is its embedding")
    return cfg


# ---------------------------------------------------------------- FLOPs
#
# The arithmetic of the repository's analytic model (6 x active parameters
# per token, plus the causal half of attention's score and value products,
# three times over for the forward and backward passes), kept here so that
# the yardstick does not move when the program does. Recomputation is not
# counted. Active parameters are the matrices a token passes through:
# attention, the dense MLP or the router plus ``top_k / experts`` of the
# routed experts, the norms and biases, and the tied embedding once, as the
# output head (its use as a lookup table costs no FLOPs).

def active_params(c: dict) -> float:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    attn = 2 * d * h * hd + 2 * d * kv * hd
    if c["qkv_bias"]:
        attn += h * hd + 2 * kv * hd
    experts = c.get("num_local_experts", 0)
    if experts:
        ffn = d * experts + 3 * d * c["intermediate_size"] * c["num_experts_per_tok"]
    else:
        ffn = 3 * d * c["intermediate_size"]
    per_layer = attn + ffn + 2 * d
    head = c["vocab_size"] * d
    return float(L * per_layer + d + head)


def attention_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward score and value products of one token, over the
    causal half of a ``seq_len`` context."""
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return 3.0 * c["num_hidden_layers"] * 2 * h * (0.5 * seq_len) * (hd + hd)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 6.0 * active_params(c) + attention_flops_per_token(c, seq_len)


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


# ---------------------------------------------------------------- layers

def _mlp(w, x, prec):
    gate = mm("bsd,df->bsf", x, w["w_gate"], prec)
    up = mm("bsd,df->bsf", x, w["w_up"], prec)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"], prec)


def _moe(a: Arch, w, x, prec):
    """Top-k routing with gates renormalised over the k chosen experts.
    Expert capacity is a departure the program makes: each expert keeps
    its first ``int(capacity_factor * k * T / E) + 1`` assignments in token
    order (then slot order) and drops the rest."""
    b, s, d = x.shape
    t, e, k = b * s, a.experts, a.top_k
    xt = x.reshape(t, d)
    probs = jax.nn.softmax(mm("td,de->te", xt, w["router"], prec), axis=-1)
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
        h = jax.nn.silu(mm("td,df->tf", xt, wg, prec)) * mm("td,df->tf", xt, wu, prec)
        return y + c[:, None] * mm("tf,fd->td", h, wd, prec), None

    y, _ = lax.scan(expert, jnp.zeros((t, d), jnp.float32),
                    (w["w_gate"], w["w_up"], w["w_down"], combine.T))
    density = onehot.sum(0).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(density * probs.mean(0)) * a.aux_weight
    return y.reshape(b, s, d), aux


def _layer(a: Arch, prec, x, w):
    b, s, _ = x.shape
    at = w["attn"]
    h = common.rms(x, w["ln1"]["scale"], a.eps)
    q = mm("bsd,de->bse", h, at["wq"], prec)
    k = mm("bsd,de->bse", h, at["wk"], prec)
    v = mm("bsd,de->bse", h, at["wv"], prec)
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = common.rope(q.reshape(b, s, a.heads, a.head_dim), a.rope_theta)
    k = common.rope(k.reshape(b, s, a.kv_heads, a.head_dim), a.rope_theta)
    v = v.reshape(b, s, a.kv_heads, a.head_dim)
    o = common.attention(q, k, v, prec, math.sqrt(a.head_dim))
    x = x + mm("bse,ed->bsd", o.reshape(b, s, a.heads * a.head_dim), at["wo"], prec)
    h = common.rms(x, w["ln2"]["scale"], a.eps)
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
    h = common.rms(x, w["final_norm"]["scale"], a.eps).reshape(-1, a.d)
    return common.mean_nll(h, labels.reshape(-1), emb, prec) + jnp.sum(aux)


# ---------------------------------------------------------------- training

def follow(a: Arch, o: common.Optim, seed: int, batches, devices, prec="f32"):
    """Train the reference from the seed's weights over ``batches`` (a list
    of (tokens, labels), each [blocks, rows, S]). Returns the loss of each
    step, the per-leaf norms of the first clipped gradient, and the per-leaf
    norms of the change of the weights after the last step."""
    return common.follow(partial(block_loss, a, prec), partial(init_weights, a),
                         o, seed, batches, devices)


def delta_norms(w, a: Arch, seed: int) -> list[float]:
    """Per-leaf norm of ``w`` minus the seed's initial weights, the first
    ``a.vocab`` rows of the embedding only (a program may pad it)."""
    return common.delta_norms(w, partial(init_weights, a), seed)
