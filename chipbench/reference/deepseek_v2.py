"""Plain float32 reference of DeepSeek-V2 as the benchmark trains it: one
chip's share of an expert-parallel layer.

Written from the published DeepSeek-V2 description (arXiv:2405.04434 and
the model's ``modeling_deepseek.py``) and from nothing in ``src/``:

- Multi-head Latent Attention without query compression: q is one
  projection into nope and rope parts; keys and values come from a
  compressed latent (RMSNorm) through one up-projection, plus one rope key
  shared by the heads. YaRN rope on the rope parts (rotate-half), and the
  softmax scale ``mscale(factor, mscale_all_dim)**2 / sqrt(q head size)``.
- A leading dense SwiGLU layer, then mixture-of-experts layers: softmax
  router over all routed experts, greedy top-k, gates not renormalised,
  shared experts on every token, and only the experts this chip holds
  computed (``experts_held_from`` and the file's ``n_routed_experts``);
  entries routed elsewhere add nothing here.
- An untied output head over the vocabulary slice; the loss is the mean
  next-token cross entropy plus the router's load-balancing term.

One AdamW step follows ``common.adamw_step``. Departures from the published
model are listed in the configuration file under ``departures``.

Everything runs in float32 at ``Precision.HIGHEST``; ``prec="fp8"`` is the
control (``common.mm``). The reference is computed in blocks: one block is
one chip's rows, attention in query chunks, the loss in token chunks, and
the held experts one at a time, each over the entries routed to it.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import common
from chipbench.reference.common import mm


@dataclass(frozen=True)
class Arch:
    vocab: int
    d: int
    layers: int              # the dense ones included
    dense_layers: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ffn: int
    expert_ffn: int
    experts: int             # the router's width
    held_from: int
    held: int
    shared: int
    top_k: int
    norm_topk: bool
    capacity_factor: float
    aux_weight: float
    rope_theta: float
    yarn: tuple               # (factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim)
    eps: float
    init_std: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        y = c["rope_scaling"]
        return cls(
            vocab=c["vocab_size"], d=c["hidden_size"],
            layers=c["num_hidden_layers"], dense_layers=c["first_k_dense_replace"],
            heads=c["num_attention_heads"], kv_rank=c["kv_lora_rank"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], dense_ffn=c["intermediate_size"],
            expert_ffn=c["moe_intermediate_size"],
            experts=c["experts_routed_over"], held_from=c["experts_held_from"],
            held=c["n_routed_experts"], shared=c["n_shared_experts"],
            top_k=c["num_experts_per_tok"], norm_topk=c["norm_topk_prob"],
            capacity_factor=float(c["capacity_factor"]),
            aux_weight=float(c["router_aux_loss_coef"]),
            rope_theta=float(c["rope_theta"]),
            yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]),
                  float(y["mscale"]), float(y["mscale_all_dim"])),
            eps=float(c["rms_norm_eps"]), init_std=float(c["initializer_range"]))


# what the reference computes, as the published config states it; a file
# that states otherwise is not this model
PUBLISHED = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
             "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
             "moe_layer_freq": 1, "attention_bias": False, "q_lora_rank": None,
             "tie_word_embeddings": False}


def program_config(cfg_file: dict):
    """The program's configuration of this file: its registry entry with the
    file's depth, vocabulary slice and held experts, after checking every
    width and flag the reference depends on against the file. The one
    function here that reads the program."""
    from repro.configs import registry

    who = cfg_file["registry_id"]
    stated = {k: cfg_file.get(k, "absent") for k in PUBLISHED}
    if stated != PUBLISHED:
        bad = sorted(k for k in PUBLISHED if stated[k] != PUBLISHED[k])
        raise ValueError(f"{who}: {', '.join(bad)} differ from what this reference "
                         f"computes: the file states {stated}, the reference {PUBLISHED}")
    full = registry.get(who)
    a = Arch.from_config(cfg_file)
    m, mo, y = full.mla, full.moe, full.rope_scaling
    got = dict(
        d=full.d_model, dense_layers=full.first_k_dense, heads=full.n_heads,
        kv_rank=m.kv_lora_rank if m else None, q_rank=m.q_lora_rank if m else None,
        nope=m.qk_nope_head_dim if m else None, rope=m.qk_rope_head_dim if m else None,
        v_dim=m.v_head_dim if m else None, dense_ffn=full.dense_d_ff,
        expert_ffn=mo.d_expert if mo else None, experts=mo.n_experts if mo else None,
        shared=mo.n_shared if mo else None, top_k=mo.top_k if mo else None,
        norm_topk=mo.norm_topk_prob if mo else None,
        capacity_factor=mo.capacity_factor if mo else None,
        aux_weight=mo.router_aux_weight if mo else None,
        rope_theta=full.rope_theta, eps=full.norm_eps,
        yarn=None if y is None else (y.factor, y.original_max_position, y.beta_fast,
                                     y.beta_slow, y.mscale, y.mscale_all_dim),
        tie_embeddings=full.tie_embeddings, act=full.act)
    want = {k: getattr(a, k) for k in got if hasattr(a, k)}
    want.update(q_rank=None, tie_embeddings=False, act="silu")
    differ = sorted(k for k in got if got[k] != want[k])
    if differ:
        raise ValueError(f"{who}: {', '.join(differ)} differ; the program runs "
                         f"{got}, the configuration file states {want}")
    if not 0 <= a.held_from <= a.experts - a.held:
        raise ValueError(f"{who}: experts {a.held_from}..{a.held_from + a.held - 1} "
                         f"are not among the router's {a.experts}")
    return dataclasses.replace(
        full, n_layers=a.layers, vocab_size=a.vocab,
        moe=dataclasses.replace(mo, held_from=a.held_from, n_held=a.held))


# ---------------------------------------------------------------- FLOPs
#
# Model FLOPs of one training token on this chip: 6 x the parameters a token
# passes through, plus the causal half of attention's score and value
# products, three times over for the forward and backward passes. A token
# passes through each layer's attention matrices, the dense layer's SwiGLU,
# each MoE layer's router and shared experts and ``top_k * held / experts``
# routed experts (the share of its routed entries this chip computes), the
# norms, and the output head; the embedding is a lookup. No recomputation
# is counted.

def active_params(c: dict) -> float:
    a = Arch.from_config(c)
    d, h = a.d, a.heads
    attn = (d * h * (a.nope + a.rope) + d * (a.kv_rank + a.rope)
            + a.kv_rank * h * (a.nope + a.v_dim) + h * a.v_dim * d + a.kv_rank)
    norms = 2 * d
    dense = 3 * d * a.dense_ffn
    routed = a.top_k * a.held / a.experts
    moe = d * a.experts + 3 * d * a.expert_ffn * (a.shared + routed)
    moe_layers = a.layers - a.dense_layers
    return float(a.layers * (attn + norms) + a.dense_layers * dense
                 + moe_layers * moe + d + a.vocab * d)


def attention_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward score and value products of one token, over the
    causal half of a ``seq_len`` context."""
    a = Arch.from_config(c)
    return 3.0 * a.layers * 2 * a.heads * (0.5 * seq_len) * (a.nope + a.rope + a.v_dim)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 6.0 * active_params(c) + attention_flops_per_token(c, seq_len)


# ---------------------------------------------------------------- weights

def _layer_shapes(a: Arch, n: int | None, dense: bool) -> dict:
    lead = () if n is None else (n,)
    d, h = a.d, a.heads
    attn = {"wq": (d, h * (a.nope + a.rope)), "wkv_a": (d, a.kv_rank + a.rope),
            "kv_norm": (a.kv_rank,),
            "wkv_b": (a.kv_rank, h * (a.nope + a.v_dim)), "wo": (h * a.v_dim, d)}
    layer = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)}, "attn": attn}
    if dense:
        f = a.dense_ffn
        layer["mlp"] = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    else:
        E, f, fs = a.held, a.expert_ffn, a.expert_ffn * a.shared
        layer["moe"] = {"router": (d, a.experts), "w_gate": (E, d, f),
                        "w_up": (E, d, f), "w_down": (E, f, d),
                        "shared": {"w_gate": (d, fs), "w_up": (d, fs), "w_down": (fs, d)}}
    return jax.tree.map(lambda s: lead + s, layer, is_leaf=lambda x: isinstance(x, tuple))


def weight_shapes(a: Arch) -> dict:
    """Shape of every weight, by the program's parameter names."""
    return {"embed": {"tok": (a.vocab, a.d), "unembed": (a.d, a.vocab)},
            "dense_layers": [_layer_shapes(a, None, True) for _ in range(a.dense_layers)],
            "layers": _layer_shapes(a, a.layers - a.dense_layers, False),
            "final_norm": {"scale": (a.d,)}}


def init_weights(a: Arch, key) -> dict:
    """Normal(0, initializer_range) matrices, unit norm scales (``scale``,
    and the latent's ``kv_norm``). Each leaf draws from its own key, folded
    in by its path, so a leaf's values do not depend on which other leaves
    exist."""
    shapes = weight_shapes(a)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for path, shape in flat:
        name = jax.tree_util.keystr(path)
        if name.endswith(("['scale']", "['kv_norm']")):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) >> 1)
            out.append(a.init_std * jax.random.normal(leaf_key, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------- layers

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: tuple) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies, float64."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def softmax_scale(a: Arch) -> float:
    factor, mscale_all = a.yarn[0], a.yarn[5]
    return yarn_mscale(factor, mscale_all) ** 2 / math.sqrt(a.nope + a.rope)


def yarn_rope(x, a: Arch):
    """Rotate-half YaRN rope of x [B, S, H, D] at positions 0..S-1."""
    s, dim = x.shape[1], x.shape[-1]
    factor, _, _, _, mscale, mscale_all = a.yarn
    ang = np.arange(s)[:, None] * yarn_inv_freq(dim, a.rope_theta, a.yarn)[None, :]
    mag = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    cos = jnp.asarray(np.cos(ang) * mag, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * mag, jnp.float32)[None, :, None]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a: Arch, w, h, prec):
    """Multi-head latent attention, per-head keys and values decompressed."""
    b, s, _ = h.shape
    H = a.heads
    q = mm("bsd,de->bse", h, w["wq"], prec).reshape(b, s, H, a.nope + a.rope)
    kv_a = mm("bsd,de->bse", h, w["wkv_a"], prec)
    c_kv = common.rms(kv_a[..., : a.kv_rank], w["kv_norm"], a.eps)
    k_pe = yarn_rope(kv_a[..., a.kv_rank:][:, :, None, :], a)            # [B,S,1,dr]
    kv = mm("bsr,re->bse", c_kv, w["wkv_b"], prec).reshape(b, s, H, a.nope + a.v_dim)
    k_nope, v = kv[..., : a.nope], kv[..., a.nope:]
    q = jnp.concatenate([q[..., : a.nope], yarn_rope(q[..., a.nope:], a)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, H, a.rope))], -1)
    o = common.attention(q, k, v, prec, 1.0 / softmax_scale(a))
    return mm("bse,ed->bsd", o.reshape(b, s, H * a.v_dim), w["wo"], prec)


def _mlp(w, x, prec):
    gate = mm("...d,df->...f", x, w["w_gate"], prec)
    up = mm("...d,df->...f", x, w["w_up"], prec)
    return mm("...f,fd->...d", jax.nn.silu(gate) * up, w["w_down"], prec)


def _moe(a: Arch, w, x, prec):
    """Softmax router over all ``a.experts``, greedy top-k, gates as they
    come (or renormalised over the k chosen). Expert capacity is a
    departure the program makes: each expert keeps its first
    ``int(capacity_factor * k * T / experts) + 1`` entries in token order
    (then slot order) and drops the rest. Only the held experts are
    computed, each over the tokens whose entries it kept; the shared
    experts run on every token."""
    b, s, d = x.shape
    t, e, k = b * s, a.experts, a.top_k
    xt = x.reshape(t, d)
    probs = jax.nn.softmax(mm("td,de->te", xt, w["router"], prec), axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    gates = top_p / jnp.sum(top_p, -1, keepdims=True) if a.norm_topk else top_p
    onehot = jax.nn.one_hot(top_e.reshape(t * k), e, dtype=jnp.int32)
    queue = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1).reshape(t, k)
    cap = int(a.capacity_factor * k * t / e) + 1
    kept = queue < cap
    rows = jnp.arange(t)[:, None]
    gate_te = jnp.zeros((t, e), jnp.float32).at[rows, top_e].add(jnp.where(kept, gates, 0.0))
    kept_te = jnp.zeros((t, e), bool).at[rows, top_e].set(kept)
    held = slice(a.held_from, a.held_from + a.held)

    @jax.checkpoint
    def expert(y, ew):
        wg, wu, wd, mine, gate = ew
        n = jnp.sum(mine)
        tok = jnp.nonzero(mine, size=cap, fill_value=0)[0]                 # [cap]
        c = jnp.where(jnp.arange(cap) < n, gate[tok], 0.0)
        xe = xt[tok]
        he = jax.nn.silu(mm("td,df->tf", xe, wg, prec)) * mm("td,df->tf", xe, wu, prec)
        return y.at[tok].add(c[:, None] * mm("tf,fd->td", he, wd, prec)), None

    y, _ = lax.scan(expert, jnp.zeros((t, d), jnp.float32),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     kept_te[:, held].T, gate_te[:, held].T))
    y = y + _mlp(w["shared"], xt, prec)
    density = onehot.sum(0).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(density * probs.mean(0)) * a.aux_weight
    return y.reshape(b, s, d), aux


def _layer(a: Arch, prec, dense, x, w):
    x = x + _attention(a, w["attn"], common.rms(x, w["ln1"]["scale"], a.eps), prec)
    h = common.rms(x, w["ln2"]["scale"], a.eps)
    if dense:
        return x + _mlp(w["mlp"], h, prec), jnp.zeros((), jnp.float32)
    m, aux = _moe(a, w["moe"], h, prec)
    return x + m, aux


def block_loss(a: Arch, prec, w, tokens, labels):
    """Mean next-token cross entropy over one block of rows through the
    untied head, plus the load-balancing term summed over the MoE layers."""
    x = jnp.take(w["embed"]["tok"], tokens, axis=0)
    for dw in w["dense_layers"]:
        x, _ = jax.checkpoint(partial(_layer, a, prec, True))(x, dw)
    x, aux = lax.scan(jax.checkpoint(partial(_layer, a, prec, False)), x, w["layers"])
    h = common.rms(x, w["final_norm"]["scale"], a.eps).reshape(-1, a.d)
    return common.mean_nll(h, labels.reshape(-1), w["embed"]["unembed"].T, prec) + jnp.sum(aux)


# ---------------------------------------------------------------- training

def follow(a: Arch, o: common.Optim, seed: int, batches, devices, prec="f32"):
    """Train the reference from the seed's weights over ``batches`` (a list
    of (tokens, labels), each [blocks, rows, S]). Returns the loss of each
    step, the per-leaf norms of the first clipped gradient, and the per-leaf
    norms of the change of the weights after the last step."""
    return common.follow(partial(block_loss, a, prec), partial(init_weights, a),
                         o, seed, batches, devices)


def delta_norms(w, a: Arch, seed: int) -> list[float]:
    """Per-leaf norm of ``w`` minus the seed's initial weights."""
    return common.delta_norms(w, partial(init_weights, a), seed)
