"""Shared neural layers (pure functional JAX — params are nested dicts).

Conventions:
  * ``init_*`` returns a params pytree; ``*_apply`` consumes it.
  * activations flow in ``cdt`` (compute dtype, usually bf16); params are
    stored in the config's param dtype and cast at use.
  * attention tensors use [batch, seq, heads, head_dim] at rest and
    [batch, heads, seq, head_dim] inside kernels.
  * no sequence-quadratic op materializes an S×S score matrix.  Causal
    self-attention without a cache or a window runs, on a TPU, as one fused
    Pallas kernel forward and backward (:func:`fused_causal_attention`);
    every other attention, and every attention off the TPU, goes through
    :func:`blocked_attention` (online-softmax flash pattern) or
    :func:`decode_attention`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnScaling
from repro.runtime import tracing

Init = jax.nn.initializers.normal


def _dense_init(key, shape, dtype, scale=0.02):
    return Init(scale)(key, shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype) -> dict:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def norm_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, y: YarnScaling) -> jax.Array:
    """DeepSeek-V2's YaRN frequencies: each frequency index i < head_dim/2
    blends the original frequency and the one interpolated by ``factor``,
    the interpolated one wholly from ``high`` up and the original one wholly
    below ``low``, linearly between.  ``low`` and ``high`` are the indices
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    def index(turns):
        return (head_dim * math.log(y.original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index(y.beta_fast)), 0)
    high = min(math.ceil(index(y.beta_slow)), head_dim - 1)
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 1e-3), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scale: float = 1.0, scaling: YarnScaling | None = None) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (int).  ``scale`` multiplies the
    rotated x in f32, before its one cast back to x's dtype.  ``scaling``
    takes the frequencies and the cos/sin magnitude from YaRN."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if scaling is None else yarn_freqs(d, theta, scaling)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if mag != 1.0:
            cos, sin = cos * mag, sin * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if scale != 1.0:
        out = out * scale
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocked (flash-pattern) attention — pure jnp oracle of kernels/flash_attention
# ---------------------------------------------------------------------------

def blocked_attention(
    q: jax.Array,              # [B, Sq, H, D]
    k: jax.Array,              # [B, Skv, K, D]
    v: jax.Array,              # [B, Skv, K, Dv]
    *,
    causal: bool = True,
    q_offset: int = 0,
    window: int | None = None,
    scale: float | None = None,
    q_block: int = 512,
    kv_block: int = 1024,
) -> jax.Array:
    """Online-softmax attention; never materializes [Sq, Skv].

    GQA: H = K * G handled by folding the group into the batch of the
    einsum.  Peak live intermediate: [B, H, q_block, kv_block].
    """
    b, sq, h, d = q.shape
    skv, kh, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qb = min(q_block, sq)
    kvb = min(kv_block, skv)
    pad_q = (-sq) % qb
    pad_kv = (-skv) % kvb
    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else k
    vf = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else v
    nq, nkv = qf.shape[1] // qb, kf.shape[1] // kvb

    # [nq, B, K, G, qb, D] / [nkv, B, K, kvb, D]
    qs = qf.reshape(b, nq, qb, kh, g, d).transpose(1, 0, 3, 4, 2, 5)
    ks = kf.reshape(b, nkv, kvb, kh, d).transpose(1, 0, 3, 2, 4)
    vs = vf.reshape(b, nkv, kvb, kh, dv).transpose(1, 0, 3, 2, 4)

    kv_pos = jnp.arange(nkv * kvb).reshape(nkv, kvb)

    def q_block_fn(args):
        qi, qblk = args                      # qblk [B, K, G, qb, D]
        q_pos = q_offset + qi * qb + jnp.arange(qb)

        def kv_step(carry, inp):
            m, l, acc = carry
            kblk, vblk, kpos = inp           # [B,K,kvb,D], [B,K,kvb,Dv], [kvb]
            s = jnp.einsum(
                "bkgqd,bksd->bkgqs", qblk.astype(jnp.float32),
                kblk.astype(jnp.float32),
            ) * scale
            mask = jnp.ones((qb, kvb), bool)
            if causal:
                mask &= q_pos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows (padded tail): keep m finite
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p, vblk.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, qb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qb), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qb, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (ks, vs, kv_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                            # [B, K, G, qb, Dv]

    outs = lax.map(q_block_fn, (jnp.arange(nq), qs))   # [nq, B, K, G, qb, Dv]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * qb, h, dv)
    if pad_q:
        out = out[:, :sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,              # [B, 1, H, D]
    k_cache: jax.Array,        # [B, S, K, D]
    v_cache: jax.Array,        # [B, S, K, Dv]
    length: jax.Array | int,   # valid prefix length (scalar or [B])
    *,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Single-token attention against a KV cache: [B,H,S] scores, no S×S."""
    b, _, h, d = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qh = q.reshape(b, kh, g, d)
    # preferred_element_type keeps the accumulation in f32 WITHOUT
    # materializing an f32 copy of the whole cache (measured 2×6.4 GiB/device
    # on the 67B decode cell — see §Perf hypothesis log)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qh, k_cache.astype(qh.dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    pos = jnp.arange(s)
    larr = jnp.asarray(length)
    if larr.ndim == 0:
        valid = (pos < larr)[None, None, None, :]
        if window is not None:
            valid = jnp.logical_and(valid, (pos >= larr - window)[None, None, None, :])
    else:
        valid = (pos[None, :] < larr[:, None])[:, None, None, :]
        if window is not None:
            valid = jnp.logical_and(
                valid, (pos[None, :] >= larr[:, None] - window)[:, None, None, :])
    scores = jnp.where(valid, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused causal attention (TPU) — JAX's splash attention kernel
# ---------------------------------------------------------------------------

# the name splash attention gives its forward kernel in the compiled program
FUSED_KERNEL = "splash_mqa_fwd"


def fused_attention_blocks(seq_len: int) -> splash.BlockSizes | None:
    """The kernel's tiles for one KV head's causal self-attention over
    ``seq_len`` tokens, or None where the sequence is no multiple of them.

    Chosen on a TPU v5e at both training cells' shapes (2,048 tokens with
    head_dim 128, 4,096 with 64), timing the forward, its remat recompute
    and the backward together: 1,024-row q and kv tiles, the forward's
    scores taken 512 keys at a time, and the fused backward (dq, dk, dv in
    one kernel) beat every other tiling tried and the separate dq kernel at
    both, so the head size does not enter the choice."""
    bq = bkv = min(1024, seq_len)
    if bkv % 128 or seq_len % bkv:
        return None
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=min(512, bkv),
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        use_fused_bwd_kernel=True)


def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Causal self-attention as one Pallas kernel with its own backward.

    q [B, S, H, D], already scaled by 1/sqrt(D); k, v [B, S, K, D] with
    K | H.  Each (batch, KV head) runs splash attention's MQA form over its
    group of H/K query heads, so K/V are never broadcast.  Score and
    probability tiles live in VMEM only; tiles above the diagonal are
    skipped; the backward recomputes them from q, k, v and the saved f32
    row log-sum-exp.  Matmul operands are q's dtype, softmax statistics and
    accumulators f32.  ``S`` must be a multiple of
    :func:`fused_attention_blocks`' tiles.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    kernel = splash.make_splash_mqa_single_device(
        splash.MultiHeadMask([splash.CausalMask((s, s))] * g),
        block_sizes=fused_attention_blocks(s), interpret=interpret)
    qt = q.reshape(b, s, kh, g, d).transpose(0, 2, 3, 1, 4)     # [B,K,G,S,D]
    kt, vt = (x.transpose(0, 2, 1, 3) for x in (k, v))          # [B,K,S,D]
    out = jax.vmap(jax.vmap(kernel))(qt, kt, vt)                # [B,K,G,S,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, v.shape[-1])


def _fused_route(batch: int, seq_len: int):
    """:func:`fused_causal_attention` as this call can run it on a TPU, or
    None.  The kernel is one device's program: where the installed mesh
    leaves only the batch axes to GSPMD, it runs under ``shard_map`` over
    them; where it leaves any other axis of more than one device (tensor
    parallelism), GSPMD would replicate the custom call, so None."""
    from repro.parallel import context as pctx

    if fused_attention_blocks(seq_len) is None:
        return None
    mesh = pctx.get_mesh()
    manual = pctx._manual_axes()
    auto = () if mesh is None else tuple(
        a for a in mesh.axis_names if mesh.shape[a] > 1 and a not in manual)
    if not auto:
        return fused_causal_attention
    if not set(auto) <= {"pod", "data"} or batch % math.prod(
            mesh.shape[a] for a in auto):
        return None
    spec = P(auto)
    return jax.shard_map(fused_causal_attention, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=set(auto), check_vma=False)


# ---------------------------------------------------------------------------
# standard GQA attention layer (with optional cache)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd), dtype),
        "wk": _dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wv": _dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wo": _dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _proj(x, w, b=None):
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def attention_apply(
    p: dict,
    x: jax.Array,                # [B, S, d]
    cfg: ModelConfig,
    positions: jax.Array,        # [B, S]
    *,
    causal: bool = True,
    window: int | None = None,
    kv_override: tuple | None = None,   # cross-attention: (k, v) precomputed
    cache: dict | None = None,          # {"k","v"} [B, S_max, K, hd]
    cache_index: jax.Array | int | None = None,
) -> tuple[jax.Array, dict | None]:
    from repro.parallel import context as pctx

    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = _proj(x, p["wq"], p.get("bq")).reshape(b, s, cfg.n_heads, hd)
    # settle attention layouts ONCE per layer: q sharded over heads ('model'),
    # kv replicated over 'model' when kv-heads don't divide it — otherwise
    # GSPMD re-shards per kv block inside the scan (measured 6.4 GB/layer of
    # all-reduce on the 67B prefill cell; §Perf iteration 11)
    q = pctx.constrain(q, pctx.BATCH, None, pctx.MODEL, None)
    rope = cfg.pos_embed == "rope" and kv_override is None
    if kv_override is None:
        k = _proj(x, p["wk"], p.get("bk")).reshape(b, s, cfg.n_kv_heads, hd)
        v = _proj(x, p["wv"], p.get("bv")).reshape(b, s, cfg.n_kv_heads, hd)
        if rope:
            k = apply_rope(k, positions, cfg.rope_theta)
        kv_spec = pctx.MODEL if cfg.n_kv_heads % pctx.model_axis_size() == 0 else None
        k = pctx.constrain(k, pctx.BATCH, None, kv_spec, None)
        v = pctx.constrain(v, pctx.BATCH, None, kv_spec, None)
    else:
        k, v = kv_override

    def queries(scale: float = 1.0):
        if rope:
            return apply_rope(q, positions, cfg.rope_theta, scale)
        return q if scale == 1.0 else (q.astype(jnp.float32) * scale).astype(q.dtype)

    new_cache = None
    if cache is not None and kv_override is None:
        kc = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                             cache_index, axis=1)
        vc = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                             cache_index, axis=1)
        new_cache = {"k": kc, "v": vc}
        if s == 1:
            tracing.take_path("attention", "decode")
            out = decode_attention(queries(), kc, vc, cache_index + 1, window=window)
        else:
            tracing.take_path("attention", "blocked")
            out = blocked_attention(queries(), kc[:, : cache_index + s],
                                    vc[:, : cache_index + s], causal=causal,
                                    q_offset=cache_index, window=window)
    elif (kv_override is None and causal and window is None
          and (fused := _fused_route(b, s)) is not None):
        # the kernel takes no scale: fold 1/sqrt(hd) into q's f32 rope
        tracing.take_path("attention", "kernel")
        out = lax.platform_dependent(
            tpu=lambda: fused(queries(1.0 / math.sqrt(hd)), k, v),
            default=lambda: blocked_attention(queries(), k, v, causal=True))
    else:
        tracing.take_path("attention", "blocked")
        out = blocked_attention(queries(), k, v, causal=causal, window=window)
    y = out.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(x.dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    m: MLAConfig = cfg.mla
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    if m.q_lora_rank is None:
        p = {"wq": _dense_init(ks[0], (cfg.d_model, h * qk), dtype)}
    else:
        p = {"wq_a": _dense_init(ks[0], (cfg.d_model, m.q_lora_rank), dtype),
             "q_norm": jnp.ones((m.q_lora_rank,), dtype),
             "wq_b": _dense_init(ks[1], (m.q_lora_rank, h * qk), dtype)}
    return {
        **p,
        "wkv_a": _dense_init(ks[2], (cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": _dense_init(ks[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)), dtype),
        "wo": _dense_init(ks[4], (h * m.v_head_dim, cfg.d_model), dtype),
    }


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head size), times ``mscale(factor, mscale_all_dim) ** 2``
    under YaRN, as DeepSeek-V2 scales its scores."""
    m: MLAConfig = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_compress(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Produce the compressed KV the cache stores: c_kv [B,S,r], k_rope [B,S,1,dr]."""
    m: MLAConfig = cfg.mla
    kv_a = _proj(x, p["wkv_a"])
    c_kv, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    c_kv = _rms(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        scaling=cfg.rope_scaling)
    return c_kv, k_rope


def mla_expand_kv(p: dict, c_kv: jax.Array, cfg: ModelConfig):
    """Decompress cached latents into per-head K_nope and V."""
    m: MLAConfig = cfg.mla
    h = cfg.n_heads
    kv = _proj(c_kv, p["wkv_b"]).reshape(*c_kv.shape[:-1], h, m.qk_nope_head_dim + m.v_head_dim)
    return kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]


def mla_queries(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                scale: float = 1.0):
    """(q_nope, q_rope), each times ``scale`` in f32 before its one cast;
    q_rope rotated.  Without query compression q is one projection."""
    m: MLAConfig = cfg.mla
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = _proj(x, p["wq"])
    else:
        q = _proj(_rms(_proj(x, p["wq_a"]), p["q_norm"], cfg.norm_eps), p["wq_b"])
    q = q.reshape(*x.shape[:-1], h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    if scale != 1.0:
        q_nope = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, scale, cfg.rope_scaling)
    return q_nope, q_rope


def mla_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    cache: dict | None = None,         # {"c_kv": [B,Smax,r], "k_rope": [B,Smax,1,dr]}
    cache_index: jax.Array | int | None = None,
) -> tuple[jax.Array, dict | None]:
    """Training runs the per-head K/V through the fused kernel on a TPU,
    as :func:`attention_apply` does (one KV head per query head, q/k of
    nope + rope width, v of its own); prefill through a cache runs
    :func:`blocked_attention`, decode the absorbed latent path."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    scale = mla_softmax_scale(cfg)
    c_kv, k_rope = mla_compress(p, x, cfg, positions)

    def full_kv(c_kv, k_rope):
        k_nope, v = mla_expand_kv(p, c_kv, cfg)     # [B,Skv,H,dn], [B,Skv,H,dv]
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], m.qk_rope_head_dim))],
            axis=-1,
        )
        return k_full, v

    def q_full(scale=1.0):
        return jnp.concatenate(mla_queries(p, x, cfg, positions, scale), axis=-1)

    if cache is None and (fused := _fused_route(b, s)) is not None:
        # the kernel takes no scale: fold the softmax scale into q's f32 math
        tracing.take_path("attention", "kernel")
        k_full, v = full_kv(c_kv, k_rope)
        out = lax.platform_dependent(
            tpu=lambda: fused(q_full(scale), k_full, v),
            default=lambda: blocked_attention(q_full(), k_full, v, causal=True,
                                              scale=scale))
        y = out.reshape(b, s, cfg.n_heads * m.v_head_dim) @ p["wo"].astype(x.dtype)
        return y, None

    q_nope, q_rope = mla_queries(p, x, cfg, positions)
    new_cache = None
    if cache is not None:
        ckv_c = lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_index, axis=1)
        krope_c = lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_index, axis=1)
        new_cache = {"c_kv": ckv_c, "k_rope": krope_c}
        if s == 1:
            # decode: traced position -> keep the full cache, mask by length
            c_kv_all, k_rope_all = ckv_c, krope_c
        else:
            upto = cache_index + s  # prefill: static start (0)
            c_kv_all, k_rope_all = ckv_c[:, :upto], krope_c[:, :upto]
    else:
        c_kv_all, k_rope_all = c_kv, k_rope

    tracing.take_path("attention", "decode" if s == 1 and cache is not None
                      else "blocked")
    if s == 1 and cache is not None:
        # ---- absorbed decode (MLA's raison d'etre): score & combine in the
        # r-dim latent space; per-head K/V are never materialized over the
        # cache.  w_kv_b is folded into the query / output projections.
        h = cfg.n_heads
        w_b = p["wkv_b"].astype(x.dtype).reshape(
            m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
        w_k, w_v = w_b[..., : m.qk_nope_head_dim], w_b[..., m.qk_nope_head_dim:]
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k)      # [B,H,r]
        s_lat = jnp.einsum("bhr,bsr->bhs", q_lat.astype(c_kv_all.dtype),
                           c_kv_all, preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bhd,bsd->bhs",
                            q_rope[:, 0].astype(k_rope_all.dtype),
                            k_rope_all[:, :, 0],
                            preferred_element_type=jnp.float32)
        scores = (s_lat + s_rope) * scale                          # [B,H,Smax]
        length = cache_index + 1
        valid = jnp.arange(scores.shape[-1])[None, None, :] < length
        scores = jnp.where(valid, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_kv_all.dtype)
        lat = jnp.einsum("bhs,bsr->bhr", probs, c_kv_all,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        out = jnp.einsum("bhr,rhd->bhd", lat, w_v)[:, None]        # [B,1,H,dv]
    else:
        k_full, v = full_kv(c_kv_all, k_rope_all)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = blocked_attention(q, k_full, v, causal=True, scale=scale,
                                q_offset=0 if cache_index is None else cache_index)
    y = out.reshape(b, s, cfg.n_heads * m.v_head_dim) @ p["wo"].astype(x.dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype, d_ff: int | None = None) -> dict:
    ff = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":  # plain 2-matrix MLP (whisper)
        return {
            "w_up": _dense_init(ks[0], (cfg.d_model, ff), dtype),
            "b_up": jnp.zeros((ff,), dtype),
            "w_down": _dense_init(ks[1], (ff, cfg.d_model), dtype),
            "b_down": jnp.zeros((cfg.d_model,), dtype),
        }
    return {  # gated (swiglu / geglu)
        "w_gate": _dense_init(ks[0], (cfg.d_model, ff), dtype),
        "w_up": _dense_init(ks[1], (cfg.d_model, ff), dtype),
        "w_down": _dense_init(ks[2], (ff, cfg.d_model), dtype),
    }


def mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "gelu":
        h = jax.nn.gelu(_proj(x, p["w_up"], p["b_up"]))
        return _proj(h, p["w_down"], p["b_down"])
    gate = _proj(x, p["w_gate"])
    gate = jax.nn.gelu(gate) if cfg.act == "geglu" else jax.nn.silu(gate)
    return _proj(gate * _proj(x, p["w_up"]), p["w_down"])


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dropless-with-capacity dispatch; EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    mo: MoEConfig = cfg.moe
    held = mo.held[1]
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (cfg.d_model, mo.n_experts), dtype),
        # the held experts' stacked weights: [E, d, ff] / [E, ff, d] — EP
        # shards dim 0
        "w_gate": _dense_init(ks[1], (held, cfg.d_model, mo.d_expert), dtype),
        "w_up": _dense_init(ks[2], (held, cfg.d_model, mo.d_expert), dtype),
        "w_down": _dense_init(ks[3], (held, mo.d_expert, cfg.d_model), dtype),
    }
    if mo.n_shared:
        p["shared"] = init_mlp(ks[4], cfg, dtype, d_ff=mo.d_expert * mo.n_shared)
    return p


def _moe_groups(t: int) -> int:
    """Dispatch-group count: one group per DP shard (GShard-style), so every
    sort/gather/scatter keeps a leading sharded batch dim and stays local."""
    from repro.parallel import context as pctx

    mesh = pctx.get_mesh()
    if mesh is None:
        return 1
    g = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            g *= mesh.shape[ax]
    return g if g > 0 and t % g == 0 else 1


class SlotMaps(NamedTuple):
    """One dispatch's int maps between the experts' S slots and N rows of m
    entries each (``moe_apply``: a token and its k routed entries).  Each
    valid slot holds one valid entry and each valid entry sits in one
    valid slot."""

    slot_row: jax.Array      # [G,S] int32: the row each slot reads
    slot_valid: jax.Array    # [G,S] bool: the slot holds an entry
    row_slot: jax.Array      # [G,N,m] int32: the slot of each of a row's entries
    row_valid: jax.Array     # [G,N,m] bool: the entry sits in a slot


def _take_rows(src: jax.Array, idx: jax.Array) -> jax.Array:
    """[G,N,d] rows at [G,M] in-bounds indices -> [G,M,d]."""
    return jnp.take_along_axis(src, idx[..., None], axis=1, mode="promise_in_bounds")


def _gather_slots(x: jax.Array, maps: SlotMaps) -> jax.Array:
    return jnp.where(maps.slot_valid[..., None], _take_rows(x, maps.slot_row), 0)


def _sum_to_tokens(y: jax.Array, maps: SlotMaps) -> jax.Array:
    g, n, m = maps.row_slot.shape
    ys = _take_rows(y, maps.row_slot.reshape(g, n * m))
    ys = jnp.where(maps.row_valid.reshape(g, n * m, 1), ys, 0)
    return ys.reshape(g, n, m, -1).sum(axis=2)


@jax.custom_vjp
def gather_slots(x: jax.Array, maps: SlotMaps) -> jax.Array:
    """[G,N,d] rows -> [G,S,d] slots: each valid slot reads its row, an
    empty slot is zero.  The dispatch; its backward is ``sum_to_tokens``."""
    return _gather_slots(x, maps)


@jax.custom_vjp
def sum_to_tokens(y: jax.Array, maps: SlotMaps) -> jax.Array:
    """[G,S,d] slots -> [G,N,d] rows: each row the sum of its valid
    entries' slots, gathered and summed over m: the exact transpose of
    ``gather_slots``.  The combine; its backward is ``gather_slots``, so
    autodiff writes no scatter-add of d-wide rows."""
    return _sum_to_tokens(y, maps)


def _gather_slots_bwd(maps: SlotMaps, ct: jax.Array):
    """The combine of the slots' cotangent, replicated over 'model' first
    as the forward's combine reads them (``moe_apply``): on an EP mesh a
    gather from slots sharded over 'model' would all-reduce all N*m rows."""
    from repro.parallel import context as pctx

    ct = pctx.constrain(ct, pctx.BATCH, None, None)
    return _sum_to_tokens(ct, maps), None


gather_slots.defvjp(lambda x, maps: (_gather_slots(x, maps), maps), _gather_slots_bwd)
sum_to_tokens.defvjp(lambda y, maps: (_sum_to_tokens(y, maps), maps),
                     lambda maps, ct: (_gather_slots(ct, maps), None))


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Returns (output, aux): ``aux["aux"]`` the load-balancing loss, and
    where this chip holds a share of the experts, ``moe_routed``,
    ``moe_held`` and ``moe_dropped``: the layer's routed entries (tokens
    times top-k), those routed to a held expert, and those of them dropped
    by capacity.  Grouped sort-based dispatch (GShard-style):

      tokens reshaped to [G, T_g] with G sharded over the DP axes -> per-group
      top-k -> per-group sort by expert -> position-in-expert -> int maps
      between slots and entries -> tokens gathered into [G, E, C_g, d] slots
      (per-group capacity, overflow dropped; ``gather_slots``) -> expert FFN
      einsum contracted over d with E sharded over 'model' (EP) -> slots
      times routing weights summed back into their tokens
      (``sum_to_tokens``, the dispatch's transpose).

    Every gather/scatter carries the G batch dim, so GSPMD keeps dispatch
    local per data shard; the [G, E, C, *] buffers are 2-D sharded
    (data × model).  A globally-sorted variant was measured 20+ GiB/device
    worse (see EXPERIMENTS.md §Perf, hypothesis log).

    With a held share (``MoEConfig.held``) the router, top-k, capacity and
    aux loss stay over all ``n_experts``; only the held experts get slots,
    and an entry routed to an expert held elsewhere adds nothing here.

    The expert FFNs are named scope ``ffn``; routing, dispatch, combine and
    the aux loss are ``moe_dispatch``.
    """
    from repro.parallel import context as pctx

    mo: MoEConfig = cfg.moe
    b, s, d = x.shape
    t = b * s
    g = _moe_groups(t)
    tg = t // g
    n_all, k = mo.n_experts, mo.top_k
    h0, e = mo.held
    share = e < n_all
    with jax.named_scope("moe_dispatch"):
        xt = pctx.constrain(x.reshape(g, tg, d), pctx.BATCH, None, None)

        logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)   # [G,Tg,E]
        probs = jax.nn.softmax(logits, axis=-1)
        weights, eids = lax.top_k(probs, k)                               # [G,Tg,k]
        if mo.norm_topk_prob:
            weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

        # aux load-balancing loss (Switch-style, computed over all tokens)
        gi = jnp.arange(g)[:, None]
        density = jnp.zeros((g, n_all), jnp.float32).at[
            jnp.broadcast_to(gi[..., None], eids.shape), eids].add(1.0)
        density = density.sum(0) / (t * k)
        router_prob = probs.mean((0, 1))
        aux = n_all * jnp.sum(density * router_prob) * mo.router_aux_weight

        cap = int(mo.capacity_factor * k * tg / n_all) + 1                # C per (group, expert)
        tgk = tg * k

        # ---- dispatch and combine as one transpose pair of slot gathers.  The
        # obvious scatter formulation (slot_buf.at[g, e, c].set(tokens)) makes
        # GSPMD's scatter partitioner replicate both operands with full-size
        # all-reduces (+95 GiB/device on the 236B cell, see the §Perf
        # hypothesis log); with the sort, every expert's entries are a
        # contiguous range, so slots can be *gathered*.  The sort is composed
        # into int maps (slot -> entry, entry -> slot) and no [G,Tg*k,d]
        # array is permuted: each gather's backward is the other's forward.
        flat_e = eids.reshape(g, tgk)                                     # [G,Tg*k]
        if share:
            # held experts renumbered 0..e-1; the rest take key e, sort
            # last, and fall outside the counts (an out-of-range scatter
            # index is dropped)
            flat_e = jnp.where((flat_e >= h0) & (flat_e < h0 + e), flat_e - h0, e)
        order = jnp.argsort(flat_e, axis=-1)
        inv_order = jnp.argsort(order, axis=-1)                           # entry -> sorted pos
        counts = jnp.zeros((g, e), jnp.int32).at[
            jnp.broadcast_to(gi, flat_e.shape), flat_e].add(1, mode="drop")  # tiny scatter
        seg_start = jnp.cumsum(counts, axis=-1) - counts                  # [G,E]

        # slot (e, c) holds sorted position seg_start[e] + c while c <
        # counts[e]; an entry sits at c = its sorted position minus its
        # expert's seg_start, and is kept while c < C and its expert is held
        slot_valid = (jnp.arange(cap)[None, None] < counts[..., None]).reshape(g, e * cap)
        slot_src = jnp.clip(seg_start[..., None] + jnp.arange(cap)[None, None],
                            0, tgk - 1).reshape(g, e * cap)
        slot_ent = jnp.take_along_axis(order, slot_src, axis=1)           # [G,E*C]
        ent_e = jnp.minimum(flat_e, e - 1)
        pos_in_e = inv_order - jnp.take_along_axis(seg_start, ent_e, axis=1)
        kept = pos_in_e < cap
        if share:
            held = flat_e < e
            kept &= held
        ent_slot = jnp.where(kept, ent_e * cap + pos_in_e, 0)             # [G,Tg*k]
        to_tokens = SlotMaps(slot_ent // k, slot_valid, ent_slot.reshape(g, tg, k),
                             kept.reshape(g, tg, k))
        slot_buf = gather_slots(xt, to_tokens).reshape(g, e, cap, d)
        slot_buf = pctx.constrain(slot_buf, pctx.BATCH, pctx.MODEL, None, None)

    with jax.named_scope("ffn"):
        # expert FFN: [G,E,C,d] x [E,d,f] -> [G,E,C,f]; d contracted, E sharded
        h_g = jnp.einsum("gecd,edf->gecf", slot_buf, p["w_gate"].astype(x.dtype))
        h_u = jnp.einsum("gecd,edf->gecf", slot_buf, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(h_g) * h_u
        y_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    with jax.named_scope("moe_dispatch"):
        # replicate the (small) expert outputs over 'model' for the local
        # combine — this reshard is the EP "return" all-to-all
        y_e = pctx.constrain(y_e, pctx.BATCH, None, None, None)

        # combine: each slot's output times its entry's routing weight, summed
        # into its token; dropped entries and empty slots add nothing
        w_slot = jnp.where(slot_valid, jnp.take_along_axis(
            weights.reshape(g, tgk), slot_ent, axis=1), 0)[..., None]     # [G,E*C,1]
        out = sum_to_tokens(y_e.reshape(g, e * cap, d) * w_slot.astype(x.dtype),
                            to_tokens)                                    # [G,Tg,d]
        out = pctx.constrain(out, pctx.BATCH, None, None)
        aux = {"aux": aux}
        if share:
            aux.update(moe_routed=jnp.asarray(t * k, jnp.float32),
                       moe_held=held.sum().astype(jnp.float32),
                       moe_dropped=(held & ~kept).sum().astype(jnp.float32))

    if mo.n_shared:
        with jax.named_scope("ffn"):
            out = out + mlp_apply(p["shared"], xt, cfg)
    return out.reshape(b, s, d), aux


def moe_aux_zero(cfg: ModelConfig) -> dict:
    """Zeros in the structure of ``moe_apply``'s aux for this configuration:
    a dense layer's aux, and the sum over no layers."""
    z = jnp.zeros((), jnp.float32)
    if cfg.moe is None or cfg.moe.held[1] == cfg.moe.n_experts:
        return {"aux": z}
    return {"aux": z, "moe_routed": z, "moe_held": z, "moe_dropped": z}


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"tok": _dense_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(ks[1], (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.pos_embed == "learned":
        p["pos"] = _dense_init(ks[2], (cfg.learned_pos_max, cfg.d_model), dtype)
    return p


def embed_apply(p: dict, tokens: jax.Array, cfg: ModelConfig, dtype,
                positions: jax.Array | None = None) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(dtype)
    if cfg.name.startswith("gemma"):
        x = x * math.sqrt(cfg.d_model)
    if cfg.pos_embed == "learned" and positions is not None:
        x = x + jnp.take(p["pos"], positions, axis=0).astype(dtype)
    return x


def unembed_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ p["tok"].T.astype(x.dtype)
    else:
        logits = x @ p["unembed"].astype(x.dtype)
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding rows out of softmax
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e9, logits.dtype), logits)
    return logits


def masked_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross entropy over labels >= 0.

    Uses the one-hot/logsumexp formulation rather than take_along_axis: the
    vocab dim stays 'model'-sharded end to end (a vocab gather makes GSPMD
    replicate the [B,S,V] logits — measured at +45 GiB/device on the 236B
    train cell; see EXPERIMENTS.md §Perf hypothesis log)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    oh = jax.nn.one_hot(safe, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.sum(oh * logits, axis=-1)
    nll = lse - ll
    mask = (labels >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
