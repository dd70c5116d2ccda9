"""Analytic FLOP accounting: MODEL_FLOPS and inner-scan corrections.

MODEL_FLOPS ("useful" flops, the roofline numerator):
    train   6 · N_active · tokens  + attention term (causal half)
    decode  2 · N_active · B       + KV-attention term (fwd only)
N_active counts matmul-participating params per token: embedding lookups
excluded, tied unembed *matmul* included, MoE routed experts scaled by
top_k / n_experts (6·N_active·D per the assignment).

Inner-scan corrections: XLA cost analysis counts while bodies once, so the
sequence-block loops (attention q/kv blocks, SSD chunks, xLSTM scans) are
undercounted even after depth extrapolation.  Each family's correction adds
(trip_count - 1) × per-iteration flops of those loops, with per-iteration
flops from the closed forms below (dominant matmul terms).
"""

from __future__ import annotations

import math
from dataclasses import replace

import jax

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import api as mapi

# blocked_attention's default blocks (keep in sync with layers.py): the jnp
# path, which every compile off the TPU takes; on a TPU, causal training
# attention runs layers.fused_causal_attention, a custom call that carries
# no cost estimate
QB, KVB = 512, 1024


# ---------------------------------------------------------------------------
# parameter census
# ---------------------------------------------------------------------------

def _param_census(cfg: ModelConfig) -> dict:
    """Split parameter counts into embedding-lookup / routed-expert / rest."""
    specs = mapi.param_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    embed = routed = rest = 0
    for path, leaf in flat:
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        n = math.prod(leaf.shape)
        if "embed/tok" in p or "embed/pos" in p or "enc_pos" in p:
            embed += n
        elif "moe/w_" in p:
            routed += n
        else:
            rest += n
    return {"embed": embed, "routed": routed, "rest": rest}


def n_active(cfg: ModelConfig) -> float:
    c = _param_census(cfg)
    act = c["rest"]
    if cfg.moe is not None:
        act += c["routed"] * cfg.moe.top_k / cfg.moe.n_experts
    if cfg.tie_embeddings:
        act += cfg.vocab_size * cfg.d_model   # tied table used as unembed matmul
    return float(act)


# ---------------------------------------------------------------------------
# attention terms
# ---------------------------------------------------------------------------

def _attn_dims(cfg: ModelConfig) -> tuple[int, int]:
    """(qk flops dim, pv flops dim) per head-pair contraction."""
    if cfg.mla is not None:
        return (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim,
                cfg.mla.v_head_dim)
    hd = cfg.resolved_head_dim
    return hd, hd


def _n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every     # one shared block per group
    if cfg.family == "xlstm":
        return 0
    return cfg.n_layers


def attention_model_flops(cfg: ModelConfig, b: int, s: int, causal_half: bool,
                          fwd_mult: float) -> float:
    """Useful attention flops (global, fwd_mult=3 for train fwd+bwd)."""
    dqk, dv = _attn_dims(cfg)
    h = cfg.n_heads
    eff = 0.5 * s * s if causal_half else float(s) * s
    win = cfg.sliding_window
    if win is not None and s > win:
        eff = min(eff, float(s) * win)
    per_layer = 2 * b * h * eff * (dqk + dv)
    total = _n_attn_layers(cfg) * per_layer
    if cfg.family == "encdec":
        # encoder self-attention (bidirectional) + decoder cross-attention
        es = cfg.encoder_seq
        total += cfg.encoder_layers * 2 * b * h * es * es * (dqk + dv)
        total += cfg.n_layers * 2 * b * h * s * es * (dqk + dv)
    return total * fwd_mult


# ---------------------------------------------------------------------------
# MODEL_FLOPS
# ---------------------------------------------------------------------------

def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Global useful flops for one step of the cell's kind."""
    b, s = shape.global_batch, shape.seq_len
    na = n_active(cfg)
    if shape.kind == "train":
        tokens = b * s
        if cfg.family == "encdec":
            tokens = b * s  # decoder tokens; encoder in attention term + rest
        return 6.0 * na * tokens + attention_model_flops(cfg, b, s, True, 3.0)
    if shape.kind == "prefill":
        tokens = b * s
        return 2.0 * na * tokens + attention_model_flops(cfg, b, s, True, 1.0)
    # decode: one token against an s-length KV cache
    dqk, dv = _attn_dims(cfg)
    ctx = s if cfg.sliding_window is None else min(s, cfg.sliding_window)
    attn = _n_attn_layers(cfg) * 2 * b * cfg.n_heads * ctx * (dqk + dv)
    if cfg.family == "encdec":
        attn += cfg.n_layers * 2 * b * cfg.n_heads * cfg.encoder_seq * (dqk + dv)
    return 2.0 * na * b + attn


# ---------------------------------------------------------------------------
# inner-scan corrections (executed-flops deltas vs once-counted loop bodies)
# ---------------------------------------------------------------------------

def _blocked_attn_correction(cfg: ModelConfig, b: int, sq: int, skv: int,
                             n_layers: int, mult: float) -> float:
    """blocked_attention runs nq*nkv block pairs; cost analysis sees one."""
    if sq <= 1:
        return 0.0
    dqk, dv = _attn_dims(cfg)
    qb, kvb = min(QB, sq), min(KVB, skv)
    sq_p = math.ceil(sq / qb) * qb
    skv_p = math.ceil(skv / kvb) * kvb
    per_layer = 2 * b * cfg.n_heads * (dqk + dv) * (sq_p * skv_p - qb * kvb)
    return n_layers * per_layer * mult


def inner_scan_correction(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Flops delta to ADD to depth-extrapolated HLO flops (global)."""
    b, s = shape.global_batch, shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0
    if shape.kind == "decode":
        return 0.0  # decode paths are scan-free per step
    total = 0.0
    fam = cfg.family
    if fam in ("decoder", "moe", "vlm"):
        sq = s + (cfg.frontend_seq if cfg.frontend == "patch_embed" else 0)
        total += _blocked_attn_correction(cfg, b, sq, sq, cfg.n_layers, mult)
    elif fam == "encdec":
        es = cfg.encoder_seq
        total += _blocked_attn_correction(cfg, b, es, es, cfg.encoder_layers, mult)
        total += _blocked_attn_correction(cfg, b, s, s, cfg.n_layers, mult)
        total += _blocked_attn_correction(cfg, b, s, es, cfg.n_layers, mult)
    elif fam == "hybrid":
        # shared attention blocks
        na = cfg.n_layers // cfg.attn_every
        total += _blocked_attn_correction(cfg, b, s, s, na, mult)
        # SSD chunk scan: (nc - 1) x per-chunk flops, per mamba layer
        sc = cfg.ssm
        d_inner = sc.expand * cfg.d_model
        nheads = d_inner // sc.head_dim
        q = min(sc.chunk, s)
        nc = math.ceil(s / q)
        n_st, p_hd = sc.state_dim, sc.head_dim
        per_chunk = (2 * b * q * q * n_st          # C·Bᵀ
                     + 2 * b * q * q * nheads * p_hd  # (CBᵀ∘L)·X
                     + 4 * b * q * n_st * nheads * p_hd)  # state out + carry in
        total += cfg.n_layers * (nc - 1) * per_chunk * mult
    elif fam == "xlstm":
        x = cfg.xlstm
        d = cfg.d_model
        d_i = int(x.proj_factor * d)
        pairs = cfg.n_layers // 2
        # mLSTM chunk scan
        from repro.models.xlstm import CHUNK
        q = min(CHUNK, s)
        nc = math.ceil(s / q)
        dh = d_i // x.n_heads
        per_chunk = (4 * b * q * q * d_i           # qk dot + weighted v
                     + 8 * b * q * d_i * dh)       # carry read + state update
        total += pairs * (nc - 1) * per_chunk * mult
        # sLSTM per-token scan
        dhs = d // x.n_heads
        per_step = (4 * b * d * d                  # wz/wo projections
                    + 4 * b * d * x.n_heads        # wi/wf
                    + 4 * b * x.n_heads * dhs * dhs)  # rz/ro recurrences
        total += pairs * (s - 1) * per_step * mult
    return total


# ---------------------------------------------------------------------------
# depth variants for 2-point extrapolation
# ---------------------------------------------------------------------------

def depth_unit(cfg: ModelConfig) -> int:
    """Layers added per unit of scan depth."""
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.family == "xlstm":
        return 2
    return 1


def scan_depth(cfg: ModelConfig) -> int:
    """Trip count of the (outermost) layer scan at full depth."""
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    if cfg.family == "xlstm":
        return cfg.n_layers // 2
    return cfg.n_layers - cfg.first_k_dense


def with_depth(cfg: ModelConfig, scan_trips: int) -> ModelConfig:
    """Config with the layer-scan trip count set to ``scan_trips``."""
    u = depth_unit(cfg)
    n = scan_trips * u + cfg.first_k_dense
    kw = {"n_layers": n}
    if cfg.family == "encdec":
        kw["encoder_layers"] = scan_trips
    return replace(cfg, **kw)


def extrapolate(f1: float, f2: float, d1: int, d2: int, full: int) -> float:
    """Linear 2-point extrapolation of a depth-linear cost."""
    slope = (f2 - f1) / (d2 - d1)
    return f1 + (full - d1) * slope
