"""Decoder-only transformer family.

Covers: deepseek-67b, qwen2-1.5b, qwen1.5-4b, gemma-7b (dense decoders),
internvl2-1b (decoder + patch-embedding stub prepended), granite-moe,
deepseek-v2-236b and deepseek-v2-lite (MoE decoders, the DeepSeek ones with
MLA attention, shared experts and first-k-dense layers).

Layers are scan-stacked: every layer's params live in one pytree whose
leaves carry a leading [L] axis, and the forward pass is a single
``lax.scan`` — keeps the HLO size O(1) in depth (95-layer deepseek-67b
compiles as fast as 2 layers) and is the shape MaxText-class frameworks use.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.parallel import context as pctx
from repro.runtime import tracing
from . import layers as L


def _use_mla(cfg: ModelConfig) -> bool:
    return cfg.mla is not None


def _use_moe(cfg: ModelConfig, layer_is_dense: bool) -> bool:
    return cfg.moe is not None and not layer_is_dense


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, dtype, dense: bool = False) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": L.init_norm(cfg, dtype), "ln2": L.init_norm(cfg, dtype)}
    if _use_mla(cfg):
        p["attn"] = L.init_mla(k1, cfg, dtype)
    else:
        p["attn"] = L.init_attention(k1, cfg, dtype)
    if _use_moe(cfg, dense):
        p["moe"] = L.init_moe(k2, cfg, dtype)
    else:
        d_ff = cfg.dense_d_ff if (dense and cfg.dense_d_ff) else (cfg.d_ff or cfg.dense_d_ff)
        p["mlp"] = L.init_mlp(k2, cfg, dtype, d_ff=d_ff)
    return p


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ke, kl, kd, kv = jax.random.split(key, 4)
    n_scan = cfg.n_layers - cfg.first_k_dense
    layer_keys = jax.random.split(kl, n_scan)
    stacked = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    p = {
        "embed": L.init_embed(ke, cfg, dtype),
        "layers": stacked,
        "final_norm": L.init_norm(cfg, dtype),
    }
    if cfg.first_k_dense:
        dks = jax.random.split(kd, cfg.first_k_dense)
        p["dense_layers"] = [init_layer(k, cfg, dtype, dense=True) for k in dks]
    if cfg.frontend == "patch_embed":
        # projection from the (stubbed) vision tower's hidden to d_model
        p["patch_proj"] = L._dense_init(kv, (cfg.d_model, cfg.d_model), dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_fwd(p, x, cfg, positions, *, cache=None, cache_index=None,
               window=None, dense=False):
    h = L.norm_apply(p["ln1"], x, cfg)
    with jax.named_scope("attention"):
        if _use_mla(cfg):
            a, new_cache = L.mla_apply(p["attn"], h, cfg, positions,
                                       cache=cache, cache_index=cache_index)
        else:
            a, new_cache = L.attention_apply(p["attn"], h, cfg, positions,
                                             causal=True, window=window,
                                             cache=cache, cache_index=cache_index)
    x = x + a
    h = L.norm_apply(p["ln2"], x, cfg)
    aux = L.moe_aux_zero(cfg)
    if _use_moe(cfg, dense):
        # scopes its expert FFNs as "ffn" and the rest as "moe_dispatch"
        m, aux = L.moe_apply(p["moe"], h, cfg)
    else:
        with jax.named_scope("ffn"):
            m = L.mlp_apply(p["mlp"], h, cfg)
    x = x + m
    x = pctx.constrain_acts(x)
    return x, new_cache, aux


def forward(
    params: dict,
    tokens: jax.Array,            # [B, S]
    cfg: ModelConfig,
    *,
    compute_dtype=jnp.bfloat16,
    patch_embeds: jax.Array | None = None,   # [B, P, d] (vlm stub)
    cache: dict | None = None,    # stacked caches {"k": [L,B,Smax,K,hd], ...}
    cache_index: int | jax.Array | None = None,
    remat: str = "full",
    window: int | None = None,
) -> tuple[jax.Array, dict | None, dict]:
    """Returns (hidden [B,S,d], new_cache | None, aux): ``aux["aux"]`` the
    layers' load-balancing loss, and where the experts are a held share,
    their entry counts summed over the layers (``layers.moe_apply``)."""
    b, s = tokens.shape
    base_pos = 0 if cache_index is None else cache_index
    with jax.named_scope("embed_head"):
        x = L.embed_apply(params["embed"], tokens, cfg, compute_dtype)

    if patch_embeds is not None:
        pe = patch_embeds.astype(compute_dtype) @ params["patch_proj"].astype(compute_dtype)
        x = jnp.concatenate([pe, x], axis=1)
        s = x.shape[1]
    positions = base_pos + jnp.arange(s)[None, :].astype(jnp.int32)
    positions = jnp.broadcast_to(positions, (b, s))
    if cfg.pos_embed == "learned":
        x = x + jnp.take(params["embed"]["pos"],
                         jnp.minimum(positions, cfg.learned_pos_max - 1),
                         axis=0).astype(compute_dtype)
    x = pctx.constrain_acts(x)

    aux_total = L.moe_aux_zero(cfg)

    # unstacked dense-FFN layers first (deepseek-v2 first_k_dense)
    dense_caches = []
    with jax.named_scope("layers"):
        for i, dp in enumerate(params.get("dense_layers", [])):
            dcache = None if cache is None else jax.tree.map(lambda c: c[i], cache["dense"])
            x, ncache, aux = _layer_fwd(dp, x, cfg, positions, cache=dcache,
                                        cache_index=cache_index, window=window,
                                        dense=True)
            dense_caches.append(ncache)
            aux_total = jax.tree.map(jnp.add, aux_total, aux)

    def body(carry, layer_in):
        xc, auxc = carry
        lp, lcache = layer_in
        xo, ncache, aux = _layer_fwd(lp, xc, cfg, positions, cache=lcache,
                                     cache_index=cache_index, window=window)
        return (xo, jax.tree.map(jnp.add, auxc, aux)), ncache

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    scan_cache = None if cache is None else cache["scan"]
    with jax.named_scope("layers"), tracing.repeated(cfg.n_layers - cfg.first_k_dense):
        (x, aux_total), new_scan_cache = lax.scan(
            body, (x, aux_total), (params["layers"], scan_cache))

    new_cache = None
    if cache is not None:
        new_cache = {"scan": new_scan_cache}
        if dense_caches:
            new_cache["dense"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *dense_caches)
    with jax.named_scope("embed_head"):
        x = L.norm_apply(params["final_norm"], x, cfg)
    return x, new_cache, aux_total


def logits_fn(params, hidden, cfg):
    logits = L.unembed_apply(params["embed"], hidden, cfg)
    return pctx.constrain(logits, pctx.BATCH, None, pctx.MODEL)


# ---------------------------------------------------------------------------
# task heads: train loss / prefill / decode
# ---------------------------------------------------------------------------

def loss_fn(params, batch: dict, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
            remat: str = "full") -> tuple[jax.Array, dict]:
    """Causal LM loss.  batch: tokens [B,S], labels [B,S] (-100 = masked),
    optional patch_embeds."""
    hidden, _, aux = forward(params, batch["tokens"], cfg,
                             compute_dtype=compute_dtype,
                             patch_embeds=batch.get("patch_embeds"),
                             remat=remat)
    labels = batch["labels"]
    if batch.get("patch_embeds") is not None:
        hidden = hidden[:, -labels.shape[1]:]  # loss over text positions only
    with jax.named_scope("embed_head"):
        logits = logits_fn(params, hidden, cfg)
        loss = L.masked_xent(logits, labels)
    metrics = {"nll": loss, "aux": aux["aux"]}
    if "moe_held" in aux:
        metrics["moe_held_share"] = aux["moe_held"] / aux["moe_routed"]
        metrics["moe_dropped_share"] = aux["moe_dropped"] / jnp.maximum(aux["moe_held"], 1.0)
    return loss + aux["aux"], metrics


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    hd = cfg.resolved_head_dim
    n_scan = cfg.n_layers - cfg.first_k_dense
    if _use_mla(cfg):
        m = cfg.mla
        one = {
            "c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_seq, 1, m.qk_rope_head_dim), dtype),
        }
    else:
        one = {
            "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
        }
    cache = {"scan": jax.tree.map(lambda z: jnp.broadcast_to(z, (n_scan, *z.shape)), one)}
    if cfg.first_k_dense:
        cache["dense"] = jax.tree.map(
            lambda z: jnp.broadcast_to(z, (cfg.first_k_dense, *z.shape)), one)
    return cache


def prefill(params, tokens, cfg: ModelConfig, cache, *, compute_dtype=jnp.bfloat16,
            patch_embeds=None, window=None):
    """Fill the cache from position 0; returns (last-token logits, cache)."""
    hidden, new_cache, _ = forward(params, tokens, cfg, compute_dtype=compute_dtype,
                                   cache=cache, cache_index=0, remat="none",
                                   patch_embeds=patch_embeds, window=window)
    logits = logits_fn(params, hidden[:, -1:], cfg)
    return logits[:, 0], new_cache


def decode_step(params, token, pos, cfg: ModelConfig, cache, *,
                compute_dtype=jnp.bfloat16, window=None):
    """One decode step.  token [B], pos scalar int32 (same for the batch —
    the serving engine aligns sequences); returns (logits [B,V], cache)."""
    hidden, new_cache, _ = forward(params, token[:, None], cfg,
                                   compute_dtype=compute_dtype,
                                   cache=cache, cache_index=pos, remat="none",
                                   window=window)
    logits = logits_fn(params, hidden, cfg)
    return logits[:, 0], new_cache
