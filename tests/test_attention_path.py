"""Which attention runs where, and the fused kernel against blocked_attention.

On a TPU, causal self-attention with no cache, no window and a sequence that
is a multiple of the kernel's tiles runs as splash attention's fused Pallas
kernel (``layers.fused_causal_attention``); everything else, and everything
off the TPU, runs ``blocked_attention`` or ``decode_attention``. The kernel
runs here in the Pallas interpreter; tests/test_chip_compile.py compiles it
for a described v5e.
"""

import math
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.models import layers as L
from repro.runtime import tracing

RNG = np.random.default_rng(0)

# norm-relative gap of two bf16 computations of the same attention: two
# bf16 units in the last place (2**-8 each)
BF16_GAP = 2.0 ** -7


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * RNG.normal(size=shape), dtype)


def _gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ------------------------------------------------- the kernel, interpreted

def _layers(attend, q, k, v, ct):
    """Two layers of ``attend`` through jax.checkpoint + lax.scan, as
    ``transformer.forward`` runs its layers; each layer's query differs."""
    def body(acc, w):
        out = attend((q.astype(jnp.float32) * w).astype(q.dtype), k, v)
        return acc + out.astype(jnp.float32), None

    acc, _ = lax.scan(jax.checkpoint(body), jnp.zeros(q.shape, jnp.float32),
                      jnp.asarray([1.0, -0.5], jnp.float32))
    return (acc * ct).sum(), acc


def _blocked(q, k, v):
    return L.blocked_attention(q, k, v, causal=True)


def _fused(q, k, v):
    # the layer folds 1/sqrt(D) into q in f32, before q's one cast
    d = q.shape[-1]
    qs = (q.astype(jnp.float32) / math.sqrt(d)).astype(q.dtype)
    return L.fused_causal_attention(qs, k, v, interpret=True)


@pytest.mark.parametrize("head_dim,groups", [(64, 2), (128, 6)])
def test_fused_attention_matches_blocked(head_dim, groups):
    blocks = L.fused_attention_blocks(4096)
    s = 2 * max(blocks.block_q, blocks.block_kv)    # 2-4 blocks of each
    assert L.fused_attention_blocks(s) == blocks
    b, kh = 1, 2
    q = _rand((b, s, kh * groups, head_dim), jnp.bfloat16)
    k = _rand((b, s, kh, head_dim), jnp.bfloat16)
    v = _rand((b, s, kh, head_dim), jnp.bfloat16)
    ct = _rand((b, s, kh * groups, head_dim))
    want = jax.value_and_grad(_layers, argnums=(1, 2, 3), has_aux=True)
    got = jax.value_and_grad(_layers, argnums=(1, 2, 3), has_aux=True)
    (_, out_b), grads_b = jax.jit(want, static_argnums=0)(_blocked, q, k, v, ct)
    (_, out_f), grads_f = jax.jit(got, static_argnums=0)(_fused, q, k, v, ct)
    assert _gap(out_f, out_b) < BF16_GAP
    for name, gf, gb in zip("qkv", grads_f, grads_b):
        assert gf.dtype == gb.dtype == jnp.bfloat16, name
        assert _gap(gf, gb) < BF16_GAP, name


def test_fused_attention_blocks_need_whole_tiles():
    assert L.fused_attention_blocks(96) is None        # under one lane tile
    assert L.fused_attention_blocks(2048 + 128) is None
    assert L.fused_attention_blocks(256) is not None


# ---------------------------------------------------------------- dispatch

def _gqa_case(case):
    """(cfg, the layer's call, the parent's computation of it, path)."""
    cfg = registry.get("qwen2-1.5b", smoke=True)
    p = L.init_attention(jax.random.key(1), cfg, jnp.float32)
    p["bq"], p["bk"], p["bv"] = (_rand(p[n].shape, scale=0.1) for n in ("bq", "bk", "bv"))
    b, s = 2, {"short": 96, "decode": 1}.get(case, 256)
    x = _rand((b, s, cfg.d_model), jnp.bfloat16)
    hd, theta = cfg.resolved_head_dim, cfg.rope_theta
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def qkv(x, pos):
        q = L._proj(x, p["wq"], p["bq"]).reshape(b, s, cfg.n_heads, hd)
        k = L._proj(x, p["wk"], p["bk"]).reshape(b, s, cfg.n_kv_heads, hd)
        v = L._proj(x, p["wv"], p["bv"]).reshape(b, s, cfg.n_kv_heads, hd)
        return L.apply_rope(q, pos, theta), L.apply_rope(k, pos, theta), v

    def wo(out):
        return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)

    kw, path = {}, "blocked"
    if case in ("train", "short"):
        path = "kernel" if case == "train" else "blocked"

        def parent():
            return wo(L.blocked_attention(*qkv(x, pos), causal=True))
    elif case == "window":
        kw = {"window": 64}

        def parent():
            return wo(L.blocked_attention(*qkv(x, pos), causal=True, window=64))
    elif case == "kv_override":
        kv = (_rand((b, 48, cfg.n_kv_heads, hd), jnp.bfloat16),
              _rand((b, 48, cfg.n_kv_heads, hd), jnp.bfloat16))
        kw = {"kv_override": kv, "causal": False}

        def parent():
            q = L._proj(x, p["wq"], p["bq"]).reshape(b, s, cfg.n_heads, hd)
            return wo(L.blocked_attention(q, *kv, causal=False))
    else:   # the serving engine's prefill into a cache, then decode
        smax = 320
        cache = {"k": _rand((b, smax, cfg.n_kv_heads, hd), jnp.bfloat16),
                 "v": _rand((b, smax, cfg.n_kv_heads, hd), jnp.bfloat16)}
        index = 0 if case == "prefill" else 200
        pos = pos + index
        kw = {"cache": cache, "cache_index": index}
        path = "decode" if case == "decode" else "blocked"

        def parent():
            q, k, v = qkv(x, pos)
            kc = lax.dynamic_update_slice_in_dim(cache["k"], k, index, axis=1)
            vc = lax.dynamic_update_slice_in_dim(cache["v"], v, index, axis=1)
            if case == "decode":
                return wo(L.decode_attention(q, kc, vc, index + 1))
            return wo(L.blocked_attention(q, kc[:, :index + s], vc[:, :index + s],
                                          causal=True, q_offset=index))

    def layer():
        return L.attention_apply(p, x, cfg, pos, **kw)[0]

    return layer, parent, path


@pytest.mark.parametrize("case", ["train", "short", "window", "kv_override",
                                  "prefill", "decode"])
def test_attention_dispatch(case):
    """Each call is counted under the path it took; off the TPU every path,
    the kernel's included, computes exactly what blocked_attention (or
    decode_attention) computed before the kernel existed."""
    layer, parent, path = _gqa_case(case)
    with tracing.PathCounter() as paths:
        got = jax.jit(layer)()
    assert dict(paths.counts) == {("attention", path): 1}
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(jax.jit(parent)(), np.float32))


@pytest.mark.parametrize("case", ["train", "decode", "serve"])
def test_mla_dispatch(case):
    """MLA's training attention takes the kernel, and off the TPU computes
    exactly what blocked_attention computed before the kernel existed; its
    decode takes the absorbed latent path; and serving through the latent
    cache, prefill then decode, gives the full forward's logits with
    DeepSeek-V2-Lite's YaRN (whose softmax scale every path must carry)."""
    if case == "serve":
        _mla_serve_matches_forward()
        return
    cfg = registry.get("deepseek-v2-236b", smoke=True)
    p = L.init_mla(jax.random.key(2), cfg, jnp.float32)
    b, s = 2, 1 if case == "decode" else 256
    x = _rand((b, s, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    kw = {}
    if case == "decode":
        m = cfg.mla
        kw = {"cache": {"c_kv": jnp.zeros((b, 64, m.kv_lora_rank), jnp.bfloat16),
                        "k_rope": jnp.zeros((b, 64, 1, m.qk_rope_head_dim), jnp.bfloat16)},
              "cache_index": 5}
    with tracing.PathCounter() as paths:
        got = jax.jit(lambda: L.mla_apply(p, x, cfg, pos, **kw)[0])()
    want = "decode" if case == "decode" else "kernel"
    assert dict(paths.counts) == {("attention", want): 1}
    if case == "train":
        def parent():
            q = jnp.concatenate(L.mla_queries(p, x, cfg, pos), axis=-1)
            c_kv, k_rope = L.mla_compress(p, x, cfg, pos)
            k_nope, v = L.mla_expand_kv(p, c_kv, cfg)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope, (*k_nope.shape[:-1], cfg.mla.qk_rope_head_dim))], axis=-1)
            out = L.blocked_attention(q, k, v, causal=True,
                                      scale=L.mla_softmax_scale(cfg))
            return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)

        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(jax.jit(parent)(), np.float32))


def _mla_serve_matches_forward():
    """Prefill of 5 tokens then 3 decode steps through the latent cache
    against the full forward's logits at those positions, in float32. The
    experts' capacity holds every entry, so the number of tokens in a call
    does not change which entries the experts keep."""
    import dataclasses

    from repro.models import api as mapi, transformer as T

    cfg = registry.get("deepseek-v2-lite", smoke=True)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    assert cfg.rope_scaling is not None and cfg.mla.q_lora_rank is None
    api = mapi.get_api(cfg, compute_dtype=jnp.float32, remat="none")
    params = api.init(jax.random.key(4))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    hidden, _, _ = T.forward(params, toks, cfg, compute_dtype=jnp.float32,
                             remat="none")
    full = np.asarray(T.logits_fn(params, hidden, cfg))
    cache = api.init_cache(2, 16, dtype=jnp.float32)
    with tracing.PathCounter() as paths:
        logits, cache = jax.jit(api.prefill)(params, {"tokens": toks[:, :5]}, cache)
    assert paths.counts[("attention", "blocked")] == cfg.n_layers
    np.testing.assert_allclose(np.asarray(logits), full[:, 4], rtol=2e-4, atol=2e-4)
    decode = jax.jit(api.decode)
    for t in range(5, 8):
        logits, cache = decode(params, toks[:, t], jnp.asarray(t, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(logits), full[:, t], rtol=2e-4, atol=2e-4)


def test_path_counter_weights_the_layer_scan():
    with tracing.PathCounter() as outer, tracing.repeated(3):
        tracing.take_path("attention", "kernel")
        with tracing.repeated(2):
            tracing.take_path("attention", "blocked")
    tracing.take_path("attention", "kernel")        # no counter open
    assert dict(outer.counts) == {("attention", "kernel"): 3,
                                  ("attention", "blocked"): 6}


def test_trainer_reports_blocked_off_the_tpu():
    """The step's layers trace onto the kernel; the CPU's compiled step
    holds none, so the trainer counts every layer as blocked_attention."""
    from repro.train import Trainer, TrainerOptions

    cfg = registry.get("qwen2-1.5b", smoke=True)

    class Source:
        def batch(self, step):
            t = np.random.default_rng(step).integers(
                0, cfg.vocab_size, (2, 256)).astype(np.int32)
            return {"tokens": t, "labels": t}

    with tempfile.TemporaryDirectory() as ckpt:
        tr = Trainer(cfg, TrainConfig(total_steps=2), Source(),
                     options=TrainerOptions(ckpt_dir=ckpt))
        assert tr.attention_paths is None
        tr.run(2)
    assert dict(tr._paths.counts) == {("attention", "kernel"): cfg.n_layers}
    assert tr.attention_paths == {"blocked": cfg.n_layers}
