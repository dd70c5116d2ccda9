"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and the absence of NaNs (assignment contract).
Full configs are exercised only via the dry-run."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models import api as mapi

RNG = np.random.default_rng(0)
B, S = 2, 16


def _batch(cfg):
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            RNG.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.bfloat16)
    if cfg.frontend == "patch_embed":
        batch["patch_embeds"] = jnp.asarray(
            RNG.normal(size=(B, cfg.frontend_seq, cfg.d_model)), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_arch_smoke_forward_and_grad(arch):
    cfg = registry.get(arch, smoke=True)
    api = mapi.get_api(cfg, remat="none")
    params = api.init(jax.random.key(0))
    batch = _batch(cfg)

    loss, metrics = jax.jit(api.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    grads = jax.grad(lambda p: api.loss(p, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "zamba2-2.7b", "xlstm-350m",
                                  "whisper-medium", "deepseek-v2-236b"])
def test_arch_smoke_prefill_decode(arch):
    """One family member per code path: prefill fills the cache, a decode
    step extends it; logits finite and correctly shaped."""
    cfg = registry.get(arch, smoke=True)
    api = mapi.get_api(cfg, remat="none")
    params = api.init(jax.random.key(0))
    batch = _batch(cfg)
    batch.pop("labels")
    cache = api.init_cache(B, 64)

    logits, cache = jax.jit(api.prefill)(params, batch, cache)
    assert logits.shape[0] == B
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    pos = S + (cfg.frontend_seq if cfg.frontend == "patch_embed" else 0)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache = jax.jit(api.decode)(params, tok, jnp.asarray(pos, jnp.int32), cache)
    assert logits2.shape[0] == B
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


def test_decode_matches_forward_qwen2():
    """Teacher-forced decode reproduces the parallel forward's logits."""
    cfg = registry.get("qwen2-1.5b", smoke=True)
    api = mapi.get_api(cfg, compute_dtype=jnp.float32, remat="none")
    params = api.init(jax.random.key(1))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)

    from repro.models import transformer as T
    hidden, _, _ = T.forward(params, toks, cfg, compute_dtype=jnp.float32,
                             remat="none")
    full_logits = T.logits_fn(params, hidden, cfg)

    cache = api.init_cache(1, 16, dtype=jnp.float32)
    logits_p, cache = api.prefill(params, {"tokens": toks[:, :4]}, cache)
    np.testing.assert_allclose(np.asarray(logits_p[0]),
                               np.asarray(full_logits[0, 3]), rtol=2e-4, atol=2e-4)
    for t in range(4, 8):
        logits_d, cache = api.decode(params, toks[:, t],
                                     jnp.asarray(t, jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits_d[0]),
                               np.asarray(full_logits[0, 7]), rtol=2e-4, atol=2e-4)


def test_causality_property_qwen2():
    """Perturbing a future token must not change past logits."""
    cfg = registry.get("qwen2-1.5b", smoke=True)
    from repro.models import transformer as T
    api = mapi.get_api(cfg, compute_dtype=jnp.float32, remat="none")
    params = api.init(jax.random.key(2))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 10)), jnp.int32)
    h1, _, _ = T.forward(params, toks, cfg, compute_dtype=jnp.float32, remat="none")
    toks2 = toks.at[0, 7].set((toks[0, 7] + 1) % cfg.vocab_size)
    h2, _, _ = T.forward(params, toks2, cfg, compute_dtype=jnp.float32, remat="none")
    np.testing.assert_allclose(np.asarray(h1[0, :7]), np.asarray(h2[0, :7]),
                               rtol=1e-5, atol=1e-5)


def test_param_counts_match_published():
    expected = {
        "deepseek-67b": (67e9, 0.08), "qwen2-1.5b": (1.5e9, 0.1),
        "qwen1.5-4b": (4e9, 0.1), "gemma-7b": (8.5e9, 0.05),
        "whisper-medium": (0.77e9, 0.1), "zamba2-2.7b": (2.7e9, 0.15),
        "granite-moe-1b-a400m": (1.3e9, 0.1), "deepseek-v2-236b": (236e9, 0.03),
        "deepseek-v2-lite": (15.7e9, 0.03),
    }
    for arch, (target, tol) in expected.items():
        n = mapi.param_count(registry.get(arch))
        assert abs(n - target) / target < tol, f"{arch}: {n/1e9:.2f}B vs {target/1e9}B"


def test_all_cells_enumerate():
    cells = registry.cells()
    assert len(cells) == 35  # 11 archs x 3 shapes + 2 sub-quadratic long_500k
    skipped = [c for c in registry.cells(include_skipped=True) if c[2]]
    assert len(skipped) == 9
