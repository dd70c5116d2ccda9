"""DeepSeek-V2-Lite on the program's normal path, at the smoke size on the
CPU: against the benchmark's plain reference (``chipbench/reference/
deepseek_v2.py``), YaRN against its formula written out here, the expert
layer's held share against the uncut layer, un-renormalised gates against
a hand top-k, the layer's counters on known routing, and MLA's q/k 192,
v 128 heads through the fused kernel (interpreted) against
``blocked_attention``."""

import dataclasses
import json
import logging
import math
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import ROOT, compare
from chipbench.reference import deepseek_v2 as R
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.models import layers as L
from repro.models import transformer as T

SMOKE = registry.get("deepseek-v2-lite", smoke=True)
RNG = np.random.default_rng(0)

# the benchmark's configuration file at the smoke widths, holding experts
# 4-7 of the router's 16
TINY = dict(
    json.loads((ROOT / "chipbench" / "configs" / "deepseek-v2-lite.json").read_text()),
    registry_id="deepseek-v2-lite-smoke", hidden_size=128, num_attention_heads=4,
    num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, intermediate_size=384, moe_intermediate_size=64,
    experts_routed_over=16, experts_held_from=4, n_routed_experts=4,
    n_shared_experts=2, num_experts_per_tok=4, num_hidden_layers=3, vocab_size=512)


def _gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * RNG.normal(size=shape), dtype)


# ------------------------------------------------------ against the reference

def test_program_config_holds_the_files_share():
    cfg = R.program_config(TINY)
    assert (cfg.n_layers, cfg.vocab_size, cfg.moe.held) == (3, 512, (4, 4))
    assert cfg.mla.q_lora_rank is None and not cfg.tie_embeddings
    with pytest.raises(ValueError, match="norm_topk differ"):
        R.program_config(dict(TINY, norm_topk_prob=True))
    with pytest.raises(ValueError, match="q_lora_rank differ"):
        R.program_config(dict(TINY, q_lora_rank=48))


def test_loss_and_grads_match_reference():
    """Both in float32 on the same weights and rows: the program (with
    remat, as it trains) and the reference agree to float32 rounding, the
    held share and its capacity drops included."""
    cfg = R.program_config(TINY)
    arch = R.Arch.from_config(TINY)
    w = R.init_weights(arch, jax.random.key(3))
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(w) == jax.tree.structure(shapes)
    assert [x.shape for x in jax.tree.leaves(w)] == [x.shape for x in jax.tree.leaves(shapes)]
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 129)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def program(p):
        return T.loss_fn(p, batch, cfg, compute_dtype=jnp.float32, remat="full")

    with jax.default_matmul_precision("highest"):
        (lp, metrics), gp = jax.jit(jax.value_and_grad(program, has_aux=True))(w)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: R.block_loss(arch, "f32", p, batch["tokens"], batch["labels"])))(w)
    assert 0 < float(metrics["moe_dropped_share"]) < 0.5     # capacity binds
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for (path, g), h in zip(jax.tree_util.tree_leaves_with_path(gp), jax.tree.leaves(gr)):
        assert _gap(g, h) < 1e-5, jax.tree_util.keystr(path)


def test_runner_first_steps_against_reference():
    """The benchmark's runner on the CPU at a tiny size: the trainer's first
    3 steps in bfloat16 against the reference in float32. Every gap is far
    under a tenth (the control's size on the chip); float8 reads above."""
    from chipbench.runners.train import Job

    traffic = json.loads((ROOT / "chipbench" / "traffic" / "train-b4s4096.json").read_text())
    traffic.update(seq_len=64, batch_per_chip=2, warmup_steps=1)
    job = Job({"config_file": TINY, "traffic_file": traffic}, jax.devices()[:1])
    try:
        _, prog, _ = job.first_steps(2**31 + 5, 3)
        ref = job.follow(2**31 + 5, 3)
        fp8 = job.follow(2**31 + 5, 3, prec="fp8")
    finally:
        job.close()
    nums, ctl = compare.numbers(prog, ref), compare.numbers(fp8, ref)
    for key in ("loss_gap", "grad_gap", "update_gap"):
        assert nums[key] < 0.1, (key, nums)
    assert ctl["loss_gap"] > nums["loss_gap"], (nums, ctl)
    hist = job.trainer.history
    assert all("moe_held_share" in h and "moe_dropped_share" in h for h in hist)


# ------------------------------------------------------------------ YaRN

def test_yarn_frequencies_and_softmax_scale():
    """DeepSeek-V2-Lite's rope dims (64, theta 1e4, factor 40 over 4096):
    the ramp runs from index 10 to 23, and the softmax scale is
    mscale(40, 0.707)**2 / sqrt(192) = 1.58963 / 13.8564."""
    cfg = registry.get("deepseek-v2-lite")
    y, dim, theta = cfg.rope_scaling, 64, 1e4
    turns = lambda r: dim * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(theta))
    low, high = math.floor(turns(32)), math.ceil(turns(1))
    assert (low, high) == (10, 23)
    extra = theta ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(L.yarn_freqs(dim, theta, y)), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(L.yarn_freqs(dim, theta, y)),
                               R.yarn_inv_freq(dim, theta, R.Arch.from_config(TINY).yarn),
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert abs(mscale - 1.26080) < 1e-5
    assert abs(L.mla_softmax_scale(cfg) - 0.114721) < 1e-6
    assert L.mla_softmax_scale(cfg) == pytest.approx(mscale ** 2 / math.sqrt(192), rel=1e-12)
    # without YaRN: the plain frequencies, and 1/sqrt(192)
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert L.mla_softmax_scale(plain) == 1 / math.sqrt(192)
    x = _rand((1, 7, 2, dim))
    pos = jnp.arange(7)[None]
    ang = pos[..., None] * extra
    cos, sin = np.cos(ang)[:, :, None], np.sin(ang)[:, :, None]
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:])
    np.testing.assert_allclose(np.asarray(L.apply_rope(x, pos, theta)),
                               np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- the expert layer

def _moe_cfg(**moe):
    base = dataclasses.replace(SMOKE.moe, n_experts=64, top_k=6, d_expert=32, **moe)
    return dataclasses.replace(SMOKE, moe=base)


def _share(params, cfg, first, n):
    """The layer as a chip that holds experts first..first+n-1 has it."""
    held = {k: (v[first:first + n] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in params.items()}
    return held, dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_from=first, n_held=n))


def test_held_shares_add_up_to_the_uncut_layer():
    """Over the 8 disjoint shares of 64 experts, the routed parts summed,
    with the shared experts counted once, equal the uncut layer; every
    share reads the same load-balancing loss (the router is the same)."""
    cfg = _moe_cfg()
    p = L.init_moe(jax.random.key(5), cfg, jnp.float32)
    x = _rand((2, 96, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, aux = jax.jit(lambda: L.moe_apply(p, x, cfg))()
        shared = L.mlp_apply(p["shared"], x, cfg)
        parts = []
        for i in range(8):
            hp, hcfg = _share(p, cfg, 8 * i, 8)
            out, a = jax.jit(lambda: L.moe_apply(hp, x, hcfg))()
            assert float(a["aux"]) == float(aux["aux"])
            parts.append(out - shared)
    assert "moe_held" not in aux                 # every expert held: no counters
    got = sum(parts) + shared
    # float32 rounding: eight partial sums, each with the shared part taken
    # back out, against one sum (float32's unit is 1.2e-7)
    assert _gap(got, whole) < 1e-5
    assert float(jnp.abs(sum(parts)).max()) > 0


def test_gates_not_renormalised():
    """norm_topk_prob=False: each token's routed output is its top-k softmax
    probabilities (over all experts) times those experts' outputs, by hand;
    renormalising over the k chosen gives another result."""
    cfg = _moe_cfg(capacity_factor=64.0)         # no entry dropped
    p = L.init_moe(jax.random.key(6), cfg, jnp.float32)
    x = _rand((1, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = L.moe_apply(p, x, cfg)[0] - L.mlp_apply(p["shared"], x, cfg)
        renorm = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, norm_topk_prob=True))
        got_renorm = L.moe_apply(p, x, renorm)[0] - L.mlp_apply(p["shared"], x, cfg)
    xs, w = np.asarray(x[0], np.float64), jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    want = np.zeros_like(xs)
    for t, xt in enumerate(xs):
        logits = xt @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        for e in np.argsort(-probs)[: cfg.moe.top_k]:
            gate = xt @ w["w_gate"][e]
            h = gate / (1 + np.exp(-gate)) * (xt @ w["w_up"][e])
            want[t] += probs[e] * (h @ w["w_down"][e])
    assert _gap(got[0], want) < 1e-5
    assert _gap(got_renorm[0], want) > 0.1


def test_counters_on_known_routing():
    """16 experts, 4 a token, experts 0-3 held here. Token i is the unit
    vector e_(i mod 8), and the router scores it 8 on expert 0, 6 on expert
    4 + (i mod 4), 4 on expert 8 + (i mod 4) and 2 on expert 12 + (i mod 4):
    one of its 4 entries is held (expert 0), and expert 0 keeps the first
    int(1.25 * 4 * T / 16) + 1 of its T entries."""
    cfg = _moe_cfg()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=16, top_k=4, held_from=0, n_held=4))
    p = L.init_moe(jax.random.key(7), cfg, jnp.float32)
    router = np.zeros((cfg.d_model, 16), np.float32)
    for j in range(8):
        router[j, [0, 4 + j % 4, 8 + j % 4, 12 + j % 4]] = [8.0, 6.0, 4.0, 2.0]
    p["router"] = jnp.asarray(router)
    for t, cap in ((32, 11), (64, 21)):
        x = jnp.asarray(np.eye(cfg.d_model, dtype=np.float32)[np.arange(t) % 8])[None]
        _, aux = jax.jit(lambda: L.moe_apply(p, x, cfg))()
        assert int(1.25 * 4 * t / 16) + 1 == cap
        assert float(aux["moe_routed"]) == 4 * t
        assert float(aux["moe_held"]) == t
        assert float(aux["moe_dropped"]) == t - cap
        metrics = {"moe_held_share": aux["moe_held"] / aux["moe_routed"],
                   "moe_dropped_share": aux["moe_dropped"] / aux["moe_held"]}
        assert float(metrics["moe_held_share"]) == 0.25
        assert float(metrics["moe_dropped_share"]) == (t - cap) / t


def test_trainer_logs_the_counters(caplog):
    """The step returns the held-share layer's counters and the trainer
    keeps them in its history and its log line; a layer holding every
    expert (Granite) adds nothing to the step's outputs."""
    from repro.train import Trainer, TrainerOptions
    from repro.train.train_step import make_train_state, make_train_step

    cfg = R.program_config(TINY)

    class Source:
        def batch(self, step):
            tk = np.random.default_rng(step).integers(0, cfg.vocab_size, (2, 65))
            return {"tokens": tk[:, :-1].astype(np.int32), "labels": tk[:, 1:].astype(np.int32)}

    with tempfile.TemporaryDirectory() as ckpt, caplog.at_level(logging.INFO, "repro.trainer"):
        tr = Trainer(cfg, TrainConfig(total_steps=2), Source(),
                     options=TrainerOptions(ckpt_dir=ckpt, log_every=1))
        tr.run(2)
    for h in tr.history:
        assert 0 < h["moe_held_share"] < 1 and 0 <= h["moe_dropped_share"] < 1
    assert any("moe_held_share" in r.getMessage() for r in caplog.records)
    granite = registry.get("granite-moe-1b-a400m", smoke=True)
    tc = TrainConfig()
    state = jax.eval_shape(lambda: make_train_state(granite, tc, jax.random.key(0)))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32) for k in ("tokens", "labels")}
    _, metrics = jax.eval_shape(make_train_step(granite, tc), state, batch)
    assert not [k for k in metrics if k.startswith("moe_")]


# ------------------------------------------------------------ fused MLA

def test_fused_mla_matches_blocked():
    """MLA's heads through the kernel in interpret mode: q and k 192 wide,
    v 128, one KV head per query head, the softmax scale folded into q in
    f32; output and q/k/v gradients within two bf16 units of
    blocked_attention (the bound tests/test_attention_path.py uses)."""
    b, s, h = 1, 2048, 2
    scale = L.mla_softmax_scale(registry.get("deepseek-v2-lite"))
    q = _rand((b, s, h, 192), jnp.bfloat16)
    k = _rand((b, s, h, 192), jnp.bfloat16)
    v = _rand((b, s, h, 128), jnp.bfloat16)
    ct = _rand((b, s, h, 128))

    def fused(q, k, v):
        qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return L.fused_causal_attention(qs, k, v, interpret=True)

    def blocked(q, k, v):
        return L.blocked_attention(q, k, v, causal=True, scale=scale)

    def run(attend):
        f = lambda q, k, v: (attend(q, k, v).astype(jnp.float32) * ct).sum()
        out = jax.jit(attend)(q, k, v)
        return out, jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    (out_f, grads_f), (out_b, grads_b) = run(fused), run(blocked)
    assert out_f.shape == (b, s, h, 128)
    assert _gap(out_f, out_b) < 2.0 ** -7
    for name, gf, gb in zip("qkv", grads_f, grads_b):
        assert gf.dtype == gb.dtype == jnp.bfloat16, name
        assert _gap(gf, gb) < 2.0 ** -7, name
