"""Compression-aware planning (DESIGN.md §15): quantization edge cases, the
fused pallas quantize+bucketize kernel against its reference, bits as a
plan-cache axis, the per-bucket width sweep (including the *decline* on
latency-bound buckets), the EF-compressed planned sync modes, and the
8-device equivalence / no-retrace harnesses."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.configs.base import TrainConfig
from repro.core import bucketing, compression, planner
from repro.core import plan_cache as PC
from repro.core.plan_cache import PlanCache, PlanKey
from repro.core.timing import Ring
from repro.core.topology import FailureMask
from repro.kernels import ops as kops
from repro.kernels import quant as kquant
from repro.kernels import ref as kref
from repro.train import train_step as TS

MASK = FailureMask(dead_segments=((0, 1),), dead_wavelengths=((2, 0),))


# ---------------------------------------------------------------------------
# quantize / dequantize edge cases
# ---------------------------------------------------------------------------

def test_quantize_zero_size_leaf():
    c = compression.quantize(jnp.zeros((0,), jnp.float32))
    assert c.q.shape == (0,) and c.q.dtype == jnp.int8
    assert compression.dequantize(c).shape == (0,)
    deq, res = compression.ef_compress_blocks(
        jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.float32), bits=8)
    assert deq.shape == (0,) and res.shape == (0,)


def test_quantize_all_zero_scale_floor():
    """An all-zero tensor hits the 1e-30 scale floor: q == 0, dequant == 0,
    and nothing overflows or NaNs."""
    x = jnp.zeros((257,), jnp.float32)
    c = compression.quantize(x)
    assert float(c.scale) == pytest.approx(1e-30 / 127.0)
    np.testing.assert_array_equal(np.asarray(c.q), 0)
    np.testing.assert_array_equal(np.asarray(compression.dequantize(c)), 0.0)
    deq, res = compression.ef_compress_blocks(x, jnp.zeros_like(x), bits=8,
                                              block=64)
    assert np.isfinite(np.asarray(deq)).all()
    np.testing.assert_array_equal(np.asarray(deq), 0.0)
    np.testing.assert_array_equal(np.asarray(res), 0.0)


def test_quantize_roundtrip_error_bound():
    """|x - dequant(quantize(x))| <= scale/2 element-wise (symmetric linear
    quantization never clips below the absmax that set the scale)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4097,)).astype(np.float32) * 3.0)
    c = compression.quantize(x)
    err = np.abs(np.asarray(x) - np.asarray(compression.dequantize(c)))
    assert err.max() <= float(c.scale) / 2 + 1e-12


def test_quantize_bf16_input():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1000,)), jnp.bfloat16)
    deq, res = compression.ef_compress_blocks(x, jnp.zeros_like(x), bits=8,
                                              block=256)
    assert deq.dtype == jnp.bfloat16 and res.dtype == jnp.bfloat16
    # the EF invariant holds in f32 to bf16 resolution
    t = np.asarray(x, np.float32)
    got = np.asarray(deq, np.float32) + np.asarray(res, np.float32)
    np.testing.assert_allclose(got, t, atol=0.02)


def test_ef_compress_blocks_invariant_and_identity():
    rng = np.random.default_rng(3)
    flat = jnp.asarray(rng.normal(size=(3000,)).astype(np.float32))
    resid = jnp.asarray(rng.normal(size=(3000,)).astype(np.float32) * 0.1)
    deq, new_r = compression.ef_compress_blocks(flat, resid, bits=8,
                                                block=256)
    # deq + new_residual == flat + residual (what EF-SGD needs)
    np.testing.assert_allclose(np.asarray(deq) + np.asarray(new_r),
                               np.asarray(flat) + np.asarray(resid),
                               atol=1e-6)
    # per-block bound: error <= scale/2 per block
    t = (np.asarray(flat) + np.asarray(resid)).astype(np.float32)
    tp = np.pad(t, (0, (-len(t)) % 256)).reshape(-1, 256)
    scales = np.maximum(np.abs(tp).max(axis=1), 1e-30) / 127.0
    err = np.abs(tp - np.pad(np.asarray(deq), (0, (-len(t)) % 256))
                 .reshape(-1, 256))
    assert (err <= scales[:, None] / 2 + 1e-12).all()
    # bits >= 32 is the exact pass-through with a zero residual
    deq32, r32 = compression.ef_compress_blocks(flat, resid, bits=32)
    assert deq32 is flat
    np.testing.assert_array_equal(np.asarray(r32), 0.0)


def test_ef_compress_blocks_per_block_scales():
    """Blocks are scaled independently: a tiny-magnitude block next to a
    huge one keeps its own resolution instead of being flattened to zero by
    a per-tensor scale."""
    small = np.full(64, 1e-4, np.float32)
    big = np.full(64, 1e4, np.float32)
    flat = jnp.asarray(np.concatenate([small, big]))
    deq, _ = compression.ef_compress_blocks(flat, jnp.zeros_like(flat),
                                            bits=8, block=64)
    got = np.asarray(deq)
    np.testing.assert_allclose(got[:64], small, rtol=0.01)
    np.testing.assert_allclose(got[64:], big, rtol=0.01)


# ---------------------------------------------------------------------------
# fused pallas kernel vs reference (golden equivalence)
# ---------------------------------------------------------------------------

# 300_001 spans several row tiles of the kernel grid, the last one ragged
@pytest.mark.parametrize("n", [1, 255, 1024, 5000, 300_001])
@pytest.mark.parametrize("bits", [8, 4])
def test_fused_kernel_matches_ref(n, bits):
    rng = np.random.default_rng(n * 31 + bits)
    g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(n,)).astype(np.float32) * 0.3)
    q, s, deq, res, nn = kquant.ef_quantize_bucketize(
        g, e, block=256, bits=bits, interpret=True)
    rq, rs, rdeq, rres, rn = kref.ef_quantize_bucketize_ref(
        g, e, block=256, bits=bits)
    assert nn == rn == n
    # the wire contract (q, scales, deq) is bit-exact
    np.testing.assert_array_equal(np.asarray(q), np.asarray(rq))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(rdeq))
    # the residual matches to 1 ulp (the fused t - q*scale contracts to FMA)
    np.testing.assert_allclose(np.asarray(res), np.asarray(rres),
                               atol=3e-7, rtol=0)


def test_fused_path_matches_jnp_path():
    rng = np.random.default_rng(5)
    flat = jnp.asarray(rng.normal(size=(3000,)).astype(np.float32))
    resid = jnp.asarray(rng.normal(size=(3000,)).astype(np.float32) * 0.1)
    dj, rj = compression.ef_compress_blocks(flat, resid, bits=8, block=256,
                                            fused=False)
    df, rf = compression.ef_compress_blocks(flat, resid, bits=8, block=256,
                                            fused=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(df))
    np.testing.assert_allclose(np.asarray(rj), np.asarray(rf),
                               atol=3e-7, rtol=0)


def test_ops_wrapper_jits():
    g = jnp.ones((512,), jnp.float32)
    e = jnp.zeros((512,), jnp.float32)
    q, s, deq, res, n = kops.ef_quantize_bucketize(
        g, e, block=256, bits=8, interpret=True)
    assert n == 512 and q.dtype == jnp.int8 and s.shape == (2,)
    np.testing.assert_allclose(np.asarray(deq), 1.0, atol=1e-2)


# ---------------------------------------------------------------------------
# satellite regressions: nested-tuple EF pytrees, eager RD routing
# ---------------------------------------------------------------------------

def test_ef_allreduce_tree_nested_tuple_leaves():
    """Pytrees whose containers are tuples used to be misparsed by the old
    ``is_leaf=tuple`` rebuild (a nested tuple looked like a (synced,
    residual) pair).  The flatten/unflatten rebuild keeps any treedef."""
    grads = {"a": (jnp.ones((4,)), (jnp.full((3,), 2.0), jnp.zeros((2,)))),
             "b": jnp.ones((5,))}
    ef = compression.init_ef_state(grads)
    synced, new_ef = compression.ef_allreduce_tree(grads, ef, "i", 1)
    assert (jax.tree.structure(synced) == jax.tree.structure(grads)
            == jax.tree.structure(new_ef))
    for g, s in zip(jax.tree.leaves(grads), jax.tree.leaves(synced)):
        np.testing.assert_allclose(np.asarray(s), np.asarray(g), atol=1e-6)


def test_compressed_rd_rejects_non_power_of_two_eagerly():
    x = jnp.ones((8,))
    with pytest.raises(ValueError, match="power-of-two") as ei:
        compression.compressed_allreduce_rd(x, "i", 6)
    assert "compressed_allreduce" in str(ei.value)
    assert compression.rd_axis_valid(8)
    assert not compression.rd_axis_valid(6)
    # axis_size 1 short-circuits everywhere
    assert compression.compressed_allreduce(x, "i", 1) is x


# ---------------------------------------------------------------------------
# plan-cache schema v5: bits is a cache axis
# ---------------------------------------------------------------------------

def test_bits_keys_never_mix():
    cache = PlanCache()
    k32 = PlanKey(n=16, w=8)
    k8 = PlanKey(n=16, w=8, bits=8)
    p32 = cache.profile(k32)
    p8 = cache.profile(k8)
    assert cache.stats.misses == 2 and cache.stats.memory_hits == 0
    assert cache.profile(k32) is p32 and cache.profile(k8) is p8
    assert cache.stats.memory_hits == 2
    assert "-B32." in k32.filename() and "-B8." in k8.filename()
    assert k32.filename() != k8.filename()
    # the width-8 profile evaluates cheaper than width-32 on a bandwidth-
    # bound payload: same structure, width-scaled payload classes
    ring = Ring(16, 8)
    d = np.array([1e8])
    t32 = p32.evaluate(ring, d).total_s[0]
    t8 = p8.evaluate(ring, d).total_s[0]
    assert t8 < t32


def test_bits_validation():
    with pytest.raises(ValueError, match="bits"):
        PlanKey(n=16, w=8, bits=0)
    with pytest.raises(ValueError, match="bits"):
        PlanKey(n=16, w=8, bits=33)


def test_pre_bump_bits_artifacts_invisible(tmp_path):
    """A disk artifact stamped with the previous schema version is never
    loaded — the v4->v5 bump (bits axis) invalidates by rename."""
    key = PlanKey(n=16, w=8, bits=8)
    warm = PlanCache(disk_dir=tmp_path)
    warm.profile(key)
    assert warm.stats.disk_writes == 1
    cur = key.filename()
    old = cur.replace(f".v{PC.SCHEMA_VERSION}.", f".v{PC.SCHEMA_VERSION - 1}.")
    os.replace(tmp_path / cur, tmp_path / old)
    cold = PlanCache(disk_dir=tmp_path)
    cold.profile(key)
    assert (cold.stats.disk_hits, cold.stats.misses) == (0, 1)


def test_degraded_depth_bits_composition(tmp_path):
    """bits composes with the failure-mask and pipeline-depth axes: all
    eight (healthy|degraded) x (D1|D2) x (B32|B4) variants are distinct
    cache citizens with distinct artifacts."""
    cache = PlanCache(disk_dir=tmp_path)
    keys = [PlanKey(n=8, w=4, collective="reduce_scatter", failures=f,
                    depth=d, bits=b)
            for f in (None, MASK) for d in (1, 2) for b in (32, 4)]
    assert len({k.filename() for k in keys}) == 8
    for k in keys:
        cache.profile(k)
    assert cache.stats.misses == 8
    for k in keys:
        cache.profile(k)
    assert cache.stats.memory_hits == 8
    # disk round-trip preserves the width-scaled classes
    cold = PlanCache(disk_dir=tmp_path)
    for k in keys:
        cold.profile(k)
    assert cold.stats.disk_hits == 8
    ring = Ring(8, 4)
    d = np.array([1e8])
    k4 = PlanKey(n=8, w=4, collective="reduce_scatter", bits=4)
    k32 = PlanKey(n=8, w=4, collective="reduce_scatter")
    assert cold.profile(k4).evaluate(ring, d).total_s[0] < \
        cold.profile(k32).evaluate(ring, d).total_s[0]


# ---------------------------------------------------------------------------
# planner: the per-bucket width sweep and the decline
# ---------------------------------------------------------------------------

def test_bits32_is_the_default_identity():
    p = planner.CostParams.optical()
    sizes = [4096.0, 1 << 20, 64 << 20]
    a = planner.plan_buckets(256, sizes, p)
    b = planner.plan_buckets(256, sizes, p, bits=32)
    assert [(x.strategy, x.cost_s) for x in a] == \
        [(x.strategy, x.cost_s) for x in b]
    assert all("bits" not in x.detail for x in a)


def test_bits_sweep_decline_and_win():
    """The tuner declines compression on a latency-bound 4 KB bucket (the
    quantize overhead exceeds the β saving) and takes int4 on a 64 MB
    bandwidth-bound bucket."""
    p = planner.CostParams.optical()
    sizes = [4096.0, 64 << 20]
    plans = planner.plan_buckets(256, sizes, p, bits_candidates=(32, 8, 4))
    small, big = plans
    assert small.detail["bits"] == 32 and "quant_s" not in small.detail
    assert big.detail["bits"] < 32 and big.detail["quant_s"] > 0
    # the sweep record covers every candidate width and the winner is argmin
    for pl in plans:
        comp = pl.detail["compression"]
        assert set(comp) == {"32", "8", "4"}
        assert pl.cost_s == min(comp.values())


def test_quant_overhead_model():
    """plan_buckets(bits=8) = width-scaled wire cost + the explicit
    quantize/dequant overhead 2·alpha_q + 2·b/B_q, stamped in detail."""
    p = planner.CostParams.optical()
    b = float(1 << 20)
    pl8 = planner.plan_buckets(64, [b], p, bits=8)[0]
    want_over = 2 * p.quant_alpha_s + 2 * b / p.quant_Bps
    assert pl8.detail["quant_s"] == pytest.approx(want_over)
    assert pl8.detail["bits"] == 8
    # wire-only part beats fp32 on a bandwidth-bound bucket
    pl32 = planner.plan_buckets(64, [b], p)[0]
    assert pl8.cost_s - pl8.detail["quant_s"] < pl32.cost_s


def test_crossover_table_bits_column():
    rows = planner.crossover_table(
        64, byte_sizes=(4096.0, float(16 << 20)),
        params=planner.CostParams.optical(), bits_candidates=(32, 8))
    assert [r["bits"] for r in rows] == [32, 8]


def test_simulated_backend_bits():
    p = planner.CostParams.optical()
    pl = planner.plan_buckets(16, [float(1 << 22)], p, backend="simulated",
                              bits=8)[0]
    assert pl.detail["bits"] == 8 and pl.cost_s > 0


# ---------------------------------------------------------------------------
# train wiring: plan_gradient_sync sweep, frozen bits, compressed buckets
# ---------------------------------------------------------------------------

class _StubMesh:
    axis_names = ("data", "model")
    shape = {"data": 256, "model": 16}


class _StubMesh2:
    axis_names = ("data", "pod")
    shape = {"data": 4, "pod": 2}


def _abstract_grads():
    return {"emb": jax.ShapeDtypeStruct((16 << 20,), jnp.float32),
            "ln": jax.ShapeDtypeStruct((512,), jnp.float32)}


def test_plan_gradient_sync_compress_sweep_and_freeze():
    tc = TrainConfig(sync_algorithm="planned_compressed",
                     bucket_bytes=8 << 20)
    cost = planner.CostParams.optical()
    plans = TS.plan_gradient_sync(_abstract_grads(), tc, _StubMesh(),
                                  cost=cost, compress=True)
    assert plans.bits is not None
    assert len(plans.bits) == len(plans.spec.bucket_sizes)
    # the 64 MB embedding bucket compresses, the 2 KB layernorm declines
    assert min(plans.bits) < 32 and 32 in plans.bits
    # the frozen-bits path reproduces the widths without re-sweeping
    again = TS.plan_gradient_sync(_abstract_grads(), tc, _StubMesh(),
                                  cost=cost, bits_overrides=plans.bits)
    assert again.bits == plans.bits
    assert [p.strategy for p in again.plans["data"]] == \
        [p.strategy for p in plans.plans["data"]]


def test_plan_gradient_sync_sharded_compressed():
    tc = TrainConfig(sync_algorithm="planned_sharded_compressed",
                     bucket_bytes=8 << 20)
    cost = planner.CostParams.optical()
    plans = TS.plan_gradient_sync(_abstract_grads(), tc, _StubMesh(),
                                  cost=cost, sharded=True, compress=True)
    assert plans.bits is not None and plans.rs_plans and plans.ag_plans
    assert len(plans.rs_plans["data"]) == len(plans.bits)


def test_sync_controller_compressed_bits_frozen_across_replan():
    """The zero-retrace contract for the compressed mode: a degraded
    re-plan re-picks strategies but NEVER the wire widths the step was
    traced with."""
    tc = TrainConfig(sync_algorithm="planned_sharded_compressed",
                     bucket_bytes=1 << 10)
    grads = {k: jax.ShapeDtypeStruct((n,), jnp.float32)
             for k, n in (("a", 37), ("b", 129), ("c", 513))}
    ctrl = TS.SyncController(grads, tc, _StubMesh2())
    assert ctrl.compress
    b0 = ctrl.plans.bits
    assert b0 is not None
    healthy = ctrl.arrays()
    degraded = ctrl.replan(MASK)
    assert ctrl.plans.bits == b0
    for k in healthy:
        assert degraded[k].shape == healthy[k].shape
        assert degraded[k].dtype == healthy[k].dtype
    restored = ctrl.replan(None)
    assert ctrl.plans.bits == b0 and ctrl.last_replan_cached


def test_bucketed_apply_compressed_numerics():
    rng = np.random.RandomState(0)
    tree = {"a": jnp.asarray(rng.randn(3000).astype(np.float32)),
            "b": jnp.asarray(rng.randn(10).astype(np.float32))}
    ef = jax.tree.map(jnp.zeros_like, tree)
    spec = bucketing.plan_buckets(tree, 8192)
    bits = tuple(8 if s > 1000 else 32 for s in spec.bucket_sizes)
    out, new_ef = bucketing.bucketed_apply_compressed(
        tree, ef, lambda f, n, i: f, spec, bits=bits, block=256)
    for k in tree:  # EF invariant per bucket: deq + residual == grad
        np.testing.assert_allclose(
            np.asarray(out[k]) + np.asarray(new_ef[k]),
            np.asarray(tree[k]), atol=1e-6)
    # the declined bucket is an exact pass-through with zero residual
    np.testing.assert_array_equal(np.asarray(out["b"]),
                                  np.asarray(tree["b"]))
    assert float(jnp.abs(new_ef["b"]).max()) == 0.0
    with pytest.raises(ValueError, match="bits"):
        bucketing.bucketed_apply_compressed(
            tree, ef, lambda f, n, i: f, spec, bits=(8,), block=256)


def test_make_train_state_carries_ef_for_compressed_modes():
    from repro.configs import registry
    cfg = registry.get("qwen2-1.5b", smoke=True)
    tc = TrainConfig(sync_algorithm="planned_compressed", remat="none")
    state = TS.make_train_state(cfg, tc, jax.random.key(0))
    assert "ef" in state
    assert (jax.tree.structure(state["ef"])
            == jax.tree.structure(state["params"]))
    tc2 = TrainConfig(sync_algorithm="planned", remat="none")
    assert "ef" not in TS.make_train_state(cfg, tc2, jax.random.key(0))


# ---------------------------------------------------------------------------
# EF convergence: compressed SGD reaches the uncompressed optimum
# ---------------------------------------------------------------------------

def _ef_sgd_distance(bits: int, steps: int, lr: float = 0.2,
                     workers: int = 4, dim: int = 512, seed: int = 42) -> float:
    """Distributed quadratic: worker w holds f_w(x) = ||x - c_w||^2 / 2;
    the optimum is mean(c_w).  Each worker EF-compresses its gradient, the
    'collective' averages the dequantized values."""
    rng = np.random.default_rng(seed)
    cs = rng.normal(size=(workers, dim)).astype(np.float32) * 5.0
    opt = cs.mean(axis=0)
    x = np.zeros(dim, np.float32)
    resid = [jnp.zeros(dim, jnp.float32) for _ in range(workers)]
    for _ in range(steps):
        deqs = []
        for w in range(workers):
            g = jnp.asarray(x - cs[w])
            deq, resid[w] = compression.ef_compress_blocks(
                g, resid[w], bits=bits, block=128)
            deqs.append(np.asarray(deq))
        x = x - lr * np.mean(deqs, axis=0)
    return float(np.linalg.norm(x - opt))


def test_ef_convergence_50_steps():
    """int4 EF-SGD converges to the DP-mean optimum: after ~50 steps the
    iterate is within a small fraction of the initial distance, and the
    trajectory keeps improving (the residual feeds back, so quantization
    error does not accumulate as a bias)."""
    d0 = _ef_sgd_distance(4, 0)
    d10 = _ef_sgd_distance(4, 10)
    d50 = _ef_sgd_distance(4, 50)
    assert d50 < d10 < d0
    # the int4 EF steady state floors near lr·(quant error); 5% of the
    # initial distance bounds it with margin across seeds
    assert d50 < 0.05 * d0
    # int8 lands at least as close as int4
    assert _ef_sgd_distance(8, 50) <= d50 * 1.5


# ---------------------------------------------------------------------------
# device-level harnesses (8 simulated devices)
# ---------------------------------------------------------------------------

PLANNED_COMPRESSED_EQ = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import TrainConfig
from repro.train import train_step as TS

def smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={'data', 'pod'})

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ('data', 'pod'))
rng = np.random.default_rng(0)
tree = {k: rng.normal(size=(8, n)).astype(np.float32)
        for k, n in (('a', 37), ('b', 129), ('c', 513))}
abstract = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape[1:], jnp.float32), tree)

for alg in ('planned_compressed', 'planned_sharded_compressed'):
    tc = TrainConfig(sync_algorithm=alg, bucket_bytes=1 << 10,
                     compress_block=128)
    # force int8 on every bucket: at this toy scale the tuner would decline,
    # and a declined sweep would make the equivalence trivially exact
    sweep = TS.plan_gradient_sync(abstract, tc, mesh, compress=True,
                                  sharded=alg == 'planned_sharded_compressed')
    nb = len(sweep.spec.bucket_sizes)
    plans = TS.plan_gradient_sync(abstract, tc, mesh, compress=True,
                                  sharded=alg == 'planned_sharded_compressed',
                                  bits_overrides=(8,) * nb)
    assert plans.bits == (8,) * nb

    def body(stacked):
        local = jax.tree.map(lambda x: x[0], stacked)
        out, new_ef = TS.sync_gradients(local, tc, mesh, sync_plans=plans)
        return (jax.tree.map(lambda x: x[None], out),
                jax.tree.map(lambda x: x[None], new_ef))

    spec = P(('data', 'pod'))
    step = jax.jit(smap(body, mesh,
                        (jax.tree.map(lambda _: spec, tree),),
                        (jax.tree.map(lambda _: spec, tree),
                         jax.tree.map(lambda _: spec, tree))))
    got, new_ef = step(tree)

    # per-worker EF tolerance: with a zero residual the wire error per
    # element is <= scale/2, scale = blockmax/127; the mean inherits the
    # worst worker's bound
    for k, v in tree.items():
        want = v.mean(axis=0)
        tol = 0.0
        for w in range(8):
            t = np.pad(v[w], (0, (-v.shape[1]) % 128)).reshape(-1, 128)
            tol = max(tol, (np.abs(t).max(axis=1) / 127.0 / 2).max())
        err = np.abs(np.asarray(got[k]) - want[None]).max()
        assert err <= tol * 1.01 + 1e-7, (alg, k, err, tol)
        # EF invariant on-device: deq + residual == local grad (mean'd out)
        assert np.isfinite(np.asarray(new_ef[k])).all()
    print(alg, 'OK')
print('COMPRESSED_EQ_OK')
"""


def test_planned_compressed_matches_dp_mean_8dev(subproc):
    assert "COMPRESSED_EQ_OK" in subproc(PLANNED_COMPRESSED_EQ)


COMPRESSED_NO_RETRACE = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import TrainConfig
from repro.core.topology import FailureMask
from repro.train import train_step as TS

def smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={'data', 'pod'})

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ('data', 'pod'))
tc = TrainConfig(sync_algorithm='planned_sharded_compressed',
                 bucket_bytes=1 << 10, compress_block=128)
rng = np.random.default_rng(0)
tree = {k: rng.normal(size=(8, n)).astype(np.float32)
        for k, n in (('a', 37), ('b', 129), ('c', 513))}

ctrl = TS.SyncController(
    jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], jnp.float32),
                 tree), tc, mesh)
bits0 = ctrl.plans.bits

TRACES = 0
def body(stacked, codes):
    global TRACES
    TRACES += 1
    local = jax.tree.map(lambda x: x[0], stacked)
    out, _ = TS.sync_gradients(local, tc, mesh, sync_plans=ctrl.plans,
                               plan_codes=codes)
    return jax.tree.map(lambda x: x[None], out)

spec = P(('data', 'pod'))
healthy = ctrl.arrays()
in_specs = (jax.tree.map(lambda _: spec, tree),
            jax.tree.map(lambda _: P(), healthy))
step = jax.jit(smap(body, mesh, in_specs, jax.tree.map(lambda _: spec, tree)))

got0 = step(tree, healthy)
mask = FailureMask(dead_segments=((0, 1),), dead_wavelengths=((2, 0),))
degraded = ctrl.replan(mask)
assert ctrl.plans.bits == bits0          # widths frozen across the re-plan
got1 = step(tree, degraded)
healed = ctrl.replan(None)
assert ctrl.plans.bits == bits0
got2 = step(tree, healed)
assert TRACES == 1, TRACES               # one compile across the storm
for k, v in tree.items():
    want = v.mean(axis=0)
    for got in (got0, got1, got2):
        assert np.abs(np.asarray(got[k]) - want[None]).max() < 0.05, k
print('COMPRESSED_NO_RETRACE_OK')
"""


def test_compressed_midrun_plan_swap_no_retrace(subproc):
    assert "COMPRESSED_NO_RETRACE_OK" in subproc(COMPRESSED_NO_RETRACE)


# ---------------------------------------------------------------------------
# hypothesis: the round-trip bound holds across shapes, widths and scales
# ---------------------------------------------------------------------------

DEEP_EXAMPLES = int(os.environ.get("REPRO_DEEP_EXAMPLES", "300"))

_strategy = dict(
    n=st.integers(min_value=1, max_value=3000),
    bits=st.sampled_from([2, 4, 8]),
    block=st.sampled_from([64, 256, 1024]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    scale_pow=st.integers(min_value=-15, max_value=15),
)


def check_roundtrip(n, bits, block, seed, scale_pow):
    rng = np.random.default_rng(seed)
    flat = jnp.asarray((rng.normal(size=(n,)) * 10.0 ** scale_pow)
                       .astype(np.float32))
    resid = jnp.asarray((rng.normal(size=(n,)) * 10.0 ** (scale_pow - 1))
                        .astype(np.float32))
    deq, new_r = compression.ef_compress_blocks(flat, resid, bits=bits,
                                                block=block)
    t = (np.asarray(flat, np.float64) + np.asarray(resid, np.float64))
    # EF invariant
    got = np.asarray(deq, np.float64) + np.asarray(new_r, np.float64)
    np.testing.assert_allclose(got, t, rtol=1e-5,
                               atol=1e-6 * 10.0 ** scale_pow)
    # per-block half-step bound
    qmax = 2 ** (bits - 1) - 1
    tp = np.pad(t, (0, (-n) % block)).reshape(-1, block)
    scales = np.maximum(np.abs(tp).max(axis=1), 1e-30) / qmax
    err = np.abs(tp - np.pad(np.asarray(deq, np.float64), (0, (-n) % block))
                 .reshape(-1, block))
    assert (err <= scales[:, None] * 0.51 + 1e-30).all()


@settings(max_examples=20, deadline=None)
@given(**_strategy)
def test_roundtrip_bound_hypothesis(n, bits, block, seed, scale_pow):
    check_roundtrip(n, bits, block, seed, scale_pow)


@pytest.mark.deep
@settings(max_examples=DEEP_EXAMPLES, deadline=None)
@given(**_strategy)
def test_roundtrip_bound_hypothesis_deep(n, bits, block, seed, scale_pow):
    check_roundtrip(n, bits, block, seed, scale_pow)


@pytest.mark.deep
@settings(max_examples=max(10, DEEP_EXAMPLES // 10), deadline=None)
@given(bits=st.sampled_from([4, 8]),
       workers=st.sampled_from([2, 4, 8]),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_ef_convergence_hypothesis_deep(bits, workers, seed):
    """EF-SGD converges on random quadratics regardless of width, worker
    count or problem instance: 50 steps improve on 10, and the steady state
    lands within 5% of the initial distance (nightly deep lane)."""
    d0 = _ef_sgd_distance(bits, 0, workers=workers, seed=seed)
    d10 = _ef_sgd_distance(bits, 10, workers=workers, seed=seed)
    d50 = _ef_sgd_distance(bits, 50, workers=workers, seed=seed)
    assert d50 <= d10 < d0
    assert d50 < 0.05 * d0
