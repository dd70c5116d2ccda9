"""The expert layer's dispatch and combine (``layers.moe_apply``): one
transpose pair of slot gathers (``gather_slots`` / ``sum_to_tokens``),
against the three-gather formulation it replaced, kept here as the
reference: output and gradients in float32 with every expert held and with
a held share, capacity binding in both, on one device and on an
expert-parallel mesh; and the lowered gradient's scatters."""

import dataclasses
import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax._src.lib.mlir import ir

from repro.configs import registry
from repro.models import layers as L


def reference_moe(p, x, cfg):
    """The routed part of the layer as three data gathers (slots from
    tokens, entries from slots, the inverse permutation), whose transposes
    autodiff writes as scatter-adds. Returns (output, aux, entries dropped
    by capacity among the held)."""
    mo = cfg.moe
    b, s, d = x.shape
    g, tg = 1, b * s
    n_all, k = mo.n_experts, mo.top_k
    h0, e = mo.held
    share = e < n_all
    xt = x.reshape(g, tg, d)
    probs = jax.nn.softmax((xt @ p["router"]).astype(jnp.float32), axis=-1)
    weights, eids = lax.top_k(probs, k)
    if mo.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    density = jax.nn.one_hot(eids, n_all).sum((0, 1, 2)) / (tg * k)
    aux = n_all * jnp.sum(density * probs.mean((0, 1))) * mo.router_aux_weight
    cap = int(mo.capacity_factor * k * tg / n_all) + 1
    tgk = tg * k
    flat_e = eids.reshape(g, tgk)
    if share:
        flat_e = jnp.where((flat_e >= h0) & (flat_e < h0 + e), flat_e - h0, e)
    order = jnp.argsort(flat_e, axis=-1)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    inv_order = jnp.argsort(order, axis=-1)
    counts = (flat_e[..., None] == jnp.arange(e)).sum(1)
    seg_start = jnp.cumsum(counts, axis=-1) - counts
    slot_src = seg_start[..., None] + jnp.arange(cap)[None, None]
    slot_valid = jnp.arange(cap)[None, None] < counts[..., None]
    slot_src = jnp.clip(slot_src, 0, tgk - 1).reshape(g, e * cap)
    slot_tok = jnp.take_along_axis(order // k, slot_src, axis=1)
    xs = jnp.take_along_axis(xt, slot_tok[..., None], axis=1)
    slot_buf = jnp.where(slot_valid.reshape(g, e * cap, 1), xs, 0).reshape(g, e, cap, d)
    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", slot_buf, p["w_gate"]))
         * jnp.einsum("gecd,edf->gecf", slot_buf, p["w_up"]))
    y_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    elsewhere = sorted_e >= e
    sorted_e = jnp.minimum(sorted_e, e - 1)
    pos_in_e = jnp.arange(tgk)[None] - jnp.take_along_axis(seg_start, sorted_e, axis=-1)
    dropped = pos_in_e >= cap
    slot_of = sorted_e * cap + jnp.clip(pos_in_e, 0, cap - 1)
    y_sorted = jnp.take_along_axis(y_e.reshape(g, e * cap, d), slot_of[..., None], axis=1)
    y_sorted = jnp.where((dropped | elsewhere)[..., None], 0, y_sorted)
    y_entries = jnp.take_along_axis(y_sorted, inv_order[..., None], axis=1)
    out = (y_entries * weights.reshape(g, tgk)[..., None]).reshape(g, tg, k, d).sum(2)
    return out.reshape(b, s, d), {"aux": aux}, (dropped & ~elsewhere).sum()


def _cfg(n_experts, top_k, n_held=0, capacity_factor=1.0):
    base = registry.get("granite-moe-1b-a400m", smoke=True)
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=n_experts, top_k=top_k, d_expert=48,
        capacity_factor=capacity_factor, n_held=n_held))


def _gap(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# (experts, per token, held): every expert held gives more slots than
# entries; 2 held of 16 give 34 slots against 256 entries
@pytest.mark.parametrize("n_experts,top_k,n_held", [(8, 2, 0), (16, 4, 2)],
                         ids=["all_held", "held_share"])
def test_pair_matches_three_gathers(n_experts, top_k, n_held):
    """Output and the gradients with respect to x, the router and the three
    expert weights, in float32, against the reference; the capacity drops
    entries of held experts in both shapes."""
    cfg = _cfg(n_experts, top_k, n_held)
    p = L.init_moe(jax.random.key(1), cfg, jnp.float32)
    p["router"] = p["router"] * 40.0          # sharp routing: experts overflow
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.key(3), x.shape, jnp.float32)

    def loss(fn):
        def f(p, x):
            out, aux = fn(p, x, cfg)[:2]
            return (out * ct).sum() + aux["aux"]
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    with jax.default_matmul_precision("highest"):
        out, aux = jax.jit(lambda p, x: L.moe_apply(p, x, cfg))(p, x)
        (lv, (gp, gx)) = loss(L.moe_apply)(p, x)
        want, want_aux, n_dropped = jax.jit(lambda p, x: reference_moe(p, x, cfg))(p, x)
        (lr, (rp, rx)) = loss(reference_moe)(p, x)
    assert int(n_dropped) > 0                 # capacity binds
    if n_held:
        assert float(aux["moe_dropped"]) == int(n_dropped)
    assert float(aux["aux"]) == pytest.approx(float(want_aux["aux"]), rel=1e-6)
    assert _gap(out, want) < 1e-6
    assert abs(float(lv) - float(lr)) < 1e-5 * abs(float(lr))
    assert _gap(gx, rx) < 1e-6
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert float(jnp.abs(rp[name]).max()) > 0, name
        assert _gap(gp[name], rp[name]) < 1e-6, name


# ------------------------------------------------------------ structure

def _ops(op):
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                yield o
                yield from _ops(o)


def row_scatters(fn, *args) -> list[tuple[int, ...]]:
    """The update shapes of the scatters that move windows of rows (not
    single elements) in ``fn`` lowered for ``args``."""
    module = jax.jit(fn).lower(*args).compiler_ir("stablehlo")
    shapes = []
    for op in _ops(module.operation):
        if op.operation.name != "stablehlo.scatter":
            continue
        dims = str(op.operation.attributes["scatter_dimension_numbers"])
        if "update_window_dims" in dims:
            shapes.append(tuple(ir.RankedTensorType(op.operation.operands[-1].type).shape))
    return shapes


def _deepseek_layer():
    """DeepSeek-V2-Lite's expert layer holding experts 0-7 of its 64, at
    4x4096 tokens."""
    cfg = registry.get("deepseek-v2-lite")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_from=0, n_held=8)), 4 * 4096


def _granite_layer():
    return registry.get("granite-moe-1b-a400m"), 2 * 4096


@pytest.mark.parametrize("layer", [_granite_layer, _deepseek_layer],
                         ids=["granite", "deepseek_held_share"])
def test_gradient_holds_no_row_scatter(layer):
    """The lowered gradient of a full-size layer, every expert held
    (Granite) or a held share (DeepSeek-V2-Lite's 8 of 64), holds no
    scatter of d-wide rows: the forward and the backward gather them. The
    three-gather reference lowers to three such scatter-adds."""
    cfg, t = layer()
    p = jax.eval_shape(lambda: L.init_moe(jax.random.key(0), cfg, jnp.bfloat16))
    x = jax.ShapeDtypeStruct((1, t, cfg.d_model), jnp.bfloat16)

    def f(fn):
        def loss(p, x):
            out, aux = fn(p, x, cfg)[:2]
            return jnp.square(out.astype(jnp.float32)).sum() + aux["aux"]
        return jax.grad(loss, argnums=(0, 1))

    def rows(fn):
        return [s for s in row_scatters(f(fn), p, x) if s[-1] == cfg.d_model]

    assert rows(L.moe_apply) == []
    assert len(rows(reference_moe)) == 3


# ------------------------------------------------------------ expert parallel

EP_MESH = """
import contextlib
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.models import layers as L
from repro.parallel import context as pctx
from repro.parallel.sharding import param_partition_specs

base = registry.get("granite-moe-1b-a400m", smoke=True)
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, n_experts=8, top_k=2, d_expert=48, capacity_factor=1.0))
p = {"moe": L.init_moe(jax.random.key(1), cfg, jnp.float32)}
p["moe"]["router"] = p["moe"]["router"] * 40.0
x = jax.random.normal(jax.random.key(2), (4, 32, cfg.d_model), jnp.float32)
ct = jax.random.normal(jax.random.key(3), x.shape, jnp.float32)

def grads(fn, shape=None, axes=()):
    def f(p, x):
        out, aux = fn(p["moe"], x, cfg)[:2]
        return (out * ct).sum() + aux["aux"]
    mesh = shape and Mesh(np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape), axes)
    pctx.set_mesh(mesh)
    specs = jax.tree.map(lambda s: P(*[a if a in axes else None for a in s]),
                         param_partition_specs(p, "data"), is_leaf=lambda s: isinstance(s, P))
    put = lambda v, s: jax.device_put(v, NamedSharding(mesh, s)) if mesh else v
    with jax.set_mesh(mesh) if mesh else contextlib.nullcontext(), \\
            jax.default_matmul_precision("highest"):
        v, (gp, gx) = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(
            jax.tree.map(put, p, specs), put(x, P("data")))
    return [np.asarray(a, np.float64) for a in
            [v, gx, *[gp["moe"][n] for n in ("router", "w_gate", "w_up", "w_down")]]]

def gaps(a, b):
    return [float(np.linalg.norm(u - v) / np.linalg.norm(v)) for u, v in zip(a, b)]

# one dispatch group, the slots over 4 devices, against the reference
one = gaps(grads(L.moe_apply, (1, 4), ("data", "model")), grads(reference_moe))
# two dispatch groups: the slots over 'model' against every device's whole
two = gaps(grads(L.moe_apply, (2, 4), ("data", "model")), grads(L.moe_apply, (2,), ("data",)))
print("GAPS", one + two)
"""


def test_pair_under_expert_parallel_mesh(subproc):
    """On a (data, model) mesh, the experts' slots sharded over 'model':
    the layer's loss and gradients with one dispatch group are the
    reference's, and with two groups those of the experts on every
    device."""
    ref = inspect.getsource(reference_moe)
    gaps = eval(subproc(EP_MESH.replace("def gaps", ref + "\n\ndef gaps"),
                        devices=8).split("GAPS", 1)[1])
    assert len(gaps) == 12 and max(gaps) < 1e-5, gaps
