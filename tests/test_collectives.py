"""Device-level all-reduce zoo == psum, on 8 simulated devices (subprocess)."""

import pytest

CODE_ALGOS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.core import collectives as C

mesh = jax.make_mesh((8,), ('ax',), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
# 129: odd length, the pad paths; 1024: 128-element chunks, the lane layout
for n in (129, 1024):
    x = jnp.asarray(rng.normal(size=(8, n)).astype(np.float32))
    want = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
    with jax.set_mesh(mesh):
        for alg, kw in [('psum', {}), ('ring', {}), ('rd', {}), ('bt', {}),
                        ('wrht', {'m': 3}), ('wrht', {'m': 3, 'alltoall_max': 4}),
                        ('wrht', {'m': 5, 'alltoall_max': 2}), ('wrht', {'m': 8}),
                        ('wrht', {'m': 2, 'alltoall_max': None})]:
            f = jax.jit(C.make_sharded_allreduce(mesh, 'ax', alg, **kw))
            got = np.asarray(f(x))
            err = np.abs(got - want).max()
            assert err < 1e-4, (alg, kw, n, err)
print('ALGOS_OK')
"""

CODE_HIER = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import collectives as C

mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'), axis_types=(AxisType.Auto,)*3)
rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(size=(4, 37)).astype(np.float32))  # pod*data rows

for mode in ('faithful', 'scatter', 'flat'):
    def body(stacked):
        local = stacked[0]
        out = C.hierarchical_allreduce(local, ('data', 'pod'), (2, 2), mode=mode)
        return out[None]
    f = jax.shard_map(body, mesh=mesh, in_specs=P(('pod', 'data')),
                      out_specs=P(('pod', 'data')), axis_names={'pod', 'data'})
    with jax.set_mesh(mesh):
        got = np.asarray(jax.jit(f)(x))
    want = np.tile(np.asarray(x).sum(0, keepdims=True), (4, 1))
    assert np.abs(got - want).max() < 1e-4, mode
print('HIER_OK')
"""

CODE_COMPRESS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import compression as comp

mesh = jax.make_mesh((8,), ('ax',), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(2)
x = jnp.asarray(rng.normal(size=(8, 257)).astype(np.float32))

def body(stacked):
    return comp.compressed_allreduce_rd(stacked[0], 'ax', 8)[None]
f = jax.shard_map(body, mesh=mesh, in_specs=P('ax'), out_specs=P('ax'), axis_names={'ax'})
with jax.set_mesh(mesh):
    got = np.asarray(jax.jit(f)(x))
want = np.asarray(x).sum(0, keepdims=True)
rel = np.abs(got - want).max() / np.abs(want).max()
assert rel < 0.05, rel  # int8 quantization error over log2(8)=3 hops
print('COMPRESS_OK', rel)
"""


def test_all_algorithms_match_psum(subproc):
    assert "ALGOS_OK" in subproc(CODE_ALGOS)


def test_hierarchical_allreduce_modes(subproc):
    assert "HIER_OK" in subproc(CODE_HIER)


def test_compressed_allreduce_error_bounded(subproc):
    assert "COMPRESS_OK" in subproc(CODE_COMPRESS)
