"""Records the small trace that ``test_trace.py`` reads, on a v5e:2x2.

    python3 chipbench/tests/record_trace.py chipbench/tests/data/dp4_small.xplane.pb

Four devices each multiply a [1024, 1024] bf16 block, then sum the products
over the devices and pass them round the ring: compute, then two
collectives. Three steps, each behind a ``feed.batch`` span as the
benchmark's feed makes them, after one lead-in step.
"""

import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out: str) -> None:
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("d",))

    def body(x):
        y = x @ x.T
        y = jax.lax.psum(y, "d")
        return jax.lax.ppermute(y, "d", [(i, (i + 1) % 4) for i in range(4)])

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P("d")))
    sharding = NamedSharding(mesh, P("d"))
    make = lambda i: jax.device_put(
        jnp.full((4 * 1024, 1024), 0.001 * i, jnp.bfloat16), sharding)
    f(make(0)).block_until_ready()
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for i in range(4):
        with jax.profiler.TraceAnnotation("feed.batch"):
            x = make(i)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(log_dir)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
