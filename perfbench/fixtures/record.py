#!/usr/bin/env python3
"""Record the small trace ``selfcheck.py`` checks the reduction against: a
few steps of a tiny program on every chip of the host (matmuls, a tanh, an
all-gather and a psum over the chips), with a deliberate host sleep between
steps so that the device is idle part of the window.

    python3 perfbench/fixtures/record.py <out dir>     (on the chip)

The .xplane.pb it leaves is copied to perfbench/fixtures/ by hand.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax import shard_map       # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pbench import trace        # noqa: E402


def main():
    out = sys.argv[1]
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))

    def body(x, w):
        h = jnp.tanh(x @ w)
        g = jax.lax.all_gather(h, "data", axis=0, tiled=True)
        s = jax.lax.psum(h.sum(), "data")
        return (g @ w.T).sum() + s

    step = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                             out_specs=P(), check_vma=False))
    x = jax.device_put(jnp.ones((8 * len(devices), 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P()))
    jax.block_until_ready(step(x, w))
    with trace.capture(out):
        for i in range(3):
            with trace.span("perfbench.fixture_step", step=i):
                jax.block_until_ready(step(x, w))
            with trace.span("perfbench.fixture_sleep"):
                time.sleep(0.002)
    path = trace.find_xplane(out)
    print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
