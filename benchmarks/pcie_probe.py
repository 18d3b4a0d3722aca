"""Measured host<->device bandwidth on THIS chip environment — the bus-rate
bound in the 7B offload accounting (docs/performance.md).

Measurement rules: inputs vary per iteration and completion is forced by a
scalar fetch that depends on the transferred data.  Each timed iteration
performs exactly ONE counted transfer; the input variation happens on the
source side before the clock starts for that leg.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("d",))
    host = NamedSharding(mesh, P(), memory_kind="pinned_host")
    dev = NamedSharding(mesh, P(), memory_kind="device")
    n = 512 * 1024 * 1024  # 1 GiB of bf16
    iters = 6
    out = {}

    for name, src_sh, dst_sh in (("h2d", host, dev), ("d2h", dev, host)):
        # pre-build `iters` DISTINCT source arrays on the source side so the
        # timed loop contains only the measured move
        sources = [
            jax.device_put(jnp.full((n,), jnp.bfloat16(i + 1)), src_sh)
            for i in range(iters)
        ]

        @jax.jit
        def move(v):
            moved = jax.device_put(v, dst_sh)
            return moved, moved[0]  # scalar rides along for the sync fetch

        move(sources[0])  # compile + warm
        t0 = time.perf_counter()
        for i in range(iters):
            moved, probe = move(sources[i])
            float(probe)  # scalar fetch: the transfer has completed
        dt = time.perf_counter() - t0
        out[name + "_gib_s"] = round((2 * n / 2**30) * iters / dt, 2)
    print(json.dumps({"metric": "pcie_bandwidth", "unit": "GiB/s", **out}))


if __name__ == "__main__":
    main()
