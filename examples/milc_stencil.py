"""MILC-style 4D lattice stencil with one-sided halo exchange (paper §4.4).

Demonstrates: PSCW epochs around the halo puts, the §3 model-guided choice
of sync mode (k=2 => PSCW), and agreement with a single-device stencil.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/milc_stencil.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.core.epoch import PSCWEpoch, choose_sync


def main() -> None:
    n = len(jax.devices())
    if n < 2:
        print("run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    mesh = jax.make_mesh((n,), ("t",))
    T, X, Y, Z, C = 4 * n, 4, 4, 4, 6
    lat = jax.random.normal(jax.random.PRNGKey(0), (T, X, Y, Z, C))

    print(f"sync mode for k=2 neighbors at p={n}: {choose_sync(2, n)} (paper §6 rule)")

    def step(v):
        ep = PSCWEpoch("t", group=[0, 1])        # 2 neighbors on the T ring
        v = ep.post(v)
        padded = collectives.halo_exchange_1d(v, 1, "t", dim=0)
        v2 = ep.complete(v)
        acc = padded[2:] + padded[:-2]
        for d in (1, 2, 3):
            acc = acc + jnp.roll(v2, 1, axis=d) + jnp.roll(v2, -1, axis=d)
        return acc - 8.0 * v2

    f = jax.jit(shard_map(step, mesh=mesh, in_specs=P("t", None, None, None, None),
                          out_specs=P("t", None, None, None, None), check_vma=False))
    got = np.asarray(f(lat))

    v = np.asarray(lat)
    want = np.roll(v, 1, 0) + np.roll(v, -1, 0)
    for d in (1, 2, 3):
        want = want + np.roll(v, 1, d) + np.roll(v, -1, d)
    want = want - 8.0 * v
    err = np.max(np.abs(got - want))
    print(f"distributed vs single-device stencil max err: {err:.2e} "
          f"({'OK' if err < 1e-5 else 'FAIL'})")


if __name__ == "__main__":
    main()
