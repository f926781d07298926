"""Paper Fig. 7a: distributed hashtable inserts/second (batch of 16k/rank
in the paper; scaled-down batch here, same protocol)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import hashtable as ht


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    n_keys, cap = 512, 1024
    table, heap = 4096, 4096
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(1 << 30, size=n * n_keys, replace=False).astype(np.int64))
    vals = jnp.asarray(rng.integers(0, 1 << 30, size=n * n_keys).astype(np.int64))

    def insert(vols, k, v):
        vol = jax.tree.map(lambda a: a[0], vols)
        vol, dropped = ht.insert_epoch(vol, k, v, "x", cap)
        return jax.tree.map(lambda a: a[None], vol), dropped[None]

    vols0 = jax.vmap(lambda _: ht.make_volume(table, heap))(jnp.arange(n))
    f = jax.jit(shard_map(insert, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
                          out_specs=(P("x"), P("x")), check_vma=False))
    us = time_fn(f, vols0, keys, vals, iters=10)
    total = n * n_keys
    emit("hashtable_insert_epoch", us,
         f"inserts_per_s={total/(us*1e-6):.0f};ranks={n};batch={n_keys}")


if __name__ == "__main__":
    main()
