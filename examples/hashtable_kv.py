"""Distributed hashtable / KV store on one-sided RMA (paper §4.1).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/hashtable_kv.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import hashtable as ht


def main() -> None:
    n = len(jax.devices())
    if n < 2:
        print("run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    mesh = jax.make_mesh((n,), ("x",))
    n_keys, cap = 64, 128
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.choice(1 << 20, n * n_keys, replace=False).astype(np.int64))
    vals = jnp.asarray(rng.integers(0, 1 << 20, n * n_keys).astype(np.int64))

    def insert(vols, k, v):
        vol = jax.tree.map(lambda a: a[0], vols)
        vol, dropped = ht.insert_epoch(vol, k, v, "x", cap)
        return jax.tree.map(lambda a: a[None], vol), dropped[None]

    def lookup(vols, k):
        vol = jax.tree.map(lambda a: a[0], vols)
        v, found = ht.lookup_epoch(vol, k, "x", cap)
        return v[None], found[None]

    vols = jax.vmap(lambda _: ht.make_volume(512, 512))(jnp.arange(n))
    fi = jax.jit(shard_map(insert, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
                           out_specs=(P("x"), P("x")), check_vma=False))
    fl = jax.jit(shard_map(lookup, mesh=mesh, in_specs=(P("x"), P("x")),
                           out_specs=(P("x"), P("x")), check_vma=False))

    vols, dropped = fi(vols, keys, vals)
    v_out, found = fl(vols, keys)
    v_out = np.asarray(v_out).reshape(-1)
    found = np.asarray(found).reshape(-1)
    truth = dict(zip(np.asarray(keys).tolist(), np.asarray(vals).tolist()))
    hits = sum(1 for i, k in enumerate(np.asarray(keys).tolist())
               if found[i] and v_out[i] == truth[k])
    print(f"inserted {n*n_keys} keys over {n} ranks (dropped={int(dropped.sum())}); "
          f"lookup hits {hits}/{n*n_keys}")
    assert hits == n * n_keys


if __name__ == "__main__":
    main()
