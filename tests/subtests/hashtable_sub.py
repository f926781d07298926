"""Multi-device: distributed hashtable insert/lookup vs a python dict."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import hashtable as ht

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))

table_size, heap_size, n_keys, cap = 64, 64, 24, 32
rng = np.random.default_rng(0)
keys = rng.choice(10_000, size=N * n_keys, replace=False).astype(np.int64)
vals = rng.integers(0, 1_000_000, size=N * n_keys).astype(np.int64)


def insert(vols, k, v):
    vol = jax.tree.map(lambda a: a[0], vols)
    vol, dropped = ht.insert_epoch(vol, k, v, "x", cap)
    return jax.tree.map(lambda a: a[None], vol), dropped[None]


vols0 = jax.vmap(lambda _: ht.make_volume(table_size, heap_size))(jnp.arange(N))
f = jax.jit(shard_map(insert, mesh=mesh,
                      in_specs=(P("x"), P("x"), P("x")),
                      out_specs=(P("x"), P("x")), check_vma=False))
vols, dropped = f(vols0, jnp.asarray(keys), jnp.asarray(vals))
assert int(dropped.sum()) == 0, "capacity drops"

def lookup(vols, k):
    vol = jax.tree.map(lambda a: a[0], vols)
    v, found = ht.lookup_epoch(vol, k, "x", cap)
    return v[None], found[None]

g = jax.jit(shard_map(lookup, mesh=mesh, in_specs=(P("x"), P("x")),
                      out_specs=(P("x"), P("x")), check_vma=False))
# query: all inserted keys (should hit) + missing keys (should miss)
qk = np.concatenate([keys, keys + 20_000]).astype(np.int64)
pad = (-len(qk)) % N
qk = np.concatenate([qk, np.full(pad, 10**9, np.int64)])
v_out, f_out = g(vols, jnp.asarray(qk))
v_out, f_out = np.asarray(v_out).reshape(-1), np.asarray(f_out).reshape(-1)
truth = dict(zip(keys.tolist(), vals.tolist()))
bad = 0
for i, k in enumerate(qk.tolist()):
    if k in truth:
        bad += not (f_out[i] and v_out[i] == truth[k])
    elif k < 10**9:
        bad += bool(f_out[i])
print(f"hashtable: {bad} mismatches over {len(qk)} queries")
assert bad == 0
print("PASS hashtable")
