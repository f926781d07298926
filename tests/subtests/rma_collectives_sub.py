"""Multi-device correctness: RMA collectives vs native lax collectives."""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import collectives, dsde, rma

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
failures = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name)
    if not ok:
        failures.append(name)


# ring all-gather (both directions) vs lax.all_gather
x = jax.random.normal(jax.random.PRNGKey(0), (N * 4, 6))
ref = jax.jit(sm(lambda v: jax.lax.all_gather(v, "x"),
                 in_specs=P("x", None), out_specs=P(None, "x", None)))(x)
for bidir in (True, False):
    f = jax.jit(sm(functools.partial(collectives.ring_all_gather, axis="x", bidirectional=bidir),
                   in_specs=P("x", None), out_specs=P(None, "x", None)))
    check(f"ring_all_gather bidir={bidir}", bool(jnp.allclose(f(x), ref)))

# ring reduce-scatter vs psum_scatter
y = jax.random.normal(jax.random.PRNGKey(1), (N * N, 3))
frs = jax.jit(sm(lambda v: collectives.ring_reduce_scatter(v, "x")[None],
                 in_specs=P("x", None), out_specs=P("x", None)))
grs = jax.jit(sm(lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=0, tiled=True),
                 in_specs=P("x", None), out_specs=P("x", None)))
check("ring_reduce_scatter", bool(jnp.allclose(frs(y), grs(y), atol=1e-5)))

# all_reduce (incl. non-divisible sizes) vs psum
for rows in (N, 7):
    z = jax.random.normal(jax.random.PRNGKey(2), (N * rows, 5))
    far = jax.jit(sm(functools.partial(collectives.all_reduce, axis="x"),
                     in_specs=P("x", None), out_specs=P("x", None)))
    gar = jax.jit(sm(lambda v: jax.lax.psum(v, "x"),
                     in_specs=P("x", None), out_specs=P("x", None)))
    check(f"all_reduce rows={rows}", bool(jnp.allclose(far(z), gar(z), atol=1e-4)))

# hierarchical all-reduce on a 2D mesh == flat psum over both axes
mesh2 = jax.make_mesh((2, N // 2), ("pod", "data"))
z = jax.random.normal(jax.random.PRNGKey(3), (N * 2, 4))
fh = jax.jit(shard_map(
    functools.partial(collectives.hierarchical_all_reduce, inner_axis="data", outer_axis="pod"),
    mesh=mesh2, in_specs=P(("pod", "data"), None), out_specs=P(("pod", "data"), None),
    check_vma=False))
gh = jax.jit(shard_map(
    lambda v: jax.lax.psum(v, ("pod", "data")),
    mesh=mesh2, in_specs=P(("pod", "data"), None), out_specs=P(("pod", "data"), None),
    check_vma=False))
check("hierarchical_all_reduce", bool(jnp.allclose(fh(z), gh(z), atol=1e-4)))

# halo exchange: periodic neighbors
h = jnp.arange(N * 4 * 2, dtype=jnp.float32).reshape(N * 4, 2)
fhalo = jax.jit(sm(functools.partial(collectives.halo_exchange_1d, halo=1, axis="x", dim=0),
                   in_specs=P("x", None), out_specs=P("x", None)))
out = np.asarray(fhalo(h)).reshape(N, 6, 2)
hh = np.asarray(h).reshape(N, 4, 2)
ok = all(
    np.allclose(out[r, 0], hh[(r - 1) % N, -1])
    and np.allclose(out[r, 1:5], hh[r])
    and np.allclose(out[r, 5], hh[(r + 1) % N, 0])
    for r in range(N)
)
check("halo_exchange_1d", ok)

# DSDE conservation + correct destinations
k = jax.random.PRNGKey(4)
n_items, cap = 16, 16
data = jax.random.normal(k, (N * n_items, 2))
targets = jax.random.randint(jax.random.fold_in(k, 1), (N * n_items,), 0, N)


def _ex(d, t):
    r = dsde.exchange_accumulate(d, t, "x", cap)
    return r._replace(sent_dropped=r.sent_dropped[None])


res = jax.jit(sm(_ex, in_specs=(P("x", None), P("x")), out_specs=P("x")))(data, targets)
check("dsde conservation", int(res.recv_valid.sum()) == N * n_items and int(res.sent_dropped.sum()) == 0)
# recv counts match a host-side histogram
host_counts = np.zeros((N,), np.int64)
tn = np.asarray(targets)
for t in tn:
    host_counts[t] += 1
per_rank = np.asarray(res.recv_counts).reshape(N, N).sum(axis=1)
check("dsde recv counts", bool(np.array_equal(per_rank, host_counts)))

# message-complexity bound: halo uses exactly 2 puts (O(k), k=2)
with rma.OpCounter() as c:
    jax.eval_shape(lambda v: shard_map(
        functools.partial(collectives.halo_exchange_1d, halo=1, axis="x", dim=0),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None), check_vma=False)(v), h)
check("halo O(k) puts", c.puts == 2)

sys.exit(1 if failures else 0)
