"""Coverage for the remaining one-sided API surface: put_perm, get_index,
get_gather, broadcast, all_to_all, fetch_and_op, epoch statistics."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import rma
from repro.core.epoch import FenceEpoch, PSCWEpoch, SharedLockEpoch, flush

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
x = jnp.arange(N * 3, dtype=jnp.float32).reshape(N, 3)

# put_perm: reverse permutation
perm = [(i, N - 1 - i) for i in range(N)]
f = jax.jit(sm(lambda v: rma.put_perm(v, perm, "x"), in_specs=P("x", None), out_specs=P("x", None)))
got = np.asarray(f(x))
want = np.asarray(x)[::-1]
assert np.allclose(got, want), (got, want)
print("PASS put_perm")

# get_index / broadcast: everyone reads rank 2's shard
g = jax.jit(sm(lambda v: rma.get_index(v, 2, "x")[None], in_specs=P("x", None), out_specs=P(None, None)))
assert np.allclose(np.asarray(g(x))[0], np.asarray(x)[2])
print("PASS get_index")

# get_gather: rank r reads from src[r]
src = jnp.asarray([(i + 2) % N for i in range(N)], jnp.int32)
h = jax.jit(sm(lambda v, s: rma.get_gather(v, s, "x")[None],
               in_specs=(P("x", None), P(None)), out_specs=P("x", None)))
got = np.asarray(h(x, src))
for r in range(N):
    assert np.allclose(got[r], np.asarray(x)[(r + 2) % N]), r
print("PASS get_gather")

# fetch_and_op: returns old value, applies op
old, new = rma.fetch_and_op(jnp.asarray(3.0), jnp.asarray(4.0), "x")
assert float(old) == 4.0 and float(new) == 7.0
print("PASS fetch_and_op")

# epoch statistics: fence counts log2 p stages; PSCW counts k msgs
ep = FenceEpoch("x", N)
_ = ep.close(ep.open(x))
assert ep.stats.barrier_stages >= 1
ps = PSCWEpoch("x", group=[0, 1, 2])
_ = ps.complete(ps.start(ps.wait(ps.post(x))))
assert ps.stats.post_msgs == 3 and ps.stats.complete_msgs == 3
assert ps.stats.start_msgs == 0 and ps.stats.wait_msgs == 0  # paper: zero
lk = SharedLockEpoch("x")
with rma.OpCounter() as c:
    _ = lk.unlock(lk.lock(x))
assert c.accs == 2  # one AMO each way
_ = flush(x)
print("PASS epoch stats")

# predicted costs are finite and ordered sensibly
assert ep.predicted_cost() > 0 and ps.predicted_cost() > 0 and lk.predicted_cost() > 0
print("PASS predicted costs")
