"""Multi-device deferred substrate (DESIGN.md §8): coalesced-vs-eager
equivalence, mixed-dtype packing, backend dispatch (XLA vs Pallas
interpret), epoch families at p>1, and the fused rmaq queue append."""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import dsde, rma
from repro.core.plan import AccessEpoch, RmaPlan
from repro.core.rma import OpCounter
from repro.rmaq import queue as rq

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
failures = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name)
    if not ok:
        failures.append(name)


# ---- 1. k same-perm puts: one fused transfer, values == eager
K = 6
x = jax.random.normal(jax.random.PRNGKey(0), (N, K, 3))


def coalesced(v):
    pl = RmaPlan("x")
    hs = [pl.put_shift(v[0, i], 1) for i in range(K)]
    st = pl.flush(aggregate=True)
    assert st.coalesced == 1 and st.raw == K
    return jnp.stack([h.result() for h in hs])[None]


def eager(v):
    return jnp.stack([rma.put_shift(v[0, i], 1, "x") for i in range(K)])[None]


spec = P("x", None, None)
with OpCounter() as c_plan:
    out_c = np.asarray(jax.jit(sm(coalesced, in_specs=spec, out_specs=spec))(x))
with OpCounter() as c_eager:
    out_e = np.asarray(jax.jit(sm(eager, in_specs=spec, out_specs=spec))(x))
check("coalesced == eager values", np.allclose(out_c, out_e))
check("raw=k coalesced=1", c_plan.raw_msgs == K and c_plan.coalesced_msgs == 1
      and c_plan.puts == K)
check("eager raw==wire", c_eager.raw_msgs == K and c_eager.coalesced_msgs == K)

# ---- 2. distinct permutations stay separate wire transfers
def mixed_perms(v):
    pl = RmaPlan("x")
    h_f = pl.put_shift(v[0], +1)
    h_b = pl.put_shift(v[0], -1)
    st = pl.flush(aggregate=True)
    assert st.groups == 2 and st.coalesced == 2
    return jnp.stack([h_f.result(), h_b.result()])[None]


y = jax.random.normal(jax.random.PRNGKey(1), (N, 4))
out = np.asarray(jax.jit(sm(mixed_perms, in_specs=P("x", None),
                            out_specs=P("x", None, None)))(y))
yy = np.asarray(y)
check("distinct perms correct",
      np.allclose(out[:, 0], np.roll(yy, 1, axis=0))
      and np.allclose(out[:, 1], np.roll(yy, -1, axis=0)))

# ---- 3. mixed-dtype fused a2a roundtrips exactly
vf = jax.random.normal(jax.random.PRNGKey(2), (N, N, 2))
vi = jnp.arange(N * N, dtype=jnp.uint32).reshape(N, N)
vb = (jnp.arange(N * N) % 3 == 0).reshape(N, N)
vh = (jnp.arange(N * N, dtype=jnp.bfloat16) * 0.25).reshape(N, N)


def fused_a2a(f, i, b, h2):
    pl = RmaPlan("x")
    hf = pl.put_all_to_all(f[0], kind="puts")
    hi = pl.put_all_to_all(i[0], kind=None)
    hb = pl.put_all_to_all(b[0], kind=None)
    hh = pl.put_all_to_all(h2[0], kind=None)
    st = pl.flush(aggregate=True)
    assert st.coalesced == 1 and st.raw == 4
    return (hf.result()[None], hi.result()[None],
            hb.result()[None], hh.result()[None])


ff = jax.jit(sm(fused_a2a,
                in_specs=(P("x", None, None), P("x", None), P("x", None), P("x", None)),
                out_specs=(P("x", None, None), P("x", None), P("x", None), P("x", None))))
rf, ri, rb, rh = ff(vf, vi, vb, vh)


def ref_a2a(v, s):
    g = jax.jit(sm(lambda z: jax.lax.all_to_all(z[0], "x", 0, 0)[None],
                   in_specs=s, out_specs=s))
    return np.asarray(g(v))


check("fused a2a f32", np.allclose(np.asarray(rf), ref_a2a(vf, P("x", None, None))))
check("fused a2a u32", np.array_equal(np.asarray(ri), ref_a2a(vi, P("x", None))))
check("fused a2a bool", np.array_equal(np.asarray(rb), ref_a2a(vb, P("x", None)))
      and rb.dtype == jnp.bool_)
check("fused a2a bf16",
      np.array_equal(np.asarray(rh).astype(np.float32),
                     ref_a2a(vh, P("x", None)).astype(np.float32))
      and rh.dtype == jnp.bfloat16)

# ---- 4. backend dispatch: forced Pallas interpret == XLA
z = jnp.arange(N * 8 * 128, dtype=jnp.float32).reshape(N * 8, 128)


def via_backend(backend):
    def body(v):
        pl = RmaPlan("x")
        h = pl.put_shift(v, 1)
        pl.flush(backend=backend)
        return h.result()
    return np.asarray(jax.jit(sm(body, in_specs=P("x", None),
                                 out_specs=P("x", None)))(z))


check("pallas interpret == xla backend",
      np.allclose(via_backend("interpret"), via_backend("xla")))

# ---- 5. AccessEpoch families at p>1 (fence + pscw)
for family, kwargs in (("fence", {"p": N}), ("pscw", {"group": list(range(N))})):
    eps = {}

    def ep_body(v, family=family, kwargs=kwargs):
        ep = AccessEpoch("x", family=family, **kwargs)
        t = ep.open(v[0])
        hs = [ep.put_shift(t + i, 1) for i in range(3)]
        ha = ep.accumulate_shift(t, jnp.zeros_like(t), 1)
        t = ep.close(t, aggregate=True)
        eps["ep"] = ep
        return (t + 0 * ha.result())[None], jnp.stack([h.result() for h in hs])[None]

    fep = jax.jit(sm(ep_body, in_specs=P("x", None),
                     out_specs=(P("x", None), P("x", None, None))))
    _, hs_out = fep(y)
    ep = eps["ep"]
    check(f"{family} epoch coalesces (raw=4 wire=1)",
          ep.sync.stats.raw_msgs == 4 and ep.sync.stats.coalesced_msgs == 1)
    check(f"{family} epoch values",
          np.allclose(np.asarray(hs_out)[:, 0], np.roll(np.asarray(y), 1, 0)))

# ---- 6. rmaq queue append: one fused reserve + one fused payload transfer
desc, state0 = rq.queue_allocate(mesh, "x", capacity=16, item_shape=(2,))
specs = rq.state_specs("x")


def qstep(state, msgs, dest):
    st = rq.to_local(state)
    st, receipt = rq.enqueue(desc, st, msgs[0], dest[0])
    return rq.to_global(st), receipt.accepted[None]


fq = jax.jit(sm(qstep, in_specs=(specs, P("x", None, None), P("x", None)),
                out_specs=(specs, P("x", None))))
msgs = jnp.ones((N, 3, 2), jnp.float32)
dest = jnp.tile(jnp.arange(3, dtype=jnp.int32)[None] % N, (N, 1))
with OpCounter() as cq:
    _ = fq(state0, msgs, dest)
check("queue append = 2 wire transfers (was 5 collectives)",
      cq.raw_msgs == 5 and cq.coalesced_msgs == 2)
check("queue append kind attribution",
      cq.by_axis["x"] == {"gets": 1, "accs": 2, "puts": 1})

# ---- 7. dsde exchange: counter + payload + validity coalesce
data = jax.random.normal(jax.random.PRNGKey(3), (N * 4, 2))
targets = jax.random.randint(jax.random.PRNGKey(4), (N * 4,), 0, N)


def dsde_body(d, t):
    r = dsde.exchange_accumulate(d, t, "x", 8)
    return r._replace(sent_dropped=r.sent_dropped[None])


with OpCounter() as cd:
    res = jax.jit(sm(dsde_body, in_specs=(P("x", None), P("x")),
                     out_specs=P("x")))(data, targets)
check("dsde exchange fused (raw=3 wire=1)",
      cd.raw_msgs == 3 and cd.coalesced_msgs == 1)
check("dsde conservation under plan",
      int(np.asarray(res.recv_valid).sum()) == N * 4)

sys.exit(1 if failures else 0)
