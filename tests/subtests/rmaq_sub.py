"""Multi-device rmaq: MPSC queue semantics on the XLA path, Pallas kernel
equivalence in interpret mode, notification-count bounds, channel lanes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.rma import OpCounter
from repro.kernels.rmaq import ops as kops, ref as kref
from repro.rmaq import channel as rch, queue as rq

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
specs = rq.state_specs("x")


# ---------------------------------------------------------------- XLA queue
desc, state0 = rq.queue_allocate(mesh, "x", capacity=16, item_shape=(2,))


def step(state, msgs, dest, max_n, d):
    st = rq.to_local(state)
    st, receipt = rq.enqueue(d, st, msgs[0], dest[0])
    st, items, valid = rq.dequeue(d, st, max_n)
    return (rq.to_global(st), items[None], valid[None],
            receipt.accepted[None], receipt.notifications[None])


f = jax.jit(sm(functools.partial(step, max_n=8, d=desc),
               in_specs=(specs, P("x", None, None), P("x", None)),
               out_specs=(specs, P("x", None, None), P("x", None),
                          P("x", None), P("x"))))

# every rank sends (src, serial) pairs to (r+1) x2 and (r+2) x1
k = 3
msgs = np.zeros((N, k, 2), np.float32)
dest = np.zeros((N, k), np.int32)
for r in range(N):
    dest[r] = [(r + 1) % N, (r + 1) % N, (r + 2) % N]
    for j in range(k):
        msgs[r, j] = [r, j]

with OpCounter() as ctr:
    state, items, valid, acc, notif = f(state0, jnp.asarray(msgs), jnp.asarray(dest))
items, valid, notif = np.asarray(items), np.asarray(valid), np.asarray(notif)

for r in range(N):
    got = [tuple(items[r, i]) for i in range(8) if valid[r, i]]
    want = {((r - 1) % N, 0.0), ((r - 1) % N, 1.0), ((r - 2) % N, 2.0)}
    assert set(got) == want, (r, got, want)                    # exactly once
    assert got.index(((r - 1) % N, 0.0)) < got.index(((r - 1) % N, 1.0))  # FIFO
assert (notif == 3).all(), notif                   # notifications == arrivals
print("PASS xla queue FIFO/exactly-once")

# notification counts match the perf-model's accounting: one counter read +
# one fetch-and-add + one put epoch + one notify accumulate per enqueue call
assert ctr.by_axis["x"]["gets"] == 1 and ctr.by_axis["x"]["accs"] == 2
assert ctr.by_axis["x"]["puts"] == 1
print("PASS op-count bound (1 get, 2 accs, 1 put epoch per enqueue)")

# ------------------------------------------------- backpressure + wraparound
desc2, st2 = rq.queue_allocate(mesh, "x", capacity=8, item_shape=())
f2 = jax.jit(sm(functools.partial(step, max_n=4, d=desc2),
                in_specs=(specs, P("x", None), P("x", None)),
                out_specs=(specs, P("x", None), P("x", None),
                           P("x", None), P("x"))))
recv = {r: [] for r in range(N)}
dropped = 0
serial = 0
for rnd in range(16):
    m = np.zeros((N, 6), np.float32)
    d = np.full((N, 6), -1, np.int32)
    for r in range(N):
        for j in range(6):
            m[r, j] = r * 10_000 + serial + j
            d[r, j] = (r + 1) % N                      # flood the right neighbor
    serial += 6
    st2, it2, va2, ac2, _ = f2(st2, jnp.asarray(m), jnp.asarray(d))
    it2, va2, ac2 = np.asarray(it2), np.asarray(va2), np.asarray(ac2)
    dropped += int((~ac2).sum())
    for r in range(N):
        recv[r] += [float(it2[r, i]) for i in range(4) if va2[r, i]]
assert dropped > 0, "flooding 6/round vs draining 4 must backpressure"
for r in range(N):
    assert recv[r] == sorted(recv[r]), r               # strict FIFO (1 producer)
    assert len(set(recv[r])) == len(recv[r])           # exactly once
    assert len(recv[r]) > 16                           # wrapped the 8-slot ring
print(f"PASS backpressure+wraparound (dropped={dropped}, "
      f"delivered={len(recv[0])}/rank over capacity-8 ring)")

# -------------------------------------------- Pallas vs XLA path equivalence
x = jnp.arange(N * 8 * 128, dtype=jnp.float32).reshape(N * 8, 128)
cnt = jnp.asarray(np.arange(N) + 1, jnp.int32)
y_k, c_k = kops.notified_put(x, cnt, 1, mesh, "x")
y_r, c_r = jax.jit(sm(functools.partial(kref.notified_put_ref, shift=1, axis="x"),
                      in_specs=(P("x", None), P("x")),
                      out_specs=(P("x", None), P("x"))))(x, cnt)
assert jnp.allclose(y_k, y_r) and jnp.array_equal(c_k, c_r)
print("PASS pallas notified_put == xla ref")

local = jnp.zeros((N,), jnp.int32)
a_k = kops.notify_accumulate(cnt, local, 1, mesh, "x")
a_r = jax.jit(sm(functools.partial(kref.notify_accumulate_ref, shift=1, axis="x"),
                 in_specs=(P("x"), P("x")), out_specs=P("x")))(cnt, local)
assert jnp.array_equal(a_k, a_r)
print("PASS pallas notify_accumulate == xla ref")

cap, w, kk = 8, 4, 5
buf = jnp.zeros((N, cap, w), jnp.float32)
ctr0 = jnp.zeros((N, 2), jnp.int32)
pmsgs = jnp.arange(N * kk * w, dtype=jnp.float32).reshape(N, kk, w)


def refbody(b, c, m):
    ob, oc, s, nn = kref.queue_push_ref(b[0], c[0], m[0], 1, "x", cap)
    return ob[None], oc[None], s, nn


frq = jax.jit(sm(refbody,
                 in_specs=(P("x", None, None), P("x", None), P("x", None, None)),
                 out_specs=(P("x", None, None), P("x", None), P("x"), P("x"))))
bk, ck, sk, nk = kops.queue_push(buf, ctr0, pmsgs, 1, mesh, "x")
br, cr, sr, nr = frq(buf, ctr0, pmsgs)
assert jnp.allclose(bk, br) and jnp.array_equal(ck, cr)
assert jnp.array_equal(sk, sr) and jnp.array_equal(nk, nr)
# second round hits backpressure (3 free slots): kernel and ref agree
bk2, ck2, sk2, nk2 = kops.queue_push(bk, ck, pmsgs, 1, mesh, "x")
br2, cr2, sr2, nr2 = frq(br, cr, pmsgs)
assert jnp.allclose(bk2, br2) and jnp.array_equal(ck2, cr2)
assert jnp.array_equal(sk2, sr2) and int(np.asarray(sk2)[0]) == 3
print("PASS pallas queue_push == xla ref (incl. backpressure)")

# --------------------------------------------------------- channel multiplex
ch, chstate = rch.channel_allocate(
    mesh, "x", 16,
    lanes=[rch.Lane("grad", (4,), jnp.float32), rch.Lane("ctrl", (2,), jnp.int32)],
)


def chstep(state, gpay, cpay, gdst, cdst):
    st = rq.to_local(state)
    st, _ = ch.send(st, "grad", gpay[0], jnp.arange(2, dtype=jnp.int32), gdst[0])
    st, _ = ch.send(st, "ctrl", cpay[0], jnp.arange(2, dtype=jnp.int32) + 10, cdst[0])
    st, batch = ch.recv(st, 8)
    g, gm = ch.payload(batch, "grad")
    c, cm = ch.payload(batch, "ctrl")
    return (rq.to_global(st), g[None], gm[None], c[None], cm[None],
            batch.src[None], batch.lane_id[None])


fch = jax.jit(sm(chstep,
                 in_specs=(specs, P("x", None, None), P("x", None, None),
                           P("x", None), P("x", None)),
                 out_specs=(specs, P("x", None, None), P("x", None),
                           P("x", None, None), P("x", None),
                           P("x", None), P("x", None))))
gpay = np.arange(N * 2 * 4, dtype=np.float32).reshape(N, 2, 4)
cpay = np.arange(N * 2 * 2, dtype=np.int32).reshape(N, 2, 2)
gdst = np.stack([np.full(2, (r + 1) % N) for r in range(N)]).astype(np.int32)
cdst = np.stack([np.full(2, (r + 1) % N) for r in range(N)]).astype(np.int32)
_, g, gm, c, cm, src, lid = fch(chstate, jnp.asarray(gpay), jnp.asarray(cpay),
                                jnp.asarray(gdst), jnp.asarray(cdst))
g, gm, c, cm, src = (np.asarray(v) for v in (g, gm, c, cm, src))
for r in range(N):
    left = (r - 1) % N
    assert gm[r].sum() == 2 and cm[r].sum() == 2       # both lanes demuxed
    np.testing.assert_allclose(g[r][gm[r]], gpay[left])  # typed f32 roundtrip
    np.testing.assert_array_equal(c[r][cm[r]], cpay[left])  # exact i32 roundtrip
    assert set(src[r][src[r] >= 0]) == {left}
print("PASS channel lanes multiplexed over one ring")
