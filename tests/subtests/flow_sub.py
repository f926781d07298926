"""Multi-device credit-based flow control (DESIGN.md §9): exhaustion →
refresh → recovery round trip, conservation under multi-producer load, the
2-fused-transfer wire cost of a credited append, zero ring rejections, and
runtime (credit-aware) lane selection over a homogeneous lane table."""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.rma import OpCounter
from repro.rmaq import flow, queue as rq
from repro.rmaq.channel import Lane

N = len(jax.devices())
mesh = jax.make_mesh((N,), ("x",))
sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
failures = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name)
    if not ok:
        failures.append(name)


K = 3
N_PROD = max(N // 2, 1)
CAP, L = 16, 2
ch, qstate0, fstate0 = flow.flow_allocate(
    mesh, "x", CAP, [Lane("a", (2,)), Lane("b", (2,))], n_producers=N_PROD)
qspecs, fspecs = rq.state_specs("x"), flow.state_specs("x")
SHARE = CAP // (N_PROD * L)          # initial credits per (producer, lane)

specs_in = (qspecs, fspecs, P("x", None, None), P("x", None), P("x", None),
            P("x", None))
specs_out = (qspecs, fspecs, (P("x", None), P("x", None), P("x"), P("x")),
             P("x", None))


def mk_step(drain):
    def step(qs, fs, payload, tag, dest, lane):
        qs, fs = rq.to_local(qs), flow.to_local(fs)
        qs, fs, r = flow.send(ch, qs, fs, "a", payload[0], tag[0], dest[0],
                              lane[0])
        out = (r.accepted[None], r.deferred[None], r.refreshed[None],
               r.rejected[None])
        if drain:
            qs, fs, batch = flow.recv(ch, qs, fs, CAP)
            m = batch.valid[None]
        else:
            m = jnp.zeros((1, CAP), jnp.bool_)
        return rq.to_global(qs), flow.to_global(fs), out, m
    return jax.jit(sm(step, in_specs=specs_in, out_specs=specs_out))


f_send = mk_step(drain=False)
f_round = mk_step(drain=True)

payload = jnp.arange(N * K * 2, dtype=jnp.float32).reshape(N, K, 2)
tag = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None], (N, 1))
# every producer floods one target on lane 0 (K > SHARE forces exhaustion)
tgt = N_PROD if N > N_PROD else 0
dest = np.full((N, K), -1, np.int32)
dest[:N_PROD, :] = tgt
lane = np.zeros((N, K), np.int32)

# ---- 1. credited append: 2 fused wire transfers, deferral not rejection
with OpCounter() as c:
    qs, fs, out, _ = f_send(qstate0, fstate0, payload, tag,
                            jnp.asarray(dest), jnp.asarray(lane))
acc, dfr, rfr, rej = (np.asarray(o) for o in out)
check("flow append = 2 wire transfers", c.coalesced_msgs == 2)
check("refresh rides reserve gather (gets=1 accs=2 puts=1)",
      c.by_axis["x"] == {"gets": 1, "accs": 2, "puts": 1})
check("cache covers exactly the initial share",
      (acc[:N_PROD].sum(axis=1) == min(SHARE, K)).all())
check("overflow deferred at origin, nothing rejected",
      (dfr[:N_PROD].sum(axis=1) == K - min(SHARE, K)).all()
      and rej.sum() == 0)
check("dry cache flagged refreshed", bool(rfr[:N_PROD].all()))

cons = flow.conservation(ch, qs, fs)
check("conservation after exhaustion",
      (cons["granted_minus_head"] == CAP).all()
      and (cons["outstanding_plus_occupancy"] == CAP).all())

# ---- 2. recovery round trip: drain grants credits; the refresh (riding the
# next epoch's reserve gather) restores the cache one epoch later
qs, fs, out, valid = f_round(qs, fs, payload, tag, jnp.asarray(dest),
                             jnp.asarray(lane))
drained1 = int(np.asarray(valid).sum())
check("drain delivers the credited sends", drained1 == N_PROD * min(SHARE, K))
qs, fs, out, valid = f_round(qs, fs, payload, tag, jnp.asarray(dest),
                             jnp.asarray(lane))   # refresh lands after this
qs, fs, out, valid = f_round(qs, fs, payload, tag, jnp.asarray(dest),
                             jnp.asarray(lane))
acc3 = np.asarray(out[0])
check("recovery: sends re-admitted after refresh",
      acc3[:N_PROD].sum() > 0 and np.asarray(out[3]).sum() == 0)
cons = flow.conservation(ch, qs, fs)
check("conservation after recovery",
      (cons["granted_minus_head"] == CAP).all()
      and (cons["outstanding_plus_occupancy"] == CAP).all())

# ---- 3. multi-producer random traffic: conservation at every epoch
rng = np.random.RandomState(0)
qs, fs = qstate0, fstate0
for it in range(6):
    d = np.full((N, K), -1, np.int32)
    ln = np.zeros((N, K), np.int32)
    for r in range(N_PROD):
        d[r] = rng.randint(0, N, size=K)
        ln[r] = rng.randint(0, L, size=K)
    qs, fs, out, _ = f_round(qs, fs, payload, tag, jnp.asarray(d),
                             jnp.asarray(ln))
    if int(np.asarray(out[3]).sum()):
        check(f"no rejection under load (epoch {it})", False)
        break
cons = flow.conservation(ch, qs, fs)
check("conservation under multi-producer load",
      (cons["granted_minus_head"] == CAP).all()
      and (cons["outstanding_plus_occupancy"] == CAP).all())

# ---- 4. runtime lane selection: per-message lanes demux + debit correctly
qs, fs = qstate0, fstate0
d = np.full((N, K), -1, np.int32)
ln = np.zeros((N, K), np.int32)
d[0] = tgt
ln[0] = [0, 1, 1]                    # one message lane a, two lane b
qs, fs, out, _ = f_send(qs, fs, payload, tag, jnp.asarray(d), jnp.asarray(ln))
check("runtime lanes all credited", np.asarray(out[0])[0].sum() == 3)
spent = np.asarray(fs.sent)[0, tgt]  # producer 0's debits at the target
check("per-lane debit follows the lane array", spent.tolist() == [1, 2])


def drain_demux(qs):
    def body(q):
        q = rq.to_local(q)
        q, batch = ch.recv(q, CAP)
        _, mask_a = ch.payload(batch, "a")
        _, mask_b = ch.payload(batch, "b")
        return rq.to_global(q), mask_a[None], mask_b[None]
    f = jax.jit(sm(body, in_specs=(qspecs,),
                   out_specs=(qspecs, P("x", None), P("x", None))))
    return f(qs)


qs, mask_a, mask_b = drain_demux(qs)
check("lane demux at the consumer",
      int(np.asarray(mask_a)[tgt].sum()) == 1
      and int(np.asarray(mask_b)[tgt].sum()) == 2)

sys.exit(1 if failures else 0)
