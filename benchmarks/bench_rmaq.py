"""rmaq queue benchmarks (DESIGN.md §6.8, §9): message throughput +
notified-put latency vs the dense alltoall dispatch, with the §6.5 model's
predictions — plus the flow-control backpressure scenario (reject/retry vs
credit-based enqueue on a flooded ring).

Columns: name,us_per_call,derived — derived carries msgs/s and the model's
predicted dispatch choice so the CSV documents the crossover.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from jax import shard_map
from repro.core import dsde
from repro.core.perfmodel import DEFAULT_MODEL
from repro.rmaq import channel as rch, flow, notify, queue as rq


def backpressure_scenario(n_steps: int = 16, cap: int = 4, k: int = 2,
                          drain: int = 1) -> dict:
    """Flood one consumer past its ring capacity under both backpressure
    schemes; returns per-scheme counters + timings (the §9 evidence).

    Rank 1 wants `k` messages/step into rank 0's `cap`-slot ring while rank
    0 drains only `drain`/step, so the ring runs full.  The reject/retry
    scheme wires every attempt and replays the rejected ones (>=1 retry per
    full-ring step); the credit scheme stages only what its local credit
    cache covers, so nothing is ever rejected or replayed — at the same 2
    fused wire transfers per append epoch.
    """
    from repro.core.rma import OpCounter

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
    lanes = [rch.Lane("m", (4,), jnp.float32)]
    qspecs = rq.state_specs("x")
    out: dict = {}

    def run(scheme: str) -> dict:
        if scheme == "credit":
            ch, qs0, fs0 = flow.flow_allocate(mesh, "x", cap, lanes,
                                              n_producers=2)
            fspecs = flow.state_specs("x")

            def step(qs, fs, payload, tagv, dest):
                qs, fs = rq.to_local(qs), flow.to_local(fs)
                qs, fs, r = flow.send(ch, qs, fs, "m", payload[0], tagv[0],
                                      dest[0])
                qs, fs, batch = flow.recv(ch, qs, fs, drain)
                return (rq.to_global(qs), flow.to_global(fs),
                        r.accepted[None], r.rejected[None], batch.valid[None])

            f = jax.jit(sm(step,
                           in_specs=(qspecs, fspecs, P("x", None, None),
                                     P("x", None), P("x", None)),
                           out_specs=(qspecs, fspecs, P("x", None),
                                      P("x", None), P("x", None))))
            state = (qs0, fs0)
        else:
            ch, qs0 = rch.channel_allocate(mesh, "x", cap, lanes)

            def step(qs, payload, tagv, dest):
                qs = rq.to_local(qs)
                qs, receipt = ch.send(qs, "m", payload[0], tagv[0], dest[0])
                qs, batch = ch.recv(qs, drain)
                return (rq.to_global(qs), receipt.accepted[None],
                        jnp.zeros((1,), jnp.int32), batch.valid[None])

            f = jax.jit(sm(step,
                           in_specs=(qspecs, P("x", None, None),
                                     P("x", None), P("x", None)),
                           out_specs=(qspecs, P("x", None), P("x", None),
                                      P("x", None))))
            state = (qs0,)

        payload = np.zeros((n, k, 4), np.float32)
        tagv = np.zeros((n, k), np.int32)
        dest0 = np.full((n, k), -1, np.int32)
        with OpCounter() as c:
            f.lower(*state, jnp.asarray(payload), jnp.asarray(tagv),
                    jnp.asarray(dest0))
        plan_ledger = [dict(p) for p in c.plans]

        backlog = list(range(10 * n_steps))
        stats = dict(steps=n_steps, sent_attempts=0, retries=0, rejects=0,
                     full_ring_steps=0, delivered=0, credit_stalls=0,
                     wire_transfers_per_append=c.coalesced_msgs,
                     raw_msgs_per_append=c.raw_msgs)
        us = None
        for s in range(n_steps):
            # stage from the backlog (credit mode: only what the producer's
            # device-held cache covers — mirrors DisaggEngine's scheduler)
            if scheme == "credit":
                fs_host = state[1]
                credit = (np.asarray(fs_host.limit).astype(np.int64)
                          - np.asarray(fs_host.sent).astype(np.int64))
                n_stage = min(k, len(backlog), max(int(credit[1, 0, 0]), 0))
                stats["credit_stalls"] += int(
                    min(k, len(backlog)) - n_stage > 0)
            else:
                n_stage = min(k, len(backlog))
            stage = backlog[:n_stage]
            del backlog[:n_stage]
            payload = np.zeros((n, k, 4), np.float32)
            payload[1, :n_stage, 0] = stage
            dest = np.full((n, k), -1, np.int32)
            dest[1, :n_stage] = 0
            res = f(*state, jnp.asarray(payload), jnp.asarray(tagv),
                    jnp.asarray(dest))
            if scheme == "credit":
                state, acc, rej, valid = res[:2], res[2], res[3], res[4]
                assert int(np.asarray(rej).sum()) == 0, "credited send rejected"
            else:
                state, acc, _, valid = (res[0],), res[1], res[2], res[3]
            acc = np.asarray(acc)[1, :n_stage]
            rejected = [m for m, a in zip(stage, acc) if not a]
            stats["sent_attempts"] += n_stage
            stats["rejects"] += len(rejected)
            stats["retries"] += len(rejected)    # each will be re-wired
            stats["full_ring_steps"] += int(len(rejected) > 0)
            stats["delivered"] += int(np.asarray(valid)[0].sum())
            backlog[:0] = rejected               # FIFO replay
        us = time_fn(lambda *a: f(*a)[-1], *state, jnp.asarray(payload),
                     jnp.asarray(tagv), jnp.asarray(dest0))
        stats["us_per_step"] = us
        stats["plan_ledger"] = plan_ledger
        return stats

    out["retry"] = run("retry")
    out["credit"] = run("credit")
    return out


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
    specs = rq.state_specs("x")

    # ---- queue enqueue+dequeue round: k msgs/rank, small payloads --------
    k, width, cap = 8, 16, 64
    desc, state = rq.queue_allocate(mesh, "x", cap, (width,))
    key = jax.random.PRNGKey(0)
    msgs = jax.random.normal(key, (n, k, width))
    dest = jax.random.randint(jax.random.fold_in(key, 1), (n, k), 0, n)

    def q_round(state, m, d):
        st = rq.to_local(state)
        st, _ = rq.enqueue(desc, st, m[0], d[0])
        st, items, valid = rq.dequeue(desc, st, k * n)
        return rq.to_global(st), items[None], valid[None]

    fq = jax.jit(sm(q_round, in_specs=(specs, P("x", None, None), P("x", None)),
                    out_specs=(specs, P("x", None, None), P("x", None))))
    us = time_fn(lambda s: fq(s, msgs, dest)[1], state)
    rate = n * k / (us * 1e-6)
    emit("rmaq_enqueue_dequeue", us, f"k={k};msgs_per_s={rate:.0f}")

    # ---- notified put vs plain put (the notification premium) ------------
    x = jax.random.normal(key, (n * 8, 128))
    cnt = jnp.zeros((n,), jnp.uint32)

    def nput(x, c):
        out, c2 = notify.notified_put_shift(x, c, 1, "x")
        return out, c2

    fn = jax.jit(sm(nput, in_specs=(P("x", None), P("x")),
                    out_specs=(P("x", None), P("x"))))
    us_n = time_fn(lambda a: fn(a, cnt)[0], x)
    from repro.core import rma

    fp = jax.jit(sm(lambda a: rma.put_shift(a, 1, "x"),
                    in_specs=P("x", None), out_specs=P("x", None)))
    us_p = time_fn(fp, x)
    pred = DEFAULT_MODEL.p_notified_put(x.nbytes / n) * 1e6
    emit("rmaq_notified_put", us_n, f"plain_put_us={us_p:.2f};model_us={pred:.2f}")

    # ---- sparse DSDE: queue protocol vs dense alltoall protocol ----------
    items, cap_pair = 2, 8          # sparse: 2 items/rank, capacity 8/pair
    data = jax.random.normal(key, (n * items, 4))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (n * items,), 0, n)
    results = {}
    for name, proto in [("rmaq_dsde_queue", dsde.exchange_queue),
                        ("rmaq_dsde_alltoall", dsde.exchange_alltoall_baseline)]:
        def body(d, t, proto=proto):
            r = proto(d, t, "x", cap_pair)
            return r.recv_data, r.recv_valid
        f = jax.jit(sm(body, in_specs=(P("x", None), P("x")),
                       out_specs=(P("x", None), P("x"))))
        results[name] = time_fn(f, data, targets)
    choice = DEFAULT_MODEL.select_dispatch(items, 4 * 4.0, n, cap_pair)
    for name, us in results.items():
        emit(name, us, f"model_choice={choice}")

    # ---- backpressure: reject/retry vs credit flow control (§9) ----------
    bp = backpressure_scenario()
    for scheme in ("retry", "credit"):
        s = bp[scheme]
        emit(f"rmaq_backpressure_{scheme}", s["us_per_step"],
             f"retries={s['retries']};full_ring_steps={s['full_ring_steps']};"
             f"credit_stalls={s['credit_stalls']};"
             f"wire_per_append={s['wire_transfers_per_append']}")


if __name__ == "__main__":
    main()
