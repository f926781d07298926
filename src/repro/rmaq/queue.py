"""MPSC ring-buffer message queues over RMA windows (DESIGN.md §6.2).

Every window rank owns one fixed-capacity multi-producer/single-consumer
ring buffer living in an *allocated* window (symmetric heap), so the queue
inherits the paper's O(1)-metadata property: one (axis, capacity, item)
tuple describes every rank's ring — `QueueDescriptor.metadata_nbytes()`
asserts it, exactly like `Window.metadata_nbytes()` does for §2.2.

The protocol per enqueue epoch (the ring-buffer write-with-notification
design of Taranov et al., built from the paper's §2.4 ops):

  1. **reserve** — every producer fetch-and-adds its per-target message
     count into each target's `tail` counter.  TPU has no remote AMOs, so
     the fetch-and-add is the *rank-ordered* epoch serialization of
     `notify.fetch_and_add_ordered`: one counter gather, identical on all
     ranks, gives each producer its slot range deterministically (producers
     in rank order, messages in program order — this is what makes dequeue
     FIFO per producer).
  2. **admit** — slots are granted only up to the ring's free space
     (`capacity - (tail - head)`); the remainder is *rejected at the
     origin*, which is the backpressure signal (receipt.accepted), never a
     silent overwrite.
  3. **put + notify** — granted payloads fly to their slot
     (`seq & (capacity-1)`, wraparound by power-of-two mask) as one-sided
     puts in a single epoch, and each target's notification counter is
     accumulated by the same epoch (`notify` column of the counter block).
     Since the deferred substrate (DESIGN.md §8) both protocol rounds are
     recorded into epoch-scoped `RmaPlan`s: the reservation is ONE fused
     counter gather and payload+sequence+notification are ONE fused
     aggregated transfer — a queue append is a single wire message, not
     three collectives.

Dequeue is owner-local: read `[head, min(tail, head+n))`, advance `head`.
No lock anywhere — head is consumer-private, tail moves only through the
epoch-serialized reservation, slot ranges are disjoint by construction.

Counters are uint32; sequence numbers wrap modulo 2**32 which is exact for
power-of-two capacities (hence the capacity check).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import plan as plan_mod
from repro.core import window as window_mod
from repro.obs import causal as obs_causal
from repro.obs import trace as obs_trace

Array = jax.Array

# counter-block columns (one uint32 row of 5 per rank)
HEAD, TAIL, ENQ, DROP, NOTIF = range(5)
N_CTRS = 5


class QueueError(RuntimeError):
    pass


class QueueState(NamedTuple):
    """Device state of one queue *per rank*.

    Global view (outside shard_map): buf [p, capacity, item_w], ctrs [p, 5].
    Local view  (inside shard_map):  buf [capacity, item_w],    ctrs [5].
    """

    buf: Array
    ctrs: Array


class EnqueueReceipt(NamedTuple):
    accepted: Array       # [k] bool  — per input message: granted a slot?
    n_sent: Array         # []  int32 — messages accepted somewhere
    n_dropped: Array      # []  int32 — valid messages rejected (backpressure)
    incoming: Array       # [p] int32 — msgs admitted into MY ring, per producer
    notifications: Array  # []  uint32 — notifications delivered to me this epoch


@dataclasses.dataclass(frozen=True)
class QueueDescriptor:
    """O(1) metadata describing every rank's ring (the §2.2 property)."""

    axis: str
    capacity: int
    item_shape: tuple
    dtype: Any
    window: window_mod.Window

    @property
    def item_width(self) -> int:
        return int(np.prod(self.item_shape)) if self.item_shape else 1

    @property
    def mask(self) -> int:
        return self.capacity - 1

    def metadata_nbytes(self) -> int:
        """Per-process queue metadata: descriptor constants + the window's
        own O(1) descriptor.  Independent of p AND of capacity — the ring
        storage itself is window *payload*, not metadata."""
        return 48 + self.window.metadata_nbytes()


# ------------------------------------------------------------------ creation
def queue_allocate(
    mesh,
    axis: str,
    capacity: int,
    item_shape: tuple = (),
    dtype: Any = jnp.float32,
) -> tuple[QueueDescriptor, QueueState]:
    """Allocate one ring per rank on `axis` inside an allocated window."""
    if capacity < 2 or capacity & (capacity - 1):
        raise QueueError(f"capacity must be a power of two >= 2, got {capacity}")
    item_w = int(np.prod(item_shape)) if item_shape else 1
    win, buf = window_mod.win_allocate(mesh, axis, (capacity, item_w), dtype)
    desc = QueueDescriptor(axis, capacity, tuple(item_shape), jnp.dtype(dtype), win)
    ctrs = jax.device_put(
        jnp.zeros((mesh.shape[axis], N_CTRS), jnp.uint32),
        NamedSharding(mesh, P(axis, None)),
    )
    return desc, QueueState(buf, ctrs)


def state_specs(axis: str) -> QueueState:
    """shard_map in/out specs for a QueueState's global arrays."""
    return QueueState(P(axis, None, None), P(axis, None))


def to_local(state: QueueState) -> QueueState:
    """Strip the leading size-1 rank dim shard_map leaves on each block."""
    return QueueState(state.buf[0], state.ctrs[0])


def to_global(state: QueueState) -> QueueState:
    return QueueState(state.buf[None], state.ctrs[None])


# ------------------------------------------------------------ admission plan
def admission_plan(C, used, capacity: int, xp=jnp):
    """Rank-ordered slot admission, shared by the SPMD and host paths.

    C[r, t]  : messages producer r wants to enqueue at target t
    used[t]  : tail - head at target t (occupancy)
    Returns (grant[r, t], offset[r, t]): how many of r's messages t admits,
    and r's slot offset past t's current tail — exactly the value a
    rank-order-serialized fetch-and-add would have fetched.
    """
    cum = xp.cumsum(C, axis=0) - C                     # exclusive prefix
    free = (capacity - used).astype(C.dtype)
    grant = xp.clip(free[None, :] - cum, 0, C)
    offset = xp.minimum(cum, free[None, :])
    return grant, offset


def _fifo_pos(key: Array, valid: Array, n_keys: int) -> Array:
    """Program-order index of each message within its group (`key` in
    [0, n_keys), e.g. the target rank — or target*L+lane for per-lane credit
    accounting in `flow`) — the per-message fetch-and-add result."""
    k = key.shape[0]
    key = jnp.where(valid, key, n_keys)                # invalid sort last
    order = jnp.argsort(key, stable=True)
    s_key = key[order]
    pos_sorted = (
        jnp.arange(k, dtype=jnp.int32)
        - jnp.searchsorted(s_key, s_key, side="left").astype(jnp.int32)
    )
    return jnp.zeros((k,), jnp.int32).at[order].set(pos_sorted)


# ------------------------------------------------------------------- enqueue
def enqueue_epoch(
    desc: QueueDescriptor,
    state: QueueState,
    msgs: Array,
    dest: Array,
    reserve_riders: tuple = (),
) -> tuple[QueueState, EnqueueReceipt, tuple]:
    """Collective enqueue epoch (all ranks participate; inside shard_map).

    msgs: [k, *item_shape] payloads; dest: [k] int32 target ranks, -1 = no
    message in that slot.  Returns the updated state and a receipt; rejected
    messages (receipt.accepted == False) stay with the caller — retry after
    the consumer drains (backpressure, never overwrite).

    `reserve_riders` are extra per-rank arrays all-gathered on the
    reservation plan — they ride the SAME fused wire transfer as the counter
    fetch (zero marginal messages) and come back as the third return value
    ([p, *rider.shape] each).  `flow` uses this for credit-limit refreshes.
    """
    axis, cap = desc.axis, desc.capacity
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    k = dest.shape[0]
    tr = obs_trace.TRACER
    if tr.enabled:  # trace-time: static shape attrs only
        tr.event("queue.enqueue_epoch", axis=axis, k=int(k), p=int(p),
                 riders=len(reserve_riders))
    flat = msgs.reshape(k, desc.item_width).astype(desc.dtype)

    # out-of-range dests are treated as "no message" (never accepted), so the
    # receipt contract holds: accepted=True implies delivered exactly once
    valid = (dest >= 0) & (dest < p)
    dest_safe = jnp.where(valid, dest, 0).astype(jnp.int32)
    onehot = jax.nn.one_hot(dest_safe, p, dtype=jnp.int32)
    counts = (onehot * valid[:, None].astype(jnp.int32)).sum(axis=0)  # [p]

    # ---- 1. reserve: rank-ordered fetch-and-add on every target's tail.
    # The count fetch and the counter-window read ride ONE fused gather
    # (an epoch-scoped plan, DESIGN.md §8) instead of two.
    rplan = plan_mod.RmaPlan(axis)
    h_C = rplan.all_gather(counts, kind="gets")        # counter window fetch
    h_ctrs = rplan.all_gather(state.ctrs, kind="accs")  # the fetch-and-add round
    h_riders = [rplan.all_gather(r, kind=None) for r in reserve_riders]
    rplan.flush(aggregate=True)
    C = h_C.result()                                   # [p, p] producer x target
    ctrs_all = h_ctrs.result()                         # [p, 5] counter window read
    rider_out = tuple(h.result() for h in h_riders)
    tails = ctrs_all[:, TAIL]
    used = (tails - ctrs_all[:, HEAD]).astype(jnp.int32)

    # ---- 2. admit up to free space, producers served in rank order
    grant, offset = admission_plan(C, used, cap)       # [p, p] each
    base = tails[None, :] + offset.astype(jnp.uint32)  # absolute start seq

    pos = _fifo_pos(dest, valid, p)                    # [k] FIFO index in group
    accepted = valid & (pos < grant[me, dest_safe])
    seq = base[me, dest_safe] + pos.astype(jnp.uint32)

    # ---- 3. put + notify: pack granted payloads per target and exchange
    slot_idx = dest_safe * k + pos                     # [k] row in [p, k] layout
    oob = p * k                                        # drop index for rejected
    put_idx = jnp.where(accepted, slot_idx, oob)
    send_buf = jnp.zeros((p * k, desc.item_width), desc.dtype).at[put_idx].set(
        flat, mode="drop"
    )
    send_seq = jnp.zeros((p * k,), jnp.uint32).at[put_idx].set(seq, mode="drop")
    send_val = jnp.zeros((p * k,), jnp.bool_).at[put_idx].set(accepted, mode="drop")

    # payload + sequence numbers + notification flags are ONE fused wire
    # transfer (the write-with-notification property, now literal): a queue
    # append is a single aggregated put instead of three collectives.
    pplan = plan_mod.RmaPlan(axis)
    h_buf = pplan.put_all_to_all(send_buf.reshape(p, k, -1), kind="puts")
    h_seq = pplan.put_all_to_all(send_seq.reshape(p, k), kind=None)  # rider
    h_val = pplan.put_all_to_all(send_val.reshape(p, k), kind="accs")  # notify
    pplan.flush(aggregate=True)
    recv_buf = h_buf.result()
    recv_seq = h_seq.result()
    recv_val = h_val.result()

    # ---- owner side: scatter into disjoint ring slots, publish tail
    in_val = recv_val.reshape(p * k)
    in_slot = (recv_seq.reshape(p * k) & jnp.uint32(desc.mask)).astype(jnp.int32)
    buf = state.buf.at[jnp.where(in_val, in_slot, cap)].set(
        recv_buf.reshape(p * k, -1), mode="drop"
    )
    n_in = in_val.sum().astype(jnp.uint32)

    ctrs = state.ctrs
    ctrs = ctrs.at[TAIL].add(n_in)
    ctrs = ctrs.at[ENQ].add(n_in)
    ctrs = ctrs.at[NOTIF].add(n_in)                    # notification counter
    n_sent = accepted.sum().astype(jnp.int32)
    n_dropped = (valid & ~accepted).sum().astype(jnp.int32)
    ctrs = ctrs.at[DROP].add(n_dropped.astype(jnp.uint32))

    receipt = EnqueueReceipt(
        accepted=accepted,
        n_sent=n_sent,
        n_dropped=n_dropped,
        incoming=grant[:, me],
        notifications=n_in,
    )
    return QueueState(buf, ctrs), receipt, rider_out


def enqueue(
    desc: QueueDescriptor, state: QueueState, msgs: Array, dest: Array
) -> tuple[QueueState, EnqueueReceipt]:
    """`enqueue_epoch` without riders (the plain two-transfer append)."""
    state, receipt, _ = enqueue_epoch(desc, state, msgs, dest)
    return state, receipt


def enqueue_shift(
    desc: QueueDescriptor, state: QueueState, msgs: Array, shift: int
) -> tuple[QueueState, EnqueueReceipt]:
    """All k messages to rank (me+shift) mod p — the pipeline/ring special
    case the Pallas `queue_push` kernel implements with literal DMAs."""
    p = jax.lax.axis_size(desc.axis)
    me = lax.axis_index(desc.axis)
    dest = jnp.full((msgs.shape[0],), (me + shift) % p, jnp.int32)
    return enqueue(desc, state, msgs, dest)


# ------------------------------------------------------------------- dequeue
def available(state: QueueState) -> Array:
    return (state.ctrs[TAIL] - state.ctrs[HEAD]).astype(jnp.int32)


def dequeue(
    desc: QueueDescriptor, state: QueueState, max_n: int
) -> tuple[QueueState, Array, Array]:
    """Owner-local drain of up to `max_n` messages in arrival (seq) order.

    Returns (state, items [max_n, *item_shape], valid [max_n]).  Purely
    local — no communication, no lock: head is consumer-private (§2.3
    passive-target analogue where the owner is the only reader).
    """
    tr = obs_trace.TRACER
    if tr.enabled:  # trace-time: static shape attrs only
        tr.event("queue.dequeue", axis=desc.axis, max_n=int(max_n))
    n = jnp.minimum(available(state), max_n)
    offs = jnp.arange(max_n, dtype=jnp.uint32)
    valid = offs < n.astype(jnp.uint32)
    idx = ((state.ctrs[HEAD] + offs) & jnp.uint32(desc.mask)).astype(jnp.int32)
    items = state.buf[idx]
    items = jnp.where(valid[:, None], items, jnp.zeros_like(items))
    ctrs = state.ctrs.at[HEAD].add(n.astype(jnp.uint32))
    return QueueState(state.buf, ctrs), items.reshape((max_n,) + desc.item_shape), valid


def drain(
    desc: QueueDescriptor, state: QueueState
) -> tuple[QueueState, Array, Array]:
    """Dequeue everything currently in the ring (up to capacity)."""
    return dequeue(desc, state, desc.capacity)


def stats(state: QueueState) -> dict:
    """Message-count instrumentation for the complexity assertions."""
    c = state.ctrs
    return {
        "head": c[..., HEAD],
        "tail": c[..., TAIL],
        "enqueued": c[..., ENQ],
        "dropped_by_me": c[..., DROP],
        "notifications": c[..., NOTIF],
    }


# ----------------------------------------------------------- host simulation
class HostQueueGroup:
    """Host-side simulation of p ranks' rings, sharing `admission_plan`.

    The control plane (ft.heartbeat) and unit tests run the identical
    protocol — reservation order, backpressure, wraparound — against numpy
    buffers, without needing a device mesh.

    Remote accesses route through a `core.fabric.Fabric`: the default
    `LocalFabric` applies them immediately (byte-identical to the direct
    mutation this class used to do — the diff test pins it), while
    `repro.sim.fabric.SimFabric` delays/reorders/duplicates delivery so the
    conformance suite can run this exact protocol under chaos schedules.
    """

    def __init__(self, p: int, capacity: int, item_width: int, dtype=np.float32,
                 fabric=None, name: str = "q"):
        from repro.core.fabric import default_fabric

        if capacity < 2 or capacity & (capacity - 1):
            raise QueueError(f"capacity must be a power of two >= 2, got {capacity}")
        self.p = p
        self.capacity = capacity
        self.item_width = item_width
        self.buf = np.zeros((p, capacity, item_width), dtype)
        self.ctrs = np.zeros((p, N_CTRS), np.uint64)
        self.fabric = default_fabric(fabric, p=p)
        self._name = name
        self.fabric.register(f"{name}.buf", self.buf)
        self.fabric.register(f"{name}.ctrs", self.ctrs)

    def step(self, sends: dict[int, list[tuple[int, np.ndarray]]]) -> dict[int, list[bool]]:
        """One enqueue epoch.  sends[r] = [(dest, payload), ...] in program
        order.  Returns per-producer accepted flags (the receipt).

        Fabric protocol per epoch: fence (close the previous epoch so the
        reservation sees delivered state), ONE fused counter gather, then
        per producer a batch of slot puts closed by a flush, and finally the
        owner-side tail/enq/notif publish as `fence_add`s — ordered after
        every payload of this epoch (payload visible ⇒ notification
        visible, the §6.1 write-with-notification guarantee).
        """
        tr = obs_trace.TRACER
        if not tr.enabled:
            return self._step_impl(sends)
        with tr.span("queue.step", rank=-1, queue=self._name,
                     producers=len(sends), epoch=self.fabric.epoch,
                     rids=obs_causal.current_epoch_rids()) as sp:
            accepted = self._step_impl(sends)
            flat = [ok for flags in accepted.values() for ok in flags]
            sp.set(accepted=sum(flat), rejected=len(flat) - sum(flat))
            return accepted

    def _step_impl(self, sends: dict[int, list[tuple[int, np.ndarray]]]) -> dict[int, list[bool]]:
        fab, name = self.fabric, self._name
        fab.fence()  # close the previous epoch before reserving against it
        C = np.zeros((self.p, self.p), np.int64)
        for r, items in sends.items():
            for dst, _ in items:
                C[r, dst] += 1
        ctrs_all = fab.gather(0, f"{name}.ctrs")           # reservation gather
        used = (ctrs_all[:, TAIL] - ctrs_all[:, HEAD]).astype(np.int64)
        grant, offset = admission_plan(C, used, self.capacity, xp=np)
        accepted: dict[int, list[bool]] = {}
        taken = np.zeros((self.p, self.p), np.int64)  # msgs placed so far per pair
        for r, items in sends.items():
            flags = []
            for dst, payload in items:
                j = taken[r, dst]
                ok = j < grant[r, dst]
                if ok:
                    seq = ctrs_all[dst, TAIL] + np.uint64(offset[r, dst] + j)
                    slot = int(seq) & (self.capacity - 1)
                    fab.put(r, dst, f"{name}.buf", slot,
                            np.asarray(payload, self.buf.dtype).reshape(-1))
                else:
                    fab.add(r, r, f"{name}.ctrs", (DROP,), 1)
                taken[r, dst] = j + 1
                flags.append(bool(ok))
            accepted[r] = flags
            fab.flush(r)                                   # producer's epoch close
        admitted = grant.sum(axis=0).astype(np.uint64)
        for t in np.nonzero(admitted)[0]:
            n = admitted[t]
            fab.fence_add(int(t), f"{name}.ctrs", (TAIL,), n)
            fab.fence_add(int(t), f"{name}.ctrs", (ENQ,), n)
            fab.fence_add(int(t), f"{name}.ctrs", (NOTIF,), n)
        return accepted

    def drain(self, rank: int, max_n: int | None = None) -> list[np.ndarray]:
        avail = int(self.ctrs[rank, TAIL] - self.ctrs[rank, HEAD])
        n = avail if max_n is None else min(avail, max_n)
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("queue.drain", rank=rank, queue=self._name, n=n,
                     epoch=self.fabric.epoch)
        out = []
        for i in range(n):
            slot = int(self.ctrs[rank, HEAD] + np.uint64(i)) & (self.capacity - 1)
            out.append(self.buf[rank, slot].copy())
        self.ctrs[rank, HEAD] += np.uint64(n)
        return out

    def stats(self, rank: int) -> dict:
        c = self.ctrs[rank]
        return {
            "head": int(c[HEAD]),
            "tail": int(c[TAIL]),
            "enqueued": int(c[ENQ]),
            "dropped_by_me": int(c[DROP]),
            "notifications": int(c[NOTIF]),
        }
