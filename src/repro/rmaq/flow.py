"""Credit-based flow control for rmaq channels (DESIGN.md §9).

The queue's reject/retry backpressure (§6.2 step 2) keeps the ring safe but
reintroduces the round trip the paper's bufferless protocols exist to avoid:
a producer that hits a full ring learns so only from the receipt, and the
*host* must replay the message next epoch — a wasted reservation round per
rejection.  RAMC (Schonbein et al.) and Taranov et al.'s RDMA protocols both
remove it with **credit-based flow control**: the receiver publishes how many
slots each producer may use, the producer spends from a *local* credit cache,
and a message is simply *deferred at the origin* (never wired) when the cache
is dry.  This module builds that scheme over the §6 machinery:

  * **Credit layout** — each rank publishes one uint32 block
    ``granted[p, L]`` in its queue window next to the §6.2 counter block:
    ``granted[r, l]`` is the *cumulative* number of ring slots this rank has
    ever granted producer r on lane l (initial static partition of the
    capacity + one credit per drained message, returned to the producer that
    sent it).  Cumulative counters wrap mod 2**32 exactly like ``tail``.
  * **Sender state** — O(p·L) words per producer, O(1) per (target, lane):
    ``sent`` (messages pushed) and ``limit`` (last-fetched grant).  The
    credit cache is ``limit - sent``; a send spends one credit, a drain at
    the receiver eventually returns it.
  * **Refresh** — the fetch of a fresh ``limit`` is a get of the target's
    published block.  On the hot path it is recorded as a *rider* on the
    enqueue epoch's reservation plan (`queue.enqueue_epoch`), so it shares
    the fused counter gather: the credit-controlled append is wire-identical
    to the §6.2 append — 2 fused transfers — but never bounces.  An idle
    sender refreshes standalone via `notify.fetch_credits`.
  * **Conservation** — per target t: ``sum_{r,l} granted[t,r,l] - head[t] ==
    capacity`` at all times (grants start at capacity and move in lockstep
    with ``head``), hence outstanding credits + ring occupancy == capacity
    and a credit-admitted message can never find the ring full: the §6.2
    admission becomes a proof obligation instead of a branch (the receipt's
    ``rejected`` count must stay 0; tests assert it).

Every producer on a flow-controlled channel must send through `flow.send` —
one uncredited producer (plain `channel.send`) can consume free space that
credits have already promised to someone else.

The refresh is *one epoch stale* by construction (it rides the current
reservation but is applied to the next epoch's cache): admitting against the
in-flight refresh would need the grant values before the counts gather that
carries them.  That staleness is exactly the credit-return latency the
`PerfModel.p_enqueue_credit` model charges, and it is why a drained ring
recovers in one round trip (exhaust → deferred send whose epoch carries the
refresh → next epoch admits).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import causal as obs_causal
from repro.obs import trace as obs_trace

from . import channel as rch
from . import notify
from . import queue as rq

Array = jax.Array


class FlowError(RuntimeError):
    pass


class FlowState(NamedTuple):
    """Per-rank credit state.

    Global view (outside shard_map): each leaf [p, p, L].
    Local view  (inside shard_map):  each leaf [p, L].

    `sent` / `limit` are origin-private sender state (row t = my traffic
    toward target t); `granted` is the published block remote refreshes
    read — it lives in the queue window beside the §6.2 counter block.
    """

    sent: Array     # [p, L] uint32 — cumulative messages I sent to (t, lane)
    limit: Array    # [p, L] uint32 — cumulative grant last fetched from t
    granted: Array  # [p, L] uint32 — cumulative credits I granted (r, lane)


class FlowReceipt(NamedTuple):
    accepted: Array    # [k] bool — credit-admitted AND delivered
    deferred: Array    # [k] bool — valid but uncredited: never hit the wire
    n_sent: Array      # []  int32
    n_deferred: Array  # []  int32
    refreshed: Array   # []  bool — the cached credits ran dry this epoch
    rejected: Array    # []  int32 — ring-admission rejections (must stay 0)


# ------------------------------------------------------------------ creation
def initial_grants(
    p: int, n_lanes: int, capacity: int, n_producers: Optional[int] = None
) -> np.ndarray:
    """[p, L] uint32 static partition of one ring among producer-lanes.

    The whole capacity is split across the first `n_producers` ranks times
    `n_lanes` lanes (remainder to the lexicographically first pairs), so the
    conservation invariant starts exact: grants sum to capacity.
    """
    nprod = p if n_producers is None else n_producers
    if not 0 < nprod <= p:
        raise FlowError(f"need 0 < n_producers <= {p}, got {nprod}")
    if capacity < nprod * n_lanes:
        raise FlowError(
            f"capacity {capacity} < n_producers*n_lanes = {nprod * n_lanes}: "
            "every producer-lane needs at least one initial credit"
        )
    base, rem = divmod(capacity, nprod * n_lanes)
    g = np.zeros((p, n_lanes), np.uint32)
    for i in range(nprod * n_lanes):
        r, lane = divmod(i, n_lanes)
        g[r, lane] = base + (1 if i < rem else 0)
    return g


def flow_attach(
    mesh, channel: rch.Channel, n_producers: Optional[int] = None
) -> FlowState:
    """Allocate the credit state for an existing channel (global view)."""
    axis = channel.desc.axis
    p = mesh.shape[axis]
    L = len(channel.lanes)
    g = initial_grants(p, L, channel.desc.capacity, n_producers)
    sharding = NamedSharding(mesh, P(axis, None, None))
    granted = jax.device_put(
        jnp.asarray(np.broadcast_to(g[None], (p, p, L)).copy()), sharding
    )
    limit = jax.device_put(
        jnp.asarray(np.broadcast_to(g[:, None, :], (p, p, L)).copy()), sharding
    )
    sent = jax.device_put(jnp.zeros((p, p, L), jnp.uint32), sharding)
    return FlowState(sent, limit, granted)


def flow_allocate(
    mesh,
    axis: str,
    capacity: int,
    lanes: Sequence[rch.Lane],
    n_producers: Optional[int] = None,
) -> tuple[rch.Channel, rq.QueueState, FlowState]:
    """Channel + queue + credit state in one call."""
    channel, qstate = rch.channel_allocate(mesh, axis, capacity, lanes)
    return channel, qstate, flow_attach(mesh, channel, n_producers)


def state_specs(axis: str) -> FlowState:
    """shard_map in/out specs for a FlowState's global arrays."""
    spec = P(axis, None, None)
    return FlowState(spec, spec, spec)


def to_local(f: FlowState) -> FlowState:
    return FlowState(f.sent[0], f.limit[0], f.granted[0])


def to_global(f: FlowState) -> FlowState:
    return FlowState(f.sent[None], f.limit[None], f.granted[None])


def credits(fstate: FlowState) -> Array:
    """[p, L] int32 — the sender's local credit cache (limit - sent)."""
    return (fstate.limit - fstate.sent).astype(jnp.int32)


def _advance_limit(limit: Array, fresh: Array) -> Array:
    """Move the cached limit forward to `fresh` in wrap-safe modular order.

    The cumulative counters wrap mod 2**32 (module docstring), so a plain
    `maximum` would discard every refresh after a wrap (fresh looks smaller
    forever) and deadlock the sender on dry credits.  `fresh` is "ahead"
    iff the modular difference is < 2**31 — same rule the queue uses for
    tail - head."""
    delta = fresh - limit                              # uint32, wraps
    ahead = delta < jnp.uint32(1 << 31)
    return limit + jnp.where(ahead, delta, jnp.uint32(0))


# ---------------------------------------------------------------- send / recv
def send(
    channel: rch.Channel,
    qstate: rq.QueueState,
    fstate: FlowState,
    name: str,
    payload: Array,
    tag: Array,
    dest: Array,
    lane: Optional[Array] = None,
) -> tuple[rq.QueueState, FlowState, FlowReceipt]:
    """Credit-gated channel send (collective; inside shard_map).

    Spends from the local credit cache: messages the cache cannot cover are
    *deferred* — they never enter the wire epoch, so nothing is ever
    rejected at the target and the host never replays a transfer.  The
    credit refresh rides this epoch's reservation gather (zero marginal wire
    transfers) and lands in the cache for the next epoch.

    `lane` ([k] int32) selects a runtime lane per message (homogeneous lane
    tables only); default is lane `name` for all k messages.
    """
    desc = channel.desc
    axis = desc.axis
    p = jax.lax.axis_size(axis)
    L = len(channel.lanes)
    me = lax.axis_index(axis)
    k = dest.shape[0]
    tr = obs_trace.TRACER
    if tr.enabled:  # trace-time: static shape attrs only
        tr.event("flow.send_epoch", axis=axis, k=int(k), lane=name)
    if lane is None:
        lane = jnp.full((k,), channel.lane_id(name), jnp.int32)
    lane = lane.astype(jnp.int32)

    valid = (dest >= 0) & (dest < p) & (lane >= 0) & (lane < L)
    dest_safe = jnp.where(valid, dest, 0).astype(jnp.int32)
    lane_safe = jnp.where(valid, lane, 0)

    # ---- spend from the local cache: per-(target, lane) FIFO admission
    avail = credits(fstate)                            # [p, L]
    pos = rq._fifo_pos(dest_safe * L + lane_safe, valid, p * L)
    ok = valid & (pos < avail[dest_safe, lane_safe])
    dry = valid & ~ok
    stage_dest = jnp.where(ok, dest, -1).astype(jnp.int32)

    # ---- the wire epoch: identical 2 fused transfers; the credit refresh
    # rides the reservation gather as a kind-less protocol rider
    msgs = channel.packed(name, payload, tag, lane_id=lane)
    qstate, receipt, (granted_all,) = rq.enqueue_epoch(
        desc, qstate, msgs, stage_dest, reserve_riders=(fstate.granted,)
    )

    # ---- debit the cache, apply the refresh (visible next epoch)
    spent = jnp.zeros((p, L), jnp.uint32).at[dest_safe, lane_safe].add(
        ok.astype(jnp.uint32)
    )
    fresh = granted_all[:, me, :]                      # what each owner grants ME
    fstate = FlowState(
        sent=fstate.sent + spent,
        limit=_advance_limit(fstate.limit, fresh),
        granted=fstate.granted,
    )
    flow_receipt = FlowReceipt(
        accepted=receipt.accepted,
        deferred=dry,
        n_sent=receipt.n_sent,
        n_deferred=dry.sum().astype(jnp.int32),
        refreshed=dry.any(),
        rejected=(ok & ~receipt.accepted).sum().astype(jnp.int32),
    )
    return qstate, fstate, flow_receipt


def recv(
    channel: rch.Channel,
    qstate: rq.QueueState,
    fstate: FlowState,
    max_n: int,
) -> tuple[rq.QueueState, FlowState, rch.RecvBatch]:
    """Owner-local drain that returns credits: every drained message grants
    one slot back to the (producer, lane) that sent it, by bumping the
    published `granted` block — the head advance and the grant move in
    lockstep, which is the conservation invariant."""
    L = len(channel.lanes)
    qstate, batch = channel.recv(qstate, max_n)
    ok = batch.valid & (batch.lane_id >= 0) & (batch.lane_id < L)
    src_safe = jnp.where(ok, batch.src, 0).astype(jnp.int32)
    lane_safe = jnp.where(ok, batch.lane_id, 0).astype(jnp.int32)
    granted = fstate.granted.at[src_safe, lane_safe].add(ok.astype(jnp.uint32))
    return qstate, fstate._replace(granted=granted), batch


def refresh(channel: rch.Channel, fstate: FlowState) -> FlowState:
    """Standalone credit refresh for an idle sender (no enqueue to ride):
    one one-sided gather of the published grant blocks (`p_credit_refresh`
    with fused=False)."""
    granted_all = notify.fetch_credits(fstate.granted, channel.desc.axis)
    me = lax.axis_index(channel.desc.axis)
    return fstate._replace(
        limit=_advance_limit(fstate.limit, granted_all[:, me, :]))


# ------------------------------------------------------------------ invariants
def conservation(
    channel: rch.Channel, qstate: rq.QueueState, fstate: FlowState
) -> dict:
    """Global-view conservation check (host side, outside shard_map).

    For every target t:  sum_{r,l} granted[t,r,l] - head[t] == capacity  and
    outstanding credits + ring occupancy == capacity.  Returns per-target
    arrays; tests assert both equal `capacity` everywhere.  (Debug/test
    helper: exact until the uint32 counters wrap, ~4e9 messages per rank.)
    """
    granted = np.asarray(fstate.granted).astype(np.int64)   # [t, r, L]
    sent = np.asarray(fstate.sent).astype(np.int64)         # [r, t, L]
    ctrs = np.asarray(qstate.ctrs).astype(np.int64)         # [t, 5]
    head, tail = ctrs[:, rq.HEAD], ctrs[:, rq.TAIL]
    outstanding = granted.sum(axis=(1, 2)) - sent.sum(axis=(0, 2))  # per target
    occupancy = tail - head
    return {
        "granted_minus_head": granted.sum(axis=(1, 2)) - head,
        "outstanding_plus_occupancy": outstanding + occupancy,
        "occupancy": occupancy,
        "capacity": channel.desc.capacity,
    }


# ----------------------------------------------------------- host simulation
class HostFlowChannel:
    """Host-side mirror of the credit protocol over `HostChannel`.

    Same cache / refresh / defer semantics as the SPMD path, with the
    refresh as an explicit one-sided read (counted in `refreshes`) issued
    only when the cache runs dry — the control-plane and unit tests exercise
    exhaustion → refresh → recovery without a device mesh.

    Credits cover **ring slots**, whatever the lane carries: on a
    descriptor-kind lane table (rendezvous pull, §16) the window is
    descriptor-width, so the credit protocol never has to account for
    payload bytes — `bytes_by_kind` / `sends_by_kind` ledger the split so
    engines and drift gates can assert a pull path puts zero payload
    bytes through the ring.

    Every window carries an **attach id** published beside the grant
    block.  `ft/elastic` leave/join can reuse a rank id; a refresh that
    monotonically maxed the *new* occupant's grants against the old
    occupant's would advance `limit` by credits nobody granted.  The
    refresh therefore rebases (limit := fresh, sent := 0) whenever the
    published attach id differs from the one it last saw — the same
    invalidation rule `rmem.DescriptorCache` applies to page tables.
    """

    def __init__(self, p: int, capacity: int, lanes: Sequence[rch.Lane],
                 n_producers: Optional[int] = None, fabric=None,
                 name: str = "q", causal_tags: bool = False):
        # causal_tags: declares that message tags ARE request ids (the serve
        # path's convention) — send/recv then stamp causal edge/cause links
        # so traces stitch into cross-rank request DAGs (obs.causal).  Off
        # by default: generic channels carry arbitrary tags.
        self.causal_tags = causal_tags
        self.ch = rch.HostChannel(p, capacity, lanes, fabric=fabric, name=name)
        self.fabric = self.ch.group.fabric
        self._granted_region = f"{name}.granted"
        self._attach_region = f"{name}.attach"
        self.p = p
        self.L = len(self.ch.lanes)
        self.capacity = capacity
        self.n_producers = p if n_producers is None else n_producers
        g = initial_grants(p, self.L, capacity, n_producers).astype(np.uint64)
        self.granted = np.tile(g[None], (p, 1, 1))          # [owner, prod, L]
        self.limit = np.tile(g[:, None, :], (1, p, 1))      # [prod, target, L]
        self.sent = np.zeros((p, p, self.L), np.uint64)     # [prod, target, L]
        # the published grant blocks live in the queue window (§9): remote
        # refreshes read them through the fabric; owner-side grant returns
        # stay direct (drain + grant move in lockstep, owner-locally)
        self.fabric.register(self._granted_region, self.granted)
        # window generation, bumped by rebind(); producers cache what they
        # last saw per target and rebase their limit on mismatch
        self.attach_id = np.zeros(p, np.int64)
        self.fabric.register(self._attach_region, self.attach_id)
        self._seen_attach = np.zeros((p, p), np.int64)      # [prod, target]
        self.refreshes = 0
        self.deferred = 0
        self.rejected = 0   # ring-admission rejections: must stay 0
        self.rebinds = 0    # refreshes that detected a window re-attach
        self.sends_by_kind = {k: 0 for k in rch.LANE_KINDS}
        self.bytes_by_kind = {k: 0 for k in rch.LANE_KINDS}

    def available(self, src: int, dest: int, lane: int) -> int:
        return int(self.limit[src, dest, lane] - self.sent[src, dest, lane])

    def ring_slot_nbytes(self) -> int:
        """Wire bytes one ring slot occupies (header + widest lane)."""
        return 4 * (rch.HDR + self.ch.payload_words)

    def ring_window_nbytes(self) -> int:
        """Per-rank ring footprint — the memory the credit window covers.
        On a descriptor lane table this is descriptor-sized no matter how
        large the KV blocks being transferred are."""
        return self.ring_slot_nbytes() * self.capacity

    def _refresh(self, src: int, dest: int) -> None:
        """One-sided get of dest's published grant row for this producer,
        guarded by the window attach id (class docstring): a re-attached
        window rebases the cache instead of maxing against stale grants."""
        self.refreshes += 1
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("flow.refresh", rank=src, dest=dest)
        aid = int(self.fabric.get(src, dest, self._attach_region))
        fresh = self.fabric.get(src, dest, self._granted_region, (src,))
        if aid != int(self._seen_attach[src, dest]):
            self._seen_attach[src, dest] = aid
            self.limit[src, dest] = fresh
            self.sent[src, dest] = 0
            self.rebinds += 1
            if tr.enabled:
                tr.event("flow.rebase", rank=src, dest=dest, attach=aid)
            return
        self.limit[src, dest] = np.maximum(self.limit[src, dest], fresh)

    def rebind(self, rank: int, n_producers: Optional[int] = None) -> None:
        """Re-attach `rank`'s window after an elastic leave/join reused its
        id: fresh ring, fresh initial grants, bumped attach id.  The caller
        (the membership layer) fences the fabric first so no epoch is in
        flight.  Producers discover the re-attach at their next refresh and
        rebase; the departed occupant's own outbound credit is frozen (its
        sender state dies with it — re-granting a *resurrected producer* is
        the membership layer's job, not the flow layer's)."""
        nprod = self.n_producers if n_producers is None else n_producers
        self.granted[rank] = initial_grants(
            self.p, self.L, self.capacity, nprod).astype(np.uint64)
        self.attach_id[rank] += 1
        grp = self.ch.group
        grp.ctrs[rank] = 0
        grp.buf[rank] = 0
        self.sent[rank] = self.limit[rank]
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("flow.rebind", rank=rank,
                     attach=int(self.attach_id[rank]))

    def send(self, src: int, name: str, payload, tag: int, dest: int) -> bool:
        """Stage one credited message; False = deferred (cache dry even
        after a refresh) and the message stays with the caller — it never
        reaches the wire, so there is nothing to retry."""
        lane = self.ch._lane_id(name)
        tr = obs_trace.TRACER
        if self.available(src, dest, lane) == 0:
            self._refresh(src, dest)                 # fall back: cache is dry
            if self.available(src, dest, lane) == 0:
                self.deferred += 1
                if tr.enabled:
                    if self.causal_tags:
                        tr.event("flow.send", rank=src, dest=dest, lane=lane,
                                 outcome="deferred", rid=int(tag),
                                 seg="credit_stall")
                    else:
                        tr.event("flow.send", rank=src, dest=dest, lane=lane,
                                 outcome="deferred")
                return False
        if tr.enabled:
            if self.causal_tags:
                # producer end of the message's causal edge; the matching
                # cause lands on the consumer's flow.deliver at recv
                tr.event("flow.send", rank=src, dest=dest, lane=lane,
                         outcome="credited", rid=int(tag),
                         edge=obs_causal.edge(int(tag), f"flow{src}-{dest}"))
            else:
                tr.event("flow.send", rank=src, dest=dest, lane=lane,
                         outcome="credited")
        self.ch.send(src, name, payload, tag, dest)
        self.sent[src, dest, lane] += 1
        kind = self.ch.lanes[lane].kind
        self.sends_by_kind[kind] += 1
        self.bytes_by_kind[kind] += self.ring_slot_nbytes()
        return True

    def flush(self) -> dict[int, list[bool]]:
        flags = self.ch.flush()
        self.rejected += sum(fl.count(False) for fl in flags.values())
        return flags

    def recv(self, rank: int, max_n: Optional[int] = None) -> list[dict]:
        msgs = self.ch.recv(rank, max_n)
        tr = obs_trace.TRACER
        if tr.enabled:
            tr.event("flow.recv", rank=rank, n=len(msgs))
            if self.causal_tags:
                for m in msgs:
                    tr.event("flow.deliver", rank=rank, rid=int(m["tag"]),
                             src=int(m["src"]),
                             cause=obs_causal.edge(
                                 int(m["tag"]), f"flow{int(m['src'])}-{rank}"))
        for m in msgs:
            self.granted[rank, m["src"], self.ch._lane_id(m["lane"])] += 1
        return msgs

    def conservation(self, rank: int) -> dict:
        ctrs = self.ch.group.ctrs[rank]
        head, tail = int(ctrs[rq.HEAD]), int(ctrs[rq.TAIL])
        g = int(self.granted[rank].sum())
        outstanding = g - int(self.sent[:, rank].sum())
        return {
            "granted_minus_head": g - head,
            "outstanding_plus_occupancy": outstanding + (tail - head),
            "occupancy": tail - head,
            "capacity": self.capacity,
        }

    def stats(self, rank: int) -> dict:
        s = self.ch.stats(rank)
        s.update(refreshes=self.refreshes, deferred=self.deferred,
                 rejected=self.rejected, rebinds=self.rebinds,
                 sends_by_kind=dict(self.sends_by_kind),
                 bytes_by_kind=dict(self.bytes_by_kind))
        return s
