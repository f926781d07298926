"""Symmetric-heap remote page allocator over a dynamic RMA window (DESIGN.md §10).

Every rank owns one fixed-size *page pool* living in a dynamic window
(`win_create_dynamic` + attach, §2.2): the pool can grow and shrink at
runtime, and each grow/shrink bumps the window's ``attach_id`` so remote
descriptor caches are invalidated instead of serving stale translations.
Free pages are arbitrated by a **per-rank remote free-list** in the style of
Taranov et al.'s RDMA allocators: the list head is a single word updated by
fetch-and-op / CAS, with a wrap-safe uint32 **generation tag** advanced on
every allocate *and* every free so a stale head (or a stale (page, tag)
descriptor held by a reader) is detected instead of silently reused — the
classic ABA defense.

Two implementations share the protocol:

  * **SPMD path** (functions below, inside ``shard_map``) — TPU has no
    remote AMOs, so multi-origin fetch-and-op is the *rank-ordered* epoch
    serialization the queue already uses (`notify.fetch_and_add_ordered`):
    one fused counter gather gives every producer its slot range in the
    target's free stack deterministically.  Alloc/free/refcount rounds are
    recorded as `RmaPlan` ops (`alloc_record`/`ref_update_record`), so
    allocation can piggyback on an existing epoch's fused gather — zero
    marginal wire transfers when it rides e.g. a queue reservation.
  * **Host path** (`HostPagePool`) — the *literal* CAS free-list: a 64-bit
    head word packing (generation << 32 | head index), pop/push via
    compare-and-swap loops on `locks_sim._AtomicWord`, per-page refcounts
    via fetch-and-add.  Used by the serving scheduler (host-side admission
    mirrors, like `HostFlowChannel`) and by the threaded stress tests that
    exercise real concurrency.

Refcount protocol (§5.1 lock discipline, CAS edition): a page is *live*
while its refcount > 0.  `ref_update(+1)` shares a page (prefix sharing);
`ref_update(-1)` releases it, and the owner pushes pages reaching zero back
onto the free stack in the same epoch — release-at-zero is atomic with the
decrement because the owner applies both, exactly like the slotted
accumulate (§2.4).  Conservation invariant, asserted like flow's credit
conservation:  ``free_top + #(refcount > 0) == n_pages``  per rank, always.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import plan as plan_mod
from repro.core import window as window_mod
from repro.core.locks_sim import _AtomicWord
from repro.obs import causal as obs_causal
from repro.obs import flight as obs_flight
from repro.obs import trace as obs_trace
from repro.rmaq.queue import admission_plan

Array = jax.Array

# head-word columns (one uint32 row of 5 per rank).  ERRS counts refcount
# deltas addressed to dead pages (the SPMD analogue of the host path's
# HeapError: device code cannot raise, so the protocol violation is dropped
# WITHOUT corrupting the pool and surfaced through this counter).
FREE_TOP, EPOCH, ALLOCS, FREES, ERRS = range(5)
N_HEAD = 5

# per-page meta columns (uint32)
REF, GEN = range(2)
N_META = 2


class HeapError(RuntimeError):
    pass


class PoolState(NamedTuple):
    """Device state of one page pool *per rank*.

    Global view (outside shard_map): pages [p, n_pages, *page_shape],
    meta [p, n_pages, 2] u32, free_stack [p, n_pages] i32,
    head [p, N_HEAD] u32.  Local view (inside shard_map): leading rank dim
    stripped.
    """

    pages: Array       # page payload storage (the symmetric heap)
    meta: Array        # (refcount, generation) per page
    free_stack: Array  # free page ids; [0, free_top) is the free set
    head: Array        # (free_top, epoch, allocs, frees) — the AMO word row


@dataclasses.dataclass(frozen=True)
class PoolDescriptor:
    """O(1) metadata describing every rank's pool (the §2.2 property)."""

    axis: str
    n_pages: int
    page_shape: tuple
    dtype: Any
    window: window_mod.Window
    regions: tuple  # attached region ids: (pages, meta, stack)

    @property
    def page_words(self) -> int:
        return int(np.prod(self.page_shape)) if self.page_shape else 1

    @property
    def page_nbytes(self) -> int:
        return self.page_words * jnp.dtype(self.dtype).itemsize

    def metadata_nbytes(self) -> int:
        """Descriptor constants + the dynamic window's own O(1)-per-region
        metadata; independent of p and of n_pages (pages are payload)."""
        return 64 + self.window.metadata_nbytes()


# ------------------------------------------------------------------ creation
def pool_allocate(
    mesh,
    axis: str,
    n_pages: int,
    page_shape: tuple = (),
    dtype: Any = jnp.float32,
) -> tuple[PoolDescriptor, PoolState]:
    """One page pool per rank on `axis`, inside a dynamic window.

    The pool's three arrays are attached regions of one
    ``win_create_dynamic`` window, so `pool_grow`/`pool_shrink` reproduce
    the §2.2 attach/detach protocol (attach_id bump → remote descriptor
    caches invalidated) instead of pretending registration is free.
    """
    if n_pages < 1:
        raise HeapError(f"need n_pages >= 1, got {n_pages}")
    p = mesh.shape[axis]
    win = window_mod.win_create_dynamic(mesh, axis)
    regions = (
        win.attach("pages", (n_pages,) + tuple(page_shape), dtype),
        win.attach("meta", (n_pages, N_META), jnp.uint32),
        win.attach("stack", (n_pages,), jnp.int32),
    )
    desc = PoolDescriptor(axis, n_pages, tuple(page_shape), jnp.dtype(dtype),
                          win, regions)
    pages = jnp.zeros((p, n_pages) + tuple(page_shape), dtype)
    meta = jnp.zeros((p, n_pages, N_META), jnp.uint32)
    stack = jnp.tile(jnp.arange(n_pages, dtype=jnp.int32)[None], (p, 1))
    head = jnp.zeros((p, N_HEAD), jnp.uint32).at[:, FREE_TOP].set(n_pages)
    state = PoolState(
        jax.device_put(pages, NamedSharding(mesh, P(axis, *[None] * (1 + len(page_shape))))),
        jax.device_put(meta, NamedSharding(mesh, P(axis, None, None))),
        jax.device_put(stack, NamedSharding(mesh, P(axis, None))),
        jax.device_put(head, NamedSharding(mesh, P(axis, None))),
    )
    return desc, state


def state_specs(axis: str, page_ndim: int = 0) -> PoolState:
    """shard_map in/out specs for a PoolState's global arrays."""
    return PoolState(
        P(axis, *[None] * (1 + page_ndim)),
        P(axis, None, None),
        P(axis, None),
        P(axis, None),
    )


def to_local(s: PoolState) -> PoolState:
    return PoolState(s.pages[0], s.meta[0], s.free_stack[0], s.head[0])


def to_global(s: PoolState) -> PoolState:
    return PoolState(s.pages[None], s.meta[None], s.free_stack[None], s.head[None])


# ------------------------------------------------------------------ alloc
def alloc_record(plan: plan_mod.RmaPlan, state: PoolState, want: Array):
    """Record the allocation epoch's one-sided reads on an existing plan.

    `want[t]` = pages this rank requests from target t's pool.  The round is
    the rank-ordered fetch-and-op on every target's free-list head word: the
    request-count fetch and the head read are the AMO (kind ``accs`` — this
    is what a hardware fetch-and-add would charge), and the stack contents
    ride the same fused gather as a kind-less protocol rider, so piggybacked
    allocation costs ZERO marginal wire transfers.  Returns opaque handles
    for `alloc_apply` after the caller flushes the plan.
    """
    h_want = plan.all_gather(want.astype(jnp.int32), kind="gets")
    h_head = plan.all_gather(state.head, kind="accs")     # the fetch-and-op
    h_stack = plan.all_gather(state.free_stack, kind=None)  # rider
    return (h_want, h_head, h_stack)


def alloc_apply(
    desc: PoolDescriptor, state: PoolState, kmax: int, handles
) -> tuple[PoolState, Array, Array]:
    """Resolve a recorded allocation epoch (after the plan's flush).

    Returns (state', ids [p, kmax] int32 — my granted page ids in target
    t's pool, -1 past my grant — and granted [p] int32 counts).  Producers
    are served in rank order (the epoch-serialized fetch-and-op), so every
    origin computes identical disjoint grants from the same gathered data.
    """
    h_want, h_head, h_stack = handles
    n_pages = desc.n_pages
    me = lax.axis_index(desc.axis)
    C = h_want.result()                                  # [p, p] producer x target
    heads = h_head.result()                              # [p, N_HEAD]
    stacks = h_stack.result()                            # [p, n_pages]

    free_top = heads[:, FREE_TOP].astype(jnp.int32)      # [p]
    used = n_pages - free_top
    grant, offset = admission_plan(C, used, n_pages)     # [p, p] each

    # my page ids: pop offset..offset+grant from the top of each stack
    j = jnp.arange(kmax, dtype=jnp.int32)
    idx = free_top[:, None] - 1 - offset[me][:, None] - j[None, :]   # [p, kmax]
    got = j[None, :] < grant[me][:, None]
    ids = jnp.take_along_axis(
        stacks, jnp.clip(idx, 0, n_pages - 1), axis=1).astype(jnp.int32)
    ids = jnp.where(got, ids, -1)

    # owner side: pop the granted top region, mark pages live (ref=1, gen+1)
    total = grant[:, me].sum().astype(jnp.int32)         # pages leaving MY pool
    top_me = free_top[me]
    i = jnp.arange(n_pages, dtype=jnp.int32)
    popped = (i >= top_me - total) & (i < top_me)        # stack rows popped
    rows = jnp.where(popped, state.free_stack, n_pages)  # page ids popped
    meta = state.meta
    meta = meta.at[rows, REF].set(1, mode="drop")
    meta = meta.at[rows, GEN].add(1, mode="drop")        # ABA tag: alloc bump
    head = state.head
    head = head.at[FREE_TOP].add((-total).astype(jnp.uint32))
    head = head.at[ALLOCS].add(total.astype(jnp.uint32))
    head = head.at[EPOCH].add(1)
    return PoolState(state.pages, meta, state.free_stack, head), ids, grant[me]


def alloc(
    desc: PoolDescriptor, state: PoolState, want: Array, kmax: int
) -> tuple[PoolState, Array, Array]:
    """Standalone allocation epoch: one fused gather (collective; inside
    shard_map).  `want[t]` pages from target t; at most `kmax` per target."""
    tr = obs_trace.TRACER
    if tr.enabled:  # trace-time: static shape attrs only
        tr.event("heap.alloc_epoch", axis=desc.axis, kmax=int(kmax))
    plan = plan_mod.RmaPlan(desc.axis)
    handles = alloc_record(plan, state, want)
    plan.flush(aggregate=True)
    return alloc_apply(desc, state, kmax, handles)


# ------------------------------------------------------- refcount / release
def ref_update_record(plan: plan_mod.RmaPlan, ids: Array, owner: Array,
                      delta: Array, axis: str):
    """Record one refcount round: (page id, delta) pairs fly to their owner
    as ONE fused a2a (the §2.4 slotted accumulate; kind ``accs``)."""
    p = jax.lax.axis_size(axis)
    k = ids.shape[0]
    valid = (owner >= 0) & (owner < p) & (ids >= 0)
    owner_safe = jnp.where(valid, owner, 0).astype(jnp.int32)
    j = jnp.arange(k, dtype=jnp.int32)
    send_id = jnp.full((p, k), -1, jnp.int32).at[owner_safe, j].set(
        jnp.where(valid, ids, -1), mode="drop")
    send_dl = jnp.zeros((p, k), jnp.int32).at[owner_safe, j].set(
        jnp.where(valid, delta, 0), mode="drop")
    h_id = plan.put_all_to_all(send_id, kind="accs")
    h_dl = plan.put_all_to_all(send_dl, kind=None)        # rides the same wire
    return (h_id, h_dl)


def ref_update_apply(
    desc: PoolDescriptor, state: PoolState, handles
) -> tuple[PoolState, Array]:
    """Owner-side: apply refcount deltas; pages reaching zero return to the
    free stack in the same epoch (release-at-zero, §5.1 discipline).
    Returns (state', n_freed).  Deltas driving a count below zero are a
    protocol bug: they clamp at zero and increment the FREES counter only
    for genuine live→dead transitions, so conservation stays checkable.
    """
    h_id, h_dl = handles
    n_pages = desc.n_pages
    recv_id = h_id.result().reshape(-1)                  # [p*k]
    recv_dl = h_dl.result().reshape(-1)
    ok = recv_id >= 0
    rows = jnp.where(ok, recv_id, n_pages)
    dsum = jnp.zeros((n_pages,), jnp.int32).at[rows].add(
        jnp.where(ok, recv_dl, 0), mode="drop")

    old_ref = state.meta[:, REF].astype(jnp.int32)
    # deltas addressed to DEAD pages are protocol violations (a stale
    # PageRef shared after free — the ABA hazard): the host path raises
    # HeapError; here they are dropped whole so a dead page can never be
    # resurrected while its id sits in the free stack, and the violation
    # is surfaced through the ERRS head counter.
    bad = (old_ref == 0) & (dsum != 0)
    dsum = jnp.where(bad, 0, dsum)
    new_ref = jnp.clip(old_ref + dsum, 0, None)
    # decrements below zero clamp: the over-release is also a violation
    bad_n = bad.sum() + ((old_ref + dsum) < 0).sum()
    freed = (old_ref > 0) & (new_ref == 0)               # live -> dead now
    n_freed = freed.sum().astype(jnp.int32)

    meta = state.meta.at[:, REF].set(new_ref.astype(jnp.uint32))
    meta = meta.at[:, GEN].add(freed.astype(jnp.uint32))  # ABA tag: free bump

    # push freed page ids onto the stack at [free_top, free_top + n_freed)
    top = state.head[FREE_TOP].astype(jnp.int32)
    pos = jnp.cumsum(freed.astype(jnp.int32)) - freed.astype(jnp.int32)
    slot = jnp.where(freed, top + pos, n_pages)
    stack = state.free_stack.at[slot].set(
        jnp.arange(n_pages, dtype=jnp.int32), mode="drop")

    head = state.head
    head = head.at[FREE_TOP].add(n_freed.astype(jnp.uint32))
    head = head.at[FREES].add(n_freed.astype(jnp.uint32))
    head = head.at[ERRS].add(bad_n.astype(jnp.uint32))
    head = head.at[EPOCH].add(1)
    return PoolState(state.pages, meta, stack, head), n_freed


def ref_update(
    desc: PoolDescriptor, state: PoolState, ids: Array, owner: Array,
    delta: Array,
) -> tuple[PoolState, Array]:
    """Standalone refcount epoch (collective; inside shard_map).

    ids/owner/delta: [k] each; owner -1 = no-op slot.  delta +1 shares a
    page (prefix sharing), -1 releases it; the owner frees at zero.
    """
    plan = plan_mod.RmaPlan(desc.axis)
    handles = ref_update_record(plan, ids, owner, delta, desc.axis)
    plan.flush(aggregate=True)
    return ref_update_apply(desc, state, handles)


def release(
    desc: PoolDescriptor, state: PoolState, ids: Array, owner: Array
) -> tuple[PoolState, Array]:
    """`ref_update` with delta -1 for every valid slot."""
    return ref_update(desc, state, ids, owner,
                      jnp.full(ids.shape, -1, jnp.int32))


def tag_valid(state: PoolState, ids: Array, gens: Array) -> Array:
    """ABA check (local view): a cached (page, generation) descriptor is
    valid iff the page's current generation still matches — any alloc or
    free since the tag was taken bumped it (wrap-safe: uint32 equality)."""
    safe = jnp.clip(ids, 0, state.meta.shape[0] - 1)
    return (state.meta[safe, GEN] == gens.astype(jnp.uint32)) & (ids >= 0)


# ------------------------------------------------------------- grow / shrink
def pool_grow(
    mesh, desc: PoolDescriptor, state: PoolState, extra: int
) -> tuple[PoolDescriptor, PoolState]:
    """Grow every rank's pool by `extra` pages (host side, global view).

    The §2.2 dynamic-window protocol: detach the three regions, re-attach
    at the new size.  Both steps bump ``attach_id``, so every remote
    `DescriptorCache` refetches instead of serving a stale translation —
    the attach → alloc → detach → realloc test hangs off this.
    """
    if extra < 1:
        raise HeapError(f"need extra >= 1, got {extra}")
    win = desc.window
    for rid in desc.regions:
        win.detach(rid)
    n_new = desc.n_pages + extra
    regions = (
        win.attach("pages", (n_new,) + desc.page_shape, desc.dtype),
        win.attach("meta", (n_new, N_META), jnp.uint32),
        win.attach("stack", (n_new,), jnp.int32),
    )
    new_desc = dataclasses.replace(desc, n_pages=n_new, regions=regions)

    p = mesh.shape[desc.axis]
    pages = np.zeros((p, n_new) + desc.page_shape, desc.dtype)
    pages[:, : desc.n_pages] = np.asarray(state.pages)
    meta = np.zeros((p, n_new, N_META), np.uint32)
    meta[:, : desc.n_pages] = np.asarray(state.meta)
    head = np.asarray(state.head).copy()
    stack = np.zeros((p, n_new), np.int32)
    old_stack = np.asarray(state.free_stack)
    for r in range(p):
        top = int(head[r, FREE_TOP])
        stack[r, :top] = old_stack[r, :top]
        stack[r, top : top + extra] = np.arange(desc.n_pages, n_new)
    head[:, FREE_TOP] += extra
    head[:, EPOCH] += 1
    return new_desc, _device_state(mesh, desc.axis, pages, meta, stack, head,
                                   len(desc.page_shape))


def pool_shrink(
    mesh, desc: PoolDescriptor, state: PoolState, remove: int
) -> tuple[PoolDescriptor, PoolState]:
    """Shrink every rank's pool by its `remove` highest page ids.

    Refuses unless those pages are free on every rank (live pages cannot be
    deregistered out from under their references).  Detach/attach bumps
    ``attach_id`` exactly like grow.
    """
    n_new = desc.n_pages - remove
    if remove < 1 or n_new < 1:
        raise HeapError(f"cannot shrink {desc.n_pages} pages by {remove}")
    meta = np.asarray(state.meta)
    live_high = meta[:, n_new:, REF] > 0
    if live_high.any():
        ranks = sorted(set(np.argwhere(live_high)[:, 0].tolist()))
        raise HeapError(
            f"pages >= {n_new} still live on ranks {ranks}: release before shrink"
        )
    win = desc.window
    for rid in desc.regions:
        win.detach(rid)
    regions = (
        win.attach("pages", (n_new,) + desc.page_shape, desc.dtype),
        win.attach("meta", (n_new, N_META), jnp.uint32),
        win.attach("stack", (n_new,), jnp.int32),
    )
    new_desc = dataclasses.replace(desc, n_pages=n_new, regions=regions)

    p = mesh.shape[desc.axis]
    pages = np.asarray(state.pages)[:, :n_new].copy()
    new_meta = meta[:, :n_new].copy()
    head = np.asarray(state.head).copy()
    old_stack = np.asarray(state.free_stack)
    stack = np.zeros((p, n_new), np.int32)
    for r in range(p):
        top = int(head[r, FREE_TOP])
        keep = old_stack[r, :top][old_stack[r, :top] < n_new]
        stack[r, : keep.size] = keep
        head[r, FREE_TOP] = keep.size
    head[:, EPOCH] += 1
    return new_desc, _device_state(mesh, desc.axis, pages, new_meta, stack,
                                   head, len(desc.page_shape))


def _device_state(mesh, axis, pages, meta, stack, head, page_ndim) -> PoolState:
    return PoolState(
        jax.device_put(jnp.asarray(pages),
                       NamedSharding(mesh, P(axis, *[None] * (1 + page_ndim)))),
        jax.device_put(jnp.asarray(meta), NamedSharding(mesh, P(axis, None, None))),
        jax.device_put(jnp.asarray(stack), NamedSharding(mesh, P(axis, None))),
        jax.device_put(jnp.asarray(head), NamedSharding(mesh, P(axis, None))),
    )


# ---------------------------------------------------------------- invariants
def conservation(desc: PoolDescriptor, state: PoolState) -> dict:
    """Global-view conservation check (host side, outside shard_map).

    Per rank: free_top + #(refcount > 0) == n_pages, and the free stack's
    first free_top entries are exactly the dead pages (set equality) — the
    page-pool analogue of flow's credit conservation.
    """
    meta = np.asarray(state.meta)
    head = np.asarray(state.head)
    stack = np.asarray(state.free_stack)
    p = meta.shape[0]
    free = head[:, FREE_TOP].astype(np.int64)
    live = (meta[:, :, REF] > 0).sum(axis=1).astype(np.int64)
    stack_ok = np.zeros((p,), bool)
    for r in range(p):
        free_set = set(stack[r, : int(free[r])].tolist())
        dead_set = set(np.where(meta[r, :, REF] == 0)[0].tolist())
        stack_ok[r] = (len(free_set) == int(free[r])) and free_set == dead_set
    return {
        "free_plus_live": free + live,
        "capacity": desc.n_pages,
        "free": free,
        "live": live,
        "stack_consistent": stack_ok,
        "protocol_errors": head[:, ERRS].astype(np.int64),
    }


def check_errors(desc: PoolDescriptor, state: PoolState) -> None:
    """Host-side surface for the SPMD protocol violations (§10): device code
    cannot raise, so double-free / share-dead deltas are dropped whole and
    counted in the ERRS head column — this promotes a nonzero count to the
    same `HeapError` the host path raises, naming the offending ranks.

    Call it wherever the host owns the loop (schedulers, tests, benchmark
    harnesses) to get fail-loud semantics on the SPMD path too.
    """
    errs = np.asarray(state.head)[..., ERRS].reshape(-1).astype(np.int64)
    bad = np.nonzero(errs)[0]
    if bad.size:
        detail = ", ".join(f"rank {int(r)}: {int(errs[r])}" for r in bad)
        raise HeapError(
            f"SPMD refcount protocol violations (double-free or share-dead "
            f"deltas dropped at the owner) — {detail}"
        )


# ----------------------------------------------------------- host simulation
# 64-bit free-list head word: (generation << 32) | head-page-index.
_IDX_MASK = (1 << 32) - 1
_EMPTY = _IDX_MASK          # index sentinel: empty list


def head_pack(gen: int, idx: int) -> int:
    return ((gen & _IDX_MASK) << 32) | (idx & _IDX_MASK)


def head_unpack(word: int) -> tuple[int, int]:
    return (word >> 32) & _IDX_MASK, word & _IDX_MASK


class HostPagePool:
    """The literal remote free-list: CAS on a (generation, head) word.

    Pop and push loop a compare-and-swap on the packed 64-bit head word;
    every successful CAS advances the generation, so the ABA interleaving
    (head A observed → A popped, B popped, A pushed back → stale CAS would
    still match a genless head) fails the tag compare instead of corrupting
    the list.  Refcounts are per-page fetch-and-add words; `release` frees
    at the 1 → 0 transition (the winner of the decrement race frees).

    AMO counts (`total_amos`) let tests assert the O(1)-expected-steps
    claim under low contention, like `locks_sim.LockWindow`.
    """

    def __init__(self, n_pages: int, page_words: int = 1, dtype=np.float32,
                 fabric=None, name: str = "heap", owner: int = 0):
        from repro.core.fabric import default_fabric

        if n_pages < 1 or n_pages >= _EMPTY:
            raise HeapError(f"bad n_pages {n_pages}")
        self.n_pages = n_pages
        self.pages = np.zeros((n_pages, page_words), dtype)
        self.next = np.full((n_pages,), _EMPTY, np.int64)
        self.gen = np.zeros((n_pages,), np.uint32)        # per-page ABA tag
        self.ref = [_AtomicWord() for _ in range(n_pages)]
        self.head = _AtomicWord()
        # The AMO words are registered as fabric banks: the default
        # in-process fabric operates on these exact `_AtomicWord`s (same
        # atomicity, same amo_count), the sim fabric interposes chaos
        # (spurious CAS contention) between the protocol and the words.
        self.owner = owner
        self.name = name
        self.fabric = default_fabric(fabric)
        self._bank_head = f"{name}.head"
        self._bank_ref = f"{name}.ref"
        self.fabric.register_words(self._bank_head, [self.head], owner=owner)
        self.fabric.register_words(self._bank_ref, self.ref, owner=owner)
        # build the initial list: 0 -> 1 -> ... -> n-1
        for i in range(n_pages - 1):
            self.next[i] = i + 1
        self.head.v = head_pack(0, 0)
        self.allocs = 0
        self.frees = 0

    @property
    def total_amos(self) -> int:
        return self.head.amo_count + sum(w.amo_count for w in self.ref)

    # ------------------------------------------------------------ alloc/free
    def alloc(self, origin: int = 0) -> Optional[int]:
        """Pop the head page (CAS loop); None when the pool is dry."""
        fab = self.fabric
        while True:
            old = fab.read_word(origin, self._bank_head, 0)
            gen, idx = head_unpack(old)
            if idx == _EMPTY:
                return None
            nxt = int(self.next[idx])
            new = head_pack(gen + 1, nxt)
            if fab.cas(origin, self._bank_head, 0, old, new) == old:
                self.gen[idx] += np.uint32(1)             # alloc bump
                self.ref[idx].v = 1
                self.allocs += 1
                tr = obs_trace.TRACER
                if tr.enabled:
                    # rid from the ambient request scope: page traffic joins
                    # the request's causal DAG without a signature change
                    tr.event("heap.alloc", rank=origin, pool=self.name,
                             page=idx, gen=int(self.gen[idx]),
                             rid=obs_causal.current_rid())
                return idx

    def free(self, idx: int, origin: int = 0) -> None:
        """Push a dead page back (CAS loop); generation advances again."""
        fab = self.fabric
        if not 0 <= idx < self.n_pages:
            raise HeapError(f"free of page {idx} outside pool")
        if fab.read_word(origin, self._bank_ref, idx) != 0:
            err = HeapError(f"free of live page {idx} (refcount > 0)")
            obs_flight.on_error(err, tag=self.name)
            raise err
        self.gen[idx] += np.uint32(1)                     # free bump
        while True:
            old = fab.read_word(origin, self._bank_head, 0)
            gen, head_idx = head_unpack(old)
            # next[idx] is single-writer: only the 1→0 release winner can
            # push idx (double-free raises), so no lock is needed — a
            # failed CAS simply re-reads the head and re-links.
            self.next[idx] = head_idx
            new = head_pack(gen + 1, idx)
            if fab.cas(origin, self._bank_head, 0, old, new) == old:
                self.frees += 1
                tr = obs_trace.TRACER
                if tr.enabled:
                    tr.event("heap.free", rank=origin, pool=self.name,
                             page=idx, gen=int(self.gen[idx]),
                             rid=obs_causal.current_rid())
                return

    # -------------------------------------------------------------- refcount
    def ref_add(self, idx: int, delta: int = 1, origin: int = 0) -> int:
        """Fetch-and-add on the page's refcount word; returns the old count.
        Sharing a dead page is a protocol bug and raises."""
        fab = self.fabric
        old = fab.fetch_add(origin, self._bank_ref, idx, delta)
        if delta > 0 and old == 0:
            fab.fetch_add(origin, self._bank_ref, idx, -delta)
            err = HeapError(f"ref_add on dead page {idx} (ABA hazard)")
            obs_flight.on_error(err, tag=self.name)
            raise err
        return old

    def release(self, idx: int, origin: int = 0) -> bool:
        """Decrement; the 1 → 0 winner pushes the page back.  True if freed."""
        fab = self.fabric
        old = fab.fetch_add(origin, self._bank_ref, idx, -1)
        if old <= 0:
            fab.fetch_add(origin, self._bank_ref, idx, 1)
            err = HeapError(f"release of dead page {idx} (double free)")
            obs_flight.on_error(err, tag=self.name)
            raise err
        if old == 1:
            self.free(idx, origin=origin)
            return True
        return False

    def pin(self, idx: int, origin: int = 0) -> int:
        """Pull-side liveness pin (rendezvous protocol, §16): one remote
        fetch-and-add before the puller issues its gets, so the source
        page cannot reach refcount 0 — and thus cannot be freed and
        reallocated — while the pull epoch is in flight.  Returns the
        page's current generation tag; the puller revalidates it with
        `tag_valid` after the data lands (a mismatch means the descriptor
        was stale *before* the pin took hold and the pull must retry).
        Raises on a dead page, exactly like `ref_add`."""
        self.ref_add(idx, 1, origin=origin)
        return self.tag(idx)

    def unpin(self, idx: int, tag: int, origin: int = 0) -> bool:
        """Drop a pull pin once the pulled bytes are consumed (or the pull
        is abandoned).  The tag must be the one `pin` returned — unpinning
        across a generation change means the pin was not actually covering
        the page the caller read.  True if this unpin freed the page."""
        if not self.tag_valid(idx, tag):
            err = HeapError(
                f"unpin of page {idx} with stale tag {tag} "
                f"(now {self.tag(idx)})")
            obs_flight.on_error(err, tag=self.name)
            raise err
        return self.release(idx, origin=origin)

    def tag(self, idx: int) -> int:
        """Current generation of a page — cache alongside the id."""
        return int(self.gen[idx])

    def tag_valid(self, idx: int, tag: int) -> bool:
        return 0 <= idx < self.n_pages and int(self.gen[idx]) == (tag & 0xFFFFFFFF)

    # ------------------------------------------------------------ inspection
    def free_count(self) -> int:
        """Walk the list (quiescent use only — tests, conservation)."""
        n = 0
        _, idx = head_unpack(self.head.v)
        while idx != _EMPTY and n <= self.n_pages:
            n += 1
            idx = int(self.next[idx])
        return n

    def live_count(self) -> int:
        return sum(1 for w in self.ref if w.v > 0)

    def conservation(self) -> dict:
        free, live = self.free_count(), self.live_count()
        return {
            "free": free,
            "live": live,
            "free_plus_live": free + live,
            "capacity": self.n_pages,
        }
