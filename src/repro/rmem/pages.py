"""Paged remote KV-cache with hash-keyed prefix sharing (DESIGN.md §10.3-10.5).

`PagedKV` turns the serving stack page-granular: a request's KV cache is a
list of fixed-size *token pages* living in decode-rank page pools
(`rmem.heap`), and the unit that crosses the wire is a **page-table entry**
— an (owner, page id) int32 pair — not the page payload.  Identical prompt
prefixes resolve to the same remote pages:

  * every page is keyed by the hash of its token content; a per-owner
    prefix index maps key → (owner, page id, generation tag);
  * an index hit *shares* the page — one CAS-style refcount increment
    (`HostPagePool.ref_add` / `heap.ref_update(+1)`), zero payload bytes on
    the wire;
  * a miss allocates from the owner's remote free list and ships the page
    once; every later request with the same prefix rides it for free;
  * release decrements; the owner frees at the 1 → 0 transition (§5.1 lock
    discipline, CAS edition) — so the conservation invariant
    free + live == capacity survives arbitrary sharing.

Requests are routed to their decode rank by consistent hash of the FIRST
page key (prefix-affinity routing): identical prefixes always land on the
same owner, so the decoder's gather is pool-local.  Cross-rank gathers (a
page table referencing another rank's pool) go through one-sided gets —
the XLA path below, or the fused `kernels.paged_gather` Pallas trio.

Elastic migration (`migrate_from`): when an owner leaves, its live pages
are re-allocated on survivors (RMA get + put per page), refcounts are
transferred verbatim, page tables and the prefix index are rewritten, and
pages whose key already exists at the destination are *merged* (refcounts
added) instead of duplicated.  `ft.elastic` wraps this as policy.
"""

from __future__ import annotations

import zlib
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod

from . import heap

Array = jax.Array

# page-table wire format: one int32 pair per page
ENTRY_OWNER, ENTRY_PAGE = range(2)
ENTRY_WORDS = 2


class PageRef(NamedTuple):
    """A page-table entry plus its ABA tag (the tag never hits the wire —
    it guards host-cached descriptors across free/realloc)."""

    owner: int
    page_id: int
    tag: int


def page_key(tokens) -> bytes:
    """Content hash key of one token page (position-independent for the
    embedding-KV model: a page's KV depends only on its tokens)."""
    return np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()


def split_pages(tokens, page_tokens: int) -> list:
    """Split a prompt into fixed-size token pages (must divide evenly)."""
    toks = np.asarray(tokens, np.int32)
    if toks.size % page_tokens:
        raise heap.HeapError(
            f"prompt length {toks.size} not a multiple of page_tokens {page_tokens}")
    return [toks[i : i + page_tokens] for i in range(0, toks.size, page_tokens)]


def route_owner(key: bytes, owners: Sequence[int]) -> int:
    """Rendezvous (highest-random-weight) routing: identical prefixes →
    identical owner, AND adding/removing an owner only reroutes the keys
    that move to/from it — modulo hashing would reshuffle nearly every key
    on a join and destroy the prefix index's hit rate."""
    return max(owners, key=lambda r: (zlib.crc32(key + r.to_bytes(4, "little")), r))


# =========================================================================
# host coordinator: per-owner pools + prefix index + page tables
# =========================================================================
class PagedKVPool:
    """Host-side paged-KV coordinator over per-owner `HostPagePool`s.

    This is the scheduler's mirror of the decode ranks' device pools — the
    same split as `HostFlowChannel` vs the SPMD flow state: allocation,
    prefix dedup, refcounts, and migration run host-side on the literal
    CAS free-lists, while page *payloads* live in the device pool arrays
    the SPMD step scatters into (`scatter_pages`).
    """

    def __init__(self, owners: Sequence[int], n_pages: int,
                 page_words: int = 1, dtype=np.float32, fabric=None):
        if not owners:
            raise heap.HeapError("need at least one owner rank")
        self.owners = list(owners)
        self.n_pages = n_pages
        self.page_words = page_words
        self.dtype = dtype
        # optional shared host transport (core.fabric): every owner pool's
        # AMO words live on it, so the sim can chaos-schedule the whole
        # paged-KV protocol; default is one in-process fabric per pool,
        # exactly the pre-fabric behavior
        self.fabric = fabric
        self._pool_gen = 0              # unique bank names across re-joins
        self.pools = {r: self._new_pool(r) for r in self.owners}
        # prefix index is per owner: sharing is only sound when the hit
        # lives where the request is routed (decoder-local gather)
        self.index: dict[tuple[int, bytes], PageRef] = {}
        self.rev: dict[tuple[int, int], bytes] = {}
        self.page_tables: dict[int, list[PageRef]] = {}
        self.hits = 0
        self.misses = 0
        self.dry = 0

    def _new_pool(self, rank: int) -> "heap.HostPagePool":
        self._pool_gen += 1
        return heap.HostPagePool(
            self.n_pages, self.page_words, self.dtype, fabric=self.fabric,
            name=f"kv{rank}.{self._pool_gen}", owner=rank)

    # ------------------------------------------------------------- routing
    def route(self, first_key: bytes) -> int:
        return route_owner(first_key, self.owners)

    # ------------------------------------------------------------- acquire
    def acquire(self, owner: int, key: bytes) -> Optional[tuple[PageRef, bool]]:
        """One page for `key` at `owner`: (ref, shared).  A prefix-index hit
        bumps the refcount (shared=True, no payload wire); a miss pops the
        owner's free list (shared=False — caller must ship the payload).
        None when the owner's pool is dry (caller defers the request)."""
        ref = self.index.get((owner, key))
        if ref is not None:
            self.pools[owner].ref_add(ref.page_id, 1)
            self.hits += 1
            return ref, True
        pid = self.pools[owner].alloc()
        if pid is None:
            self.dry += 1
            return None
        ref = PageRef(owner, pid, self.pools[owner].tag(pid))
        self.index[(owner, key)] = ref
        self.rev[(owner, pid)] = key
        self.misses += 1
        return ref, False

    def release_ref(self, ref: PageRef) -> bool:
        """Refcount decrement; the 1 → 0 winner frees the page and retires
        its index entry.  True if the page was freed."""
        freed = self.pools[ref.owner].release(ref.page_id)
        if freed:
            key = self.rev.pop((ref.owner, ref.page_id), None)
            if key is not None:
                self.index.pop((ref.owner, key), None)
        return freed

    # ---------------------------------------------------------- page tables
    def table_set(self, rid: int, refs: list[PageRef]) -> None:
        if rid in self.page_tables:
            raise heap.HeapError(f"request {rid} already has a page table")
        self.page_tables[rid] = list(refs)

    def table_release(self, rid: int) -> list[PageRef]:
        """Release every page a finished request referenced; returns the
        refs actually freed (refcount hit zero)."""
        refs = self.page_tables.pop(rid)
        return [ref for ref in refs if self.release_ref(ref)]

    def table_entries(self, rid: int) -> np.ndarray:
        """[n_pages_of_request, 2] int32 — the wire format rows."""
        return np.asarray(
            [[r.owner, r.page_id] for r in self.page_tables[rid]], np.int32)

    # ------------------------------------------------------------ invariants
    def conservation(self) -> dict:
        per = {r: pool.conservation() for r, pool in self.pools.items()}
        return {
            "per_owner": per,
            "ok": all(c["free_plus_live"] == c["capacity"] for c in per.values()),
        }

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "dry": self.dry,
            "hit_rate": self.hits / max(self.hits + self.misses, 1),
            "live_pages": {r: p.live_count() for r, p in self.pools.items()},
        }

    # ------------------------------------------------------------- elastic
    def add_owner(self, rank: int) -> None:
        """Rank join: bring up an empty pool and add it to the routing set."""
        if rank in self.pools:
            raise heap.HeapError(f"rank {rank} already owns a pool")
        self.pools[rank] = self._new_pool(rank)
        self.owners.append(rank)

    def migrate_from(self, leaving: int) -> dict:
        """Rank leave: move every live page off `leaving` onto survivors.

        Per live page: one RMA get (read the page + its refcount from the
        leaving rank) + one put (write it into a survivor's freshly
        allocated page), refcount transferred verbatim.  If the survivor
        already indexes the same key, the two pages are MERGED (refcounts
        added) — migration is also a dedup pass.  Page tables and the
        prefix index are rewritten; the leaving pool is dropped whole.
        """
        if leaving not in self.pools:
            raise heap.HeapError(f"rank {leaving} owns no pool")
        if len(self.owners) < 2:
            raise heap.HeapError("cannot migrate from the last owner")
        src = self.pools.pop(leaving)
        self.owners.remove(leaving)

        mapping: dict[tuple[int, int], PageRef] = {}
        moved = merged = 0
        for pid in range(src.n_pages):
            rc = int(src.ref[pid].v)
            if rc <= 0:
                continue
            key = self.rev.pop((leaving, pid), None)
            target = self.route(key) if key is not None else self.owners[0]
            existing = self.index.get((target, key)) if key is not None else None
            if existing is not None:
                # survivor already holds this content: merge refcounts
                self.pools[target].ref[existing.page_id].fetch_add(rc)
                mapping[(leaving, pid)] = existing
                merged += 1
                continue
            npid = self.pools[target].alloc()
            if npid is None:
                # spill to any survivor with capacity.  The spilled entry is
                # indexed under the SPILL owner: requests routed there by
                # their first page can still share it, but requests whose
                # routing points at the (full) rendezvous owner will store a
                # second copy — a capacity trade, never a correctness one.
                for r in self.owners:
                    npid = self.pools[r].alloc()
                    if npid is not None:
                        target = r
                        break
            if npid is None:
                raise heap.HeapError(
                    f"no survivor capacity for live page ({leaving}, {pid})")
            # the get+put payload copy; refcount transferred verbatim
            self.pools[target].pages[npid] = src.pages[pid]
            self.pools[target].ref[npid].v = rc
            nref = PageRef(target, npid, self.pools[target].tag(npid))
            if key is not None:
                self.index[(target, key)] = nref
                self.rev[(target, npid)] = key
            mapping[(leaving, pid)] = nref
            moved += 1

        # drop the leaving rank's remaining index entries (all dead pages)
        self.index = {k: v for k, v in self.index.items() if k[0] != leaving}
        for rid, refs in self.page_tables.items():
            self.page_tables[rid] = [
                mapping[(ref.owner, ref.page_id)] if ref.owner == leaving else ref
                for ref in refs
            ]
        return {"moved": moved, "merged": merged, "mapping": mapping}


# =========================================================================
# SPMD data plane: scatter novel pages, gather page-table rows
# =========================================================================
def scatter_pages(axis: str, pool: Array, payload: Array, slot: Array,
                  dest: Array) -> Array:
    """Write pages into remote pools (collective; inside shard_map).

    pool [n_pages, *ps] (local view), payload [S, *ps], slot/dest [S] int32
    (-1 = no page in that staging slot).  Page payloads and their target
    slots ride ONE fused a2a wire transfer (plan-aggregated), the owner
    scatters rows into its pool — the prefill → decoder-pool direct write.
    """
    p = jax.lax.axis_size(axis)
    n_pages = pool.shape[0]
    S = slot.shape[0]
    flat = payload.reshape(S, -1).astype(pool.dtype)
    valid = (dest >= 0) & (dest < p) & (slot >= 0) & (slot < n_pages)
    drow = jnp.where(valid, dest, p).astype(jnp.int32)   # p = drop row
    j = jnp.arange(S, dtype=jnp.int32)
    send_pay = jnp.zeros((p, S, flat.shape[1]), pool.dtype).at[drow, j].set(
        flat, mode="drop")
    send_slot = jnp.full((p, S), -1, jnp.int32).at[drow, j].set(
        jnp.where(valid, slot, -1), mode="drop")

    plan = plan_mod.RmaPlan(axis)
    h_pay = plan.put_all_to_all(send_pay, kind="puts")
    h_slot = plan.put_all_to_all(send_slot, kind=None)   # rider: same wire
    plan.flush(aggregate=True)
    recv_pay = h_pay.result().reshape(p * S, -1)
    recv_slot = h_slot.result().reshape(p * S)

    rows = jnp.where(recv_slot >= 0, recv_slot, n_pages)
    flat_pool = pool.reshape(n_pages, -1).at[rows].set(recv_pay, mode="drop")
    return flat_pool.reshape(pool.shape)


def gather_pages(axis: str, pool: Array, entries: Array,
                 valid: Array) -> Array:
    """Pull pages from their owners' pools by descriptor (collective;
    inside shard_map) — the rendezvous data path (§16).

    pool [n_pages, *ps] (local view), entries [m, ppb, 2] int32
    ((owner, page_id) rows, the published descriptor), valid [m] bool.
    The *consumer* initiates: one fused get carries the wanted-id lists to
    every owner, the owners' packed replies come back on a second fused
    get — two wire transfers total, batched across every (request, page)
    pair, never per-page round trips.  Returns [m, ppb, *ps] with invalid
    requests zeroed.  Runs on all ranks (SPMD): ranks that want nothing
    send empty id lists but still serve replies from their pool.
    """
    p = jax.lax.axis_size(axis)
    n_pages = pool.shape[0]
    m, ppb = entries.shape[0], entries.shape[1]
    S = m * ppb                                          # flat pull slots
    owner = entries[..., ENTRY_OWNER].reshape(S)
    pid = entries[..., ENTRY_PAGE].reshape(S)
    want = (jnp.repeat(valid, ppb) & (owner >= 0) & (owner < p)
            & (pid >= 0) & (pid < n_pages))
    orow = jnp.where(want, owner, p).astype(jnp.int32)   # p = drop row
    j = jnp.arange(S, dtype=jnp.int32)
    # slot j of row d: the page id I want from owner d (or -1)
    send_ids = jnp.full((p, S), -1, jnp.int32).at[orow, j].set(
        jnp.where(want, pid, -1), mode="drop")

    plan = plan_mod.RmaPlan(axis)
    h_ids = plan.put_all_to_all(send_ids, kind="gets")   # id lists out
    plan.flush(aggregate=True)
    recv_ids = h_ids.result().reshape(p, S)              # [requester, slot]

    # serve every requester from my pool; -1 slots reply zero pages
    flat_pool = pool.reshape(n_pages, -1)
    reply = gather_local(flat_pool, recv_ids)            # [p, S, w]

    plan = plan_mod.RmaPlan(axis)
    h_pay = plan.put_all_to_all(reply, kind="gets")      # packed replies
    plan.flush(aggregate=True)
    recv_pay = h_pay.result().reshape(p, S, -1)          # [owner, slot, w]

    osafe = jnp.clip(orow, 0, p - 1)
    out = recv_pay[osafe, j]                             # [S, w]
    out = jnp.where(want[:, None], out, jnp.zeros_like(out))
    return out.reshape((m, ppb) + pool.shape[1:])


def gather_local(pool: Array, ids: Array) -> Array:
    """Owner-local page-table gather: pool [n_pages, *ps], ids [...k] int32
    (-1 = zero page).  No communication — the decoder reading its own pool."""
    n_pages = pool.shape[0]
    safe = jnp.clip(ids, 0, n_pages - 1)
    out = pool[safe]
    mask = (ids >= 0).reshape(ids.shape + (1,) * (out.ndim - ids.ndim))
    return jnp.where(mask, out, jnp.zeros_like(out))


def gather_shift(pool: Array, ids: Array, shift: int, axis: str) -> Array:
    """Cross-rank page gather via one-sided gets (XLA path): each rank
    fetches rows `ids` from rank (r+shift)'s pool.  The Pallas equivalent
    (one fused transfer) is `repro.kernels.paged_gather`."""
    from repro.kernels.paged_gather import ref as pg_ref

    out = pg_ref.paged_gather_ref(pool, jnp.maximum(ids, 0), shift, axis)
    mask = (ids >= 0).reshape(ids.shape + (1,) * (out.ndim - ids.ndim))
    return jnp.where(mask, out, jnp.zeros_like(out))
