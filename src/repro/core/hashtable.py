"""Distributed hashtable on one-sided RMA (paper §4.1).

The paper's motif for "big data and analytics": each rank owns a *local
volume* = fixed-size table + overflow heap, with next-free / last-inserted
pointers stored inline.  Inserts go to the owner of hash(key); collisions
chain into the overflow heap via CAS (UPC/MPI-3 versions) or active messages
(MPI-1 baseline).

SPMD adaptation: inserts are batched per epoch.  Routing items to owners is
a DSDE exchange (one-sided puts); the owner then applies the CAS-chain logic
*vectorized* over its received batch.  This preserves the paper's data
structure exactly (table + overflow heap + next-free pointer) while replacing
per-element remote CAS loops — which gang-scheduled TPUs cannot express —
with owner-side conflict resolution inside the same epoch.  Lookups are
one-sided gets (gather from the owner's volume, no owner compute).

It doubles as the framework's embedding-table / KV-store substrate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


from . import dsde, plan as plan_mod


Array = jax.Array
EMPTY = jnp.int64(-1)


class LocalVolume(NamedTuple):
    """One rank's shard: fixed table + overflow heap (paper Fig. 7a text)."""

    table_key: Array     # [table_size] int64, EMPTY if free
    table_val: Array     # [table_size] int64
    table_next: Array    # [table_size] int32 index into heap, -1 = end
    heap_key: Array      # [heap_size] int64
    heap_val: Array      # [heap_size]
    heap_next: Array     # [heap_size] int32
    next_free: Array     # [] int32 — the paper's next-free-cell pointer
    last_insert: Array   # [] int32 — most-recently-inserted heap cell


def make_volume(table_size: int, heap_size: int) -> LocalVolume:
    return LocalVolume(
        table_key=jnp.full((table_size,), EMPTY, jnp.int64),
        table_val=jnp.zeros((table_size,), jnp.int64),
        table_next=jnp.full((table_size,), -1, jnp.int32),
        heap_key=jnp.full((heap_size,), EMPTY, jnp.int64),
        heap_val=jnp.zeros((heap_size,), jnp.int64),
        heap_next=jnp.full((heap_size,), -1, jnp.int32),
        next_free=jnp.zeros((), jnp.int32),
        last_insert=jnp.full((), -1, jnp.int32),
    )


def hash_owner(keys: Array, p: int) -> Array:
    """Rank owning each key (Fibonacci multiplicative hash, x64-agnostic)."""
    h = (keys.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)) >> jnp.uint32(16)
    return (h % jnp.uint32(p)).astype(jnp.int32)


def hash_slot(keys: Array, table_size: int) -> Array:
    h = (keys.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)) >> jnp.uint32(13)
    return (h % jnp.uint32(table_size)).astype(jnp.int32)


def _owner_insert(vol: LocalVolume, keys: Array, vals: Array, valid: Array) -> LocalVolume:
    """Vectorized owner-side insert of a received batch (collision→heap).

    Sequential chain semantics are preserved with a fori_loop over the batch
    (the owner serializes its own volume, exactly like the CAS winner/loser
    resolution in the paper — but without remote retries).
    """
    table_size = vol.table_key.shape[0]
    heap_size = vol.heap_key.shape[0]
    slots = hash_slot(keys, table_size)

    def body(i, vol):
        k, v, s, ok = keys[i], vals[i], slots[i], valid[i]

        def do(vol):
            tk = vol.table_key[s]
            free = tk == EMPTY
            dup = tk == k

            def into_table(vol):
                return vol._replace(
                    table_key=vol.table_key.at[s].set(k),
                    table_val=vol.table_val.at[s].set(v),
                )

            def into_heap(vol):
                # losing thread acquires a new overflow cell by bumping
                # next_free (paper: atomic increment), then links it in at
                # the head of the chain (paper: second CAS on last-pointer).
                idx = vol.next_free
                ok_heap = idx < heap_size
                idxc = jnp.minimum(idx, heap_size - 1)
                old_head = vol.table_next[s]
                vol = vol._replace(
                    heap_key=vol.heap_key.at[idxc].set(jnp.where(ok_heap, k, vol.heap_key[idxc])),
                    heap_val=vol.heap_val.at[idxc].set(jnp.where(ok_heap, v, vol.heap_val[idxc])),
                    heap_next=vol.heap_next.at[idxc].set(jnp.where(ok_heap, old_head, vol.heap_next[idxc])),
                    table_next=vol.table_next.at[s].set(jnp.where(ok_heap, idxc, vol.table_next[s])),
                    next_free=vol.next_free + jnp.where(ok_heap, 1, 0).astype(jnp.int32),
                    last_insert=jnp.where(ok_heap, idxc, vol.last_insert).astype(jnp.int32),
                )
                return vol

            def overwrite(vol):  # same key in table: update value
                return vol._replace(table_val=vol.table_val.at[s].set(v))

            return lax.cond(free, into_table, lambda vv: lax.cond(dup, overwrite, into_heap, vv), vol)

        return lax.cond(ok, do, lambda vv: vv, vol)

    return lax.fori_loop(0, keys.shape[0], body, vol)


def insert_epoch(
    vol: LocalVolume,
    keys: Array,    # [n] int64 this rank's keys to insert
    vals: Array,    # [n] int64
    axis: str,
    capacity_per_pair: int,
) -> tuple[LocalVolume, Array]:
    """One insert epoch: route to owners (DSDE one-sided puts) + owner apply.

    Returns (updated volume, number of items this rank dropped to capacity).
    """
    p = jax.lax.axis_size(axis)
    owners = hash_owner(keys, p)
    items = jnp.stack([keys, vals], axis=1)  # [n, 2] payload
    res = dsde.exchange_accumulate(items, owners, axis, capacity_per_pair)
    rk = res.recv_data[:, 0]
    rv = res.recv_data[:, 1]
    vol = _owner_insert(vol, rk, rv, res.recv_valid)
    return vol, res.sent_dropped


def lookup_epoch(vol: LocalVolume, keys: Array, axis: str, capacity_per_pair: int) -> tuple[Array, Array]:
    """One-sided lookup: get the owner's chain for each key.

    Implemented as DSDE of queries + owner-side vectorized probe + DSDE of
    answers back (two one-sided epochs — the MPI-3 get-based formulation).
    Returns (values, found) aligned with `keys`.
    """
    p = jax.lax.axis_size(axis)
    n = keys.shape[0]
    owners = hash_owner(keys, p)
    qid = jnp.arange(n, dtype=jnp.int64)
    queries = jnp.stack([keys, qid], axis=1)
    res = dsde.exchange_accumulate(queries, owners, axis, capacity_per_pair)
    rkeys = res.recv_data[:, 0]
    rqid = res.recv_data[:, 1]

    # vectorized probe: table slot, then walk the chain a bounded number of steps
    table_size = vol.table_key.shape[0]
    slots = hash_slot(rkeys, table_size)
    found = vol.table_key[slots] == rkeys
    vals = jnp.where(found, vol.table_val[slots], 0)
    nxt = vol.table_next[slots]

    def walk(_, carry):
        vals, found, nxt = carry
        idx = jnp.maximum(nxt, 0)
        hit = (nxt >= 0) & (vol.heap_key[idx] == rkeys) & (~found)
        vals = jnp.where(hit, vol.heap_val[idx], vals)
        found = found | hit
        nxt = jnp.where(nxt >= 0, vol.heap_next[idx], -1)
        return vals, found, nxt

    max_chain = vol.heap_key.shape[0]
    vals, found, _ = lax.fori_loop(0, max_chain, walk, (vals, found, nxt))

    # answers fly back one-sided: route by origin rank encoded in slots
    # slot layout of exchange_accumulate is [src_rank, cap] ordered; the
    # answer payload and its validity mask share one fused transfer (§8)
    cap = res.recv_data.shape[0] // p
    ans = jnp.stack([rqid, vals, found.astype(jnp.int64)], axis=1).reshape(p, cap, 3)
    hplan = plan_mod.RmaPlan(axis)
    h_back = hplan.put_all_to_all(ans, kind="puts")
    h_bval = hplan.put_all_to_all(res.recv_valid.reshape(p, cap), kind=None)
    hplan.flush()
    back = h_back.result().reshape(p * cap, 3)
    back_valid = h_bval.result().reshape(-1)

    out_vals = jnp.zeros((n,), jnp.int64)
    out_found = jnp.zeros((n,), jnp.bool_)
    idx = jnp.where(back_valid, back[:, 0], n).astype(jnp.int32)
    out_vals = out_vals.at[idx].set(back[:, 1], mode="drop")
    out_found = out_found.at[idx].set(back[:, 2].astype(jnp.bool_), mode="drop")
    return out_vals, out_found
