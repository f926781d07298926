"""Collective schedules composed from one-sided RMA ops (paper §4 motifs).

The paper demonstrates that application-level communication patterns (halo
exchange in MILC, slab exchange in FFT, DSDE) built on put/get + scalable
sync outperform message-passing formulations.  These schedules are that idea
packaged: every collective below is composed **only** of `repro.core.rma`
one-sided ops, epoch barriers, and (where an epoch issues several ops — the
halo exchange, the bidirectional ring step) epoch-scoped `repro.core.plan`
plans, and is a drop-in alternative to the native XLA collective.  The perf
layer (`parallel/overlap.py`) chooses between the native op and an RMA
schedule using the §3 performance models, and between XLA and Pallas
lowerings via the §8 backend dispatch.

All functions assume they are called inside ``shard_map``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


from . import plan as plan_mod, rma


Array = jax.Array


# ------------------------------------------------------------ ring schedules
def ring_all_gather(x: Array, axis: str, bidirectional: bool = True) -> Array:
    """All-gather via (p-1) one-sided ring puts; bidirectional uses 2 links.

    Returns [p, ...local] stacked in rank order.  This is the Bell/Nishtala
    overlap-friendly schedule the paper's FFT study builds on: each step's
    put can overlap with the consumer's compute on already-arrived shards
    (the fused version lives in `kernels/ring_matmul`).
    """
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    if p == 1:
        return x[None]

    out = jnp.zeros((p,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, me, 0)

    if not bidirectional:
        buf = x
        def body(i, carry):
            out, buf = carry
            buf = rma.put_shift(buf, +1, axis)  # receive from left
            src = (me - i - 1) % p
            out = lax.dynamic_update_index_in_dim(out, buf, src, 0)
            return out, buf
        out, _ = lax.fori_loop(0, p - 1, body, (out, buf))
        return out

    # bidirectional: half the shards travel each way
    fwd = bwd = x
    steps_f = (p - 1) - (p - 1) // 2
    steps_b = (p - 1) // 2

    def body(i, carry):
        out, fwd, bwd = carry
        # both directions of one ring step form one plan (an access epoch):
        # the permutations differ so they stay separate wire transfers, but
        # they share backend dispatch and raw/coalesced accounting
        step_plan = plan_mod.RmaPlan(axis)
        h_f = step_plan.put_shift(fwd, +1)
        h_b = step_plan.put_shift(bwd, -1)
        step_plan.flush()
        fwd, bwd = h_f.result(), h_b.result()
        src_f = (me - i - 1) % p
        src_b = (me + i + 1) % p
        out = lax.cond(
            i < steps_f,
            lambda o: lax.dynamic_update_index_in_dim(o, fwd, src_f, 0),
            lambda o: o,
            out,
        )
        out = lax.cond(
            i < steps_b,
            lambda o: lax.dynamic_update_index_in_dim(o, bwd, src_b, 0),
            lambda o: o,
            out,
        )
        return out, fwd, bwd

    out, _, _ = lax.fori_loop(0, max(steps_f, steps_b), body, (out, fwd, bwd))
    return out


def ring_reduce_scatter(
    x: Array, axis: str, op: Callable[[Array, Array], Array] = jnp.add
) -> Array:
    """Reduce-scatter via ring accumulate: x is [p, ...]; returns this rank's
    reduced shard.  Each step puts a partial to the right neighbor which
    accumulates it into its running slot — the slotted MPI_Accumulate
    pattern (§2.4) in ring order.
    """
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    if p == 1:
        return x[0]

    # step i: rank r forwards the growing partial for chunk (r-1-i) mod p to
    # its right neighbor; after p-1 steps rank r has received the partial for
    # chunk r carrying every other rank's contribution.
    def body(i, acc):
        idx = (me - 1 - i) % p
        chunk = lax.dynamic_index_in_dim(x, idx, 0, keepdims=False)
        outgoing = lax.cond(i == 0, lambda c, a: c, op, chunk, acc)
        return rma.put_shift(outgoing, +1, axis)

    acc = jnp.zeros_like(x[0])
    acc = lax.fori_loop(0, p - 1, body, acc)
    mine = lax.dynamic_index_in_dim(x, me, 0, keepdims=False)
    return op(mine, acc)


def all_reduce(x: Array, axis: str, op: Callable = jnp.add) -> Array:
    """RS + AG ring all-reduce over one axis, built purely on RMA puts."""
    p = jax.lax.axis_size(axis)
    if p == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % p
    flat = jnp.pad(flat, (0, pad))
    parts = flat.reshape(p, -1)
    shard = ring_reduce_scatter(parts, axis, op)
    full = ring_all_gather(shard, axis)
    return full.reshape(-1)[: x.size].reshape(x.shape)


def hierarchical_all_reduce(x: Array, inner_axis: str, outer_axis: str) -> Array:
    """Two-level all-reduce: in-pod RS → cross-pod AR → in-pod AG.

    The paper's intra-node (XPMEM) / inter-node (DMAPP) split lifted to the
    (data, pod) hierarchy: the expensive outer (DCN) axis only ever carries
    1/inner_size of the payload.
    """
    p = jax.lax.axis_size(inner_axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % p
    flat = jnp.pad(flat, (0, pad))
    parts = flat.reshape(p, -1)
    shard = ring_reduce_scatter(parts, inner_axis)           # in-pod
    shard = lax.psum(shard, outer_axis)                      # cross-pod (1/p bytes)
    full = ring_all_gather(shard, inner_axis)                # in-pod
    return full.reshape(-1)[: x.size].reshape(x.shape)


# ------------------------------------------------------------- halo exchange
def halo_exchange_1d(x: Array, halo: int, axis: str, dim: int = 0) -> Array:
    """Bidirectional halo exchange via one-sided puts (MILC §4.4 pattern).

    Returns x padded with `halo` remote rows on each side of `dim`
    (periodic).  Two puts, one PSCW-style epoch, O(k=2) messages — the
    configuration where the paper's model says PSCW beats fence.
    """
    lo = lax.slice_in_dim(x, 0, halo, axis=dim)
    hi = lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim], axis=dim)
    # one plan per halo epoch: two puts (O(k), k=2) recorded together and
    # flushed at the epoch close — the configuration where the paper's
    # model says PSCW beats fence
    ep = plan_mod.RmaPlan(axis)
    h_left = ep.put_shift(hi, +1)    # left neighbor's high rows
    h_right = ep.put_shift(lo, -1)   # right neighbor's low rows
    ep.flush()
    return jnp.concatenate([h_left.result(), x, h_right.result()], axis=dim)


def halo_exchange_nd(x: Array, halos: dict[str, int], axis_dims: dict[str, int]) -> Array:
    """Multi-axis halo exchange (4D MILC lattice): one 1-D exchange per axis."""
    for ax, h in halos.items():
        if h > 0:
            x = halo_exchange_1d(x, h, ax, dim=axis_dims[ax])
    return x


# ------------------------------------------------------------------ alltoall
def all_to_all(x: Array, axis: str) -> Array:
    """Personalized exchange: x[p, ...] block b goes to rank b."""
    return rma.put_all_to_all(x, axis)


def broadcast(x: Array, root: int, axis: str) -> Array:
    return rma.put_bcast(x, root, axis)
