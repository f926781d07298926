"""Pallas TPU kernels: fused paged attention (DESIGN.md §13).

Decode's hot loop previously ran in two kernels: `paged_gather` packed the
request's scattered KV pages into a contiguous [k·pt, 2, hd] reply buffer,
then `flash_attention` attended over the packed copy — a full
materialize-then-attend staging buffer per decode step.  The fused kernels
here walk the page-id list directly and fold each page into an
online-softmax accumulator the moment it lands, so the packed block never
exists; the only staging is a **two-page window** (the classic
double-buffer), shrinking decode's intermediate memory from O(seq) to
O(page · 2) — the paper's copy-elimination argument applied to attention.

Two variants share the math (m/l/acc carried across pages, flash-style):

* `paged_attention_pallas` — batched, pool-local.  The page-id table is a
  **scalar-prefetch operand**: Pallas reads ids[i, j] on the host side of
  the pipeline and DMAs pool page ids[i, j] as the (i, j) grid step's KV
  block, i.e. the page-table walk IS the BlockSpec index_map, and the
  pipeline's prologue fetch of step (i, j+1) overlapping step (i, j)'s
  compute is exactly the double-buffered staging window.  This is the
  vLLM paged-attention pattern and the variant `serve.disagg` calls on
  its decoder pools (prefix-affinity routing makes every page local).

* `paged_attention_shift_pallas` — cross-rank.  Symmetric SPMD over the
  ring like `paged_gather`: ranks swap id lists (one DMA), then the owner
  STREAMS each requested page as its own remote DMA into the requester's
  2-slot stage scratch, alternating slots; the requester accumulates page
  j while page j+1 is in flight.  k pages cost 1 + k wire messages versus
  the gather's 2 — the crossover `PerfModel.select_paged_attend` prices —
  but the O(k·pt) pack buffer and its HBM round-trip are gone.

Masking: page id -1 ⇒ the page's tokens are excluded (score NEG_INF);
the schedule is static so the DMA still moves a clamped row, only the
scores are masked — same discipline as `paged_gather`'s always-k pack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import neighbor_barrier as _neighbor_barrier

NEG_INF = -1e30


def _accumulate(s, valid, v_pg, m_ref, l_ref, acc_ref):
    """One online-softmax step: fold scores s [Sq, pt] (pre-masked entries
    NEG_INF, `valid` the same mask) and values v_pg [pt, hd] into the
    running (m, l, acc) state.  Fully-masked steps leave l at 0 so the
    finalize division yields zeros — never NaN."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v_pg
    m_ref[...] = m_new


# --------------------------------------------------------------- local/batched
def _paged_attention_kernel(causal: bool, pt: int, Sq: int, Sk: int,
                            ids_ref, q_ref, kv_ref, o_ref,
                            m_ref, l_ref, acc_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [Sq, hd]
    k_pg = kv_ref[0, :, 0].astype(jnp.float32)          # [pt, hd]
    v_pg = kv_ref[0, :, 1].astype(jnp.float32)
    s = jax.lax.dot_general(q, k_pg, (((1,), (1,)), ((), ())))  # [Sq, pt]

    valid = jnp.full((Sq, pt), ids_ref[i, j] >= 0)
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (Sq, pt), 0)
        k_pos = j * pt + jax.lax.broadcasted_iota(jnp.int32, (Sq, pt), 1)
        valid &= k_pos <= q_pos + (Sk - Sq)
    s = jnp.where(valid, s, NEG_INF)
    _accumulate(s, valid, v_pg, m_ref, l_ref, acc_ref)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, kv_pages: jax.Array,
                           ids: jax.Array, scale: float | None = None,
                           causal: bool = False,
                           *, interpret: bool) -> jax.Array:
    """q [m, Sq, hd], kv_pages [n_pages, pt, 2, hd], ids [m, k] int32
    → [m, Sq, hd].  Grid (m, k) with pages innermost/arbitrary; the ids
    table is scalar-prefetched so page ids[i, j]'s DMA is issued straight
    off the table — no gather, no packed intermediate."""
    m, Sq, hd = q.shape
    n_pages, pt = kv_pages.shape[0], kv_pages.shape[1]
    k = ids.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    qs = (q * jnp.asarray(scale, q.dtype)).astype(q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, k),
        in_specs=[
            pl.BlockSpec((1, Sq, hd), lambda i, j, ids: (i, 0, 0)),
            # the page-table walk: block (i, j) is pool page ids[i, j]
            pl.BlockSpec(
                (1, pt, 2, hd),
                lambda i, j, ids: (jnp.clip(ids[i, j], 0, n_pages - 1),
                                   0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Sq, hd), lambda i, j, ids: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Sq,), jnp.float32),
            pltpu.VMEM((Sq,), jnp.float32),
            pltpu.VMEM((Sq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attention_kernel, causal, pt, Sq, k * pt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, Sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(ids, qs, kv_pages)


# ----------------------------------------------------------------- cross-rank
def _paged_attention_shift_kernel(axis, n, shift, n_pages, pt, Sq, causal,
                                  scale,
                                  kv_ref, ids_ref, q_ref, o_ref,
                                  req_ids, send0, send1, stage0, stage1,
                                  m_ref, l_ref, acc_ref,
                                  isend, irecv, psend0, precv0,
                                  psend1, precv1, notify_sem):
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)       # whose pool I read
    back = jax.lax.rem(me - shift + n, n)      # who reads MY pool
    k = ids_ref.shape[0]
    Sk = k * pt

    _neighbor_barrier(axis, n)

    # ---- 1. request: id lists swap places around the ring (one DMA); my
    # scratch ends up holding `back`'s wanted page ids
    req = pltpu.make_async_remote_copy(
        src_ref=ids_ref, dst_ref=req_ids,
        send_sem=isend, recv_sem=irecv,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    req.start()
    req.wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...].astype(jnp.float32) * scale          # [Sq, hd]

    # ---- 2. stream: page j ships as its OWN remote DMA into the
    # requester's 2-slot stage window; slot parity alternates so page j+1
    # can land while page j is being folded into (m, l, acc).  No packed
    # reply buffer exists on either side.  The loop is statically
    # unrolled: interpret-mode discharge needs a static schedule, and k is
    # a handful of pages (a request's block), not a sequence length.
    sends = (send0, send1)
    stages = (stage0, stage1)
    sems = ((psend0, precv0), (psend1, precv1))
    for j in range(k):
        slot = j % 2
        idx = jnp.clip(req_ids[j], 0, n_pages - 1)
        sends[slot][pl.ds(0, 1)] = kv_ref[pl.ds(idx, 1)]
        rep = pltpu.make_async_remote_copy(
            src_ref=sends[slot], dst_ref=stages[slot],
            send_sem=sems[slot][0], recv_sem=sems[slot][1],
            device_id=(back,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rep.start()
        rep.wait()                      # symmetric: MY page j has landed

        k_pg = stages[slot][0, :, 0].astype(jnp.float32)    # [pt, hd]
        v_pg = stages[slot][0, :, 1].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_pg, (((1,), (1,)), ((), ())))
        valid = jnp.full((Sq, pt), ids_ref[j] >= 0)
        if causal:
            q_pos = jax.lax.broadcasted_iota(jnp.int32, (Sq, pt), 0)
            k_pos = j * pt + jax.lax.broadcasted_iota(jnp.int32, (Sq, pt), 1)
            valid &= k_pos <= q_pos + (Sk - Sq)
        s = jnp.where(valid, s, NEG_INF)
        _accumulate(s, valid, v_pg, m_ref, l_ref, acc_ref)

    o_ref[...] = (acc_ref[...]
                  / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)

    pltpu.semaphore_signal(notify_sem, inc=1,
                           device_id=(back,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(notify_sem, 1)
    _neighbor_barrier(axis, n)       # epoch close


def paged_attention_shift_pallas(q: jax.Array, kv_pages: jax.Array,
                                 ids: jax.Array, shift: int,
                                 axis: str, n: int,
                                 scale: float | None = None,
                                 causal: bool = False,
                                 *, interpret: bool,
                                 collective_id: int = 7) -> jax.Array:
    """q [Sq, hd], kv_pages [n_pages, pt, 2, hd], ids [k] int32 →
    [Sq, hd]: attend over pages `ids` of rank (me+shift)'s pool, streamed
    page-by-page through a 2-slot staging window."""
    n_pages, pt = kv_pages.shape[0], kv_pages.shape[1]
    Sq, hd = q.shape
    k = ids.shape[0]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    page_stage = pltpu.VMEM((1, pt, 2, hd), kv_pages.dtype)
    return pl.pallas_call(
        functools.partial(_paged_attention_shift_kernel, axis, n, shift,
                          n_pages, pt, Sq, causal, scale),
        out_shape=jax.ShapeDtypeStruct((Sq, hd), q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((k,), jnp.int32),        # incoming request ids
            page_stage, page_stage,             # producer-side send slots
            page_stage, page_stage,             # my 2-page stage window
            pltpu.VMEM((Sq,), jnp.float32),     # online-softmax m
            pltpu.VMEM((Sq,), jnp.float32),     # online-softmax l
            pltpu.VMEM((Sq, hd), jnp.float32),  # online-softmax acc
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(kv_pages, ids, q)
