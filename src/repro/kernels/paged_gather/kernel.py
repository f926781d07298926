"""Pallas TPU kernel: fused paged-KV gather (DESIGN.md §10.5).

`paged_gather` collects k scattered pages from a remote rank's page pool
into one contiguous attention-ready block with ONE payload transfer:

  1. **request** — the origin DMAs its page-id list to the target (an
     8-byte-per-page index write; ≙ the page-table lookup get);
  2. **pack** — the target copies the requested rows from its pool into a
     contiguous staging buffer (local VMEM copies, HBM-bandwidth bound);
  3. **reply** — one remote DMA ships the packed [k, w] block back to the
     origin's output buffer.

Shipping k pages therefore costs 2 wire messages (ids + packed block)
instead of k row DMAs — the fused-transfer property `PerfModel
.p_paged_gather` charges.  Under SPMD the "target" is just every rank
running the same program for its `back` neighbor (rank r serves the
requests of r-shift while its own land at r+shift), the same symmetric-get
trick `rmaq.kernel.queue_push` uses for its counter fetch.

Out-of-range ids (including -1 padding) clamp to row 0; callers mask
(`rmem.pages.gather_shift` zeroes masked rows).  Interpret-mode discharge
needs a static schedule, so the pack loop always copies k rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import neighbor_barrier as _neighbor_barrier


def _paged_gather_kernel(axis, n, shift, n_pages,
                         pages_ref, ids_ref, o_ref,
                         req_ids, pack,
                         isend, irecv, psend, precv, notify_sem):
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)       # whose pool I read
    back = jax.lax.rem(me - shift + n, n)      # who reads MY pool
    k = ids_ref.shape[0]

    _neighbor_barrier(axis, n)

    # ---- 1. request: my page ids fly to my target's scratch; symmetric
    # issue means my own scratch receives `back`'s ids (the lookup get)
    req = pltpu.make_async_remote_copy(
        src_ref=ids_ref, dst_ref=req_ids,
        send_sem=isend, recv_sem=irecv,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    req.start()
    req.wait()                                  # my scratch holds back's ids

    # ---- 2. pack: copy the requested pool rows contiguously (local)
    def pack_row(j, _):
        idx = jnp.clip(req_ids[j], 0, n_pages - 1)
        pack[pl.ds(j, 1)] = pages_ref[pl.ds(idx, 1)]
        return 0

    jax.lax.fori_loop(0, k, pack_row, 0)

    # ---- 3. reply: ONE remote DMA of the packed block to the requester
    rep = pltpu.make_async_remote_copy(
        src_ref=pack, dst_ref=o_ref,
        send_sem=psend, recv_sem=precv,
        device_id=(back,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    rep.start()
    rep.wait()                                  # my o_ref holds MY pages

    pltpu.semaphore_signal(notify_sem, inc=1,
                           device_id=(back,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(notify_sem, 1)
    _neighbor_barrier(axis, n)       # epoch close


def paged_gather_pallas(pages: jax.Array, ids: jax.Array, shift: int,
                        axis: str, n: int, *, interpret: bool,
                        collective_id: int = 6) -> jax.Array:
    """pages [n_pages, w], ids [k] int32 → [k, w]: rows `ids` of rank
    (me+shift)'s pool, gathered contiguously in one fused reply transfer."""
    n_pages, w = pages.shape
    k = ids.shape[0]
    return pl.pallas_call(
        functools.partial(_paged_gather_kernel, axis, n, shift, n_pages),
        out_shape=jax.ShapeDtypeStruct((k, w), pages.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((k,), jnp.int32),        # incoming request ids
            pltpu.VMEM((k, w), pages.dtype),    # packed reply block
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(pages, ids)
