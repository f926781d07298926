"""Pallas TPU kernels for one-sided RMA: put / get / accumulate / ring shift.

This is the paper's §2.4 mapped onto the TPU's actual RDMA engine:
``pltpu.make_async_remote_copy`` issues an inter-chip DMA with explicit
send/recv semaphores — semantically identical to ``dmapp_put_nbi`` +
completion handle.  The MPI surface maps as:

    MPI_Put            rdma.start()                  (nonblocking put)
    MPI_Win_flush      rdma.wait()                   (remote completion)
    MPI_Win_fence      barrier semaphore signal/wait (gsync + barrier)
    MPI_Win_post/start semaphore_signal / semaphore_wait on the neighbor
    MPI_Accumulate     put into the origin's private slot + owner reduce

All kernels run under ``shard_map`` with a named mesh axis; device ids are
logical positions on that axis.  Validated in interpret mode
(`pltpu.InterpretParams`) on CPU; compiled path targets TPU v5e (tiles are
(8,128)-aligned by construction — callers pad).
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import neighbor_barrier as _neighbor_barrier


# ------------------------------------------------------------------ put
def _put_shift_kernel(axis: str, n: int, shift: int, x_ref, o_ref, send_sem, recv_sem):
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)
    _neighbor_barrier(axis, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(dst,), device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma.start()          # MPI_Put (nonblocking)
    rdma.wait()           # MPI_Win_flush (remote completion)


def put_shift_pallas(x: jax.Array, shift: int, axis: str, n: int,
                     *, interpret: bool, collective_id: int = 0) -> jax.Array:
    """One-sided ring put: send my shard to rank (me+shift) mod n.

    Call inside shard_map; returns what was put into this rank's window.
    """
    return pl.pallas_call(
        functools.partial(_put_shift_kernel, axis, n, shift),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)


# ------------------------------------------------------------------ get
def _get_kernel(axis: str, n: int, src_shift: int, x_ref, o_ref, send_sem, recv_sem):
    """Get = the symmetric put issued by the (SPMD) source rank."""
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me - src_shift + n, n)   # I am the source for dst
    _neighbor_barrier(axis, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(dst,), device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma.start()
    rdma.wait()


def get_shift_pallas(x: jax.Array, src_shift: int, axis: str, n: int,
                     *, interpret: bool, collective_id: int = 0) -> jax.Array:
    """One-sided get from rank (me+src_shift) mod n."""
    return pl.pallas_call(
        functools.partial(_get_kernel, axis, n, src_shift),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)


# ------------------------------------------------------------ accumulate
def _accum_kernel(axis: str, n: int, shift: int,
                  x_ref, acc_ref, o_ref, slot, send_sem, recv_sem):
    """Slotted MPI_Accumulate: RDMA into my private slot at the target, then
    the *owner* reduces slot into its accumulator (element-wise atomicity by
    ownership, §2.4)."""
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)
    _neighbor_barrier(axis, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=slot,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(dst,), device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma.start()
    rdma.wait()           # flush: slot data is remotely complete
    _neighbor_barrier(axis, n)  # epoch close: all puts landed
    o_ref[...] = acc_ref[...] + slot[...]


def accumulate_shift_pallas(x: jax.Array, acc: jax.Array, shift: int, axis: str, n: int,
                            *, interpret: bool, collective_id: int = 0) -> jax.Array:
    return pl.pallas_call(
        functools.partial(_accum_kernel, axis, n, shift),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # only DMA'd
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # owner-read
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM(x.shape, x.dtype),   # private slot buffer
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x, acc)


# ------------------------------------------------- ring all-gather kernel
def _ring_ag_kernel(axis: str, n: int, x_ref, o_ref, buf, send_sem, recv_sem):
    """All-gather via n-1 one-sided ring puts, double-buffered.

    Each step forwards the chunk received last step to the right neighbor
    while the output row is already usable — the overlap-friendly schedule
    the fused ring matmul builds on.
    """
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    _neighbor_barrier(axis, n)

    # my own shard -> output row `me`, and into buffer slot 0
    o_ref[me] = x_ref[...]
    buf[0] = x_ref[...]

    def step(i, _):
        # per-step handshake: the receiver must have consumed slot (i+1)%2
        # from two steps ago before we overwrite it — FOMPI's post/start
        # matching applied at every epoch step.
        _neighbor_barrier(axis, n)
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=buf.at[slot], dst_ref=buf.at[nxt],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()
        src = jax.lax.rem(me - i - 1 + 2 * n, n)
        o_ref[src] = buf[nxt]
        return 0

    jax.lax.fori_loop(0, n - 1, step, 0)


def ring_all_gather_pallas(x: jax.Array, axis: str, n: int,
                           *, interpret: bool, collective_id: int = 1) -> jax.Array:
    """[local...] -> [n, local...] gathered in rank order."""
    return pl.pallas_call(
        functools.partial(_ring_ag_kernel, axis, n),
        out_shape=jax.ShapeDtypeStruct((n,) + x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + x.shape, x.dtype),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)
