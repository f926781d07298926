"""Pallas TPU kernels for notified access (DESIGN.md §6.4): the rmaq trio.

Three kernels compose put-with-notification out of the TPU's actual RDMA
primitives, mirroring `repro.rmaq.notify`'s XLA path:

  * ``notified_put``     — payload DMA + count-word DMA + doorbell to the
    ring neighbor: MPI_Put + MPI_Accumulate(counter) in one epoch.
  * ``notify_accumulate``— counter-only notification (MPI_Accumulate on an
    int window): the doorbell without payload, used for heartbeats/credits.
  * ``queue_push``       — ring-slot enqueue: fetch the target's (head,
    tail) counters with a get-DMA, admit up to free space, then per-message
    DMAs into the target ring at ``(tail + j) & mask``, count-word
    notification, receiver-side tail publish.  The MPSC queue's data plane
    with literal one-sided ops.

Notification: a remote ``semaphore_signal`` on a REGULAR semaphore is the
doorbell; the receiver's ``semaphore_wait`` is the notification.  The
count-word DMA rides the same epoch, so the receiver also holds the count.
The interpreter (``pltpu.InterpretParams``) runs the same signals.

Interpret-mode discharge also requires a *static* collective schedule (a
DMA under a rank-divergent conditional would desynchronize the lowered
all_gathers), so `queue_push` always issues its k row-DMAs and routes
rejected rows to a trash slot (row `capacity`) at the target — backpressure
without a divergent branch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import neighbor_barrier as _neighbor_barrier


def _doorbell(dst, notify_sem):
    """Remote doorbell: signal the target's notification semaphore, wait for
    our own — the literal write-with-notification handshake."""
    pltpu.semaphore_signal(notify_sem, inc=1,
                           device_id=(dst,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(notify_sem, 1)


# ----------------------------------------------------------- notified put
def _notified_put_kernel(axis, n, shift,
                         x_ref, cnt_ref, o_ref, ocnt_ref,
                         send_sem, recv_sem, csend, crecv, notify_sem):
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)
    _neighbor_barrier(axis, n)
    payload = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    note = pltpu.make_async_remote_copy(
        src_ref=cnt_ref, dst_ref=ocnt_ref,
        send_sem=csend, recv_sem=crecv,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    payload.start()          # MPI_Put (nonblocking)
    note.start()             # counter accumulate riding the same epoch
    payload.wait()
    note.wait()              # MPI_Win_flush: payload + count visible
    _doorbell(dst, notify_sem)


def notified_put_pallas(x: jax.Array, cnt: jax.Array, shift: int, axis: str,
                        n: int, *, interpret: bool,
                        collective_id: int = 3) -> tuple[jax.Array, jax.Array]:
    """Returns (payload delivered into us, notification count delivered)."""
    return pl.pallas_call(
        functools.partial(_notified_put_kernel, axis, n, shift),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(cnt.shape, cnt.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x, cnt)


# ------------------------------------------------------ notify accumulate
def _notify_accum_kernel(axis, n, shift,
                         cnt_ref, local_ref, o_ref,
                         csend, crecv, incoming, notify_sem):
    """Counter-only notification: accumulate my count into the target's
    notification counter (o = local + what arrived)."""
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)
    _neighbor_barrier(axis, n)
    note = pltpu.make_async_remote_copy(
        src_ref=cnt_ref, dst_ref=incoming,
        send_sem=csend, recv_sem=crecv,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    note.start()
    note.wait()
    _doorbell(dst, notify_sem)
    o_ref[...] = local_ref[...] + incoming[...]   # owner-side reduce (§2.4)


def notify_accumulate_pallas(cnt: jax.Array, local: jax.Array, shift: int,
                             axis: str, n: int, *, interpret: bool,
                             collective_id: int = 4) -> jax.Array:
    return pl.pallas_call(
        functools.partial(_notify_accum_kernel, axis, n, shift),
        out_shape=jax.ShapeDtypeStruct(local.shape, local.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.VMEM(cnt.shape, cnt.dtype),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(cnt, local)


# ------------------------------------------------------------- queue push
def _queue_push_kernel(axis, n, shift, capacity,
                       buf_ref, ctr_ref, msgs_ref,
                       o_buf, o_ctr, o_sent, o_notif,
                       tctr, my_cnt, in_cnt,
                       gsend, grecv, dsend, drecv, csend, crecv, notify_sem):
    """Ring-slot enqueue toward rank (me+shift): the queue's data plane.

    o_buf has `capacity`+1 rows; row `capacity` is the trash slot rejected
    rows are routed to (static DMA schedule, see module docstring).
    """
    me = jax.lax.axis_index(axis)
    dst = jax.lax.rem(me + shift + n, n)
    back = jax.lax.rem(me - shift + n, n)     # the rank that pushes into me
    k = msgs_ref.shape[0]
    mask = capacity - 1

    # everyone stages its ring + counters into the output refs first
    o_buf[: capacity] = buf_ref[...]
    o_ctr[...] = ctr_ref[...]
    _neighbor_barrier(axis, n)

    # ---- fetch the target's (head, tail): send mine to `back`, so my
    # scratch receives my *target's* counters (symmetric SPMD get)
    get_ctr = pltpu.make_async_remote_copy(
        src_ref=ctr_ref, dst_ref=tctr,
        send_sem=gsend, recv_sem=grecv,
        device_id=(back,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    get_ctr.start()
    get_ctr.wait()
    t_head = tctr[0]
    t_tail = tctr[1]
    free = capacity - (t_tail - t_head)
    accept = jnp.minimum(jnp.int32(k), free)   # backpressure at the origin

    # ---- per-message puts into the target ring (trash slot if rejected)
    def push_row(j, _):
        slot = jax.lax.select(j < accept,
                              jax.lax.rem(t_tail + j, jnp.int32(mask + 1)),
                              jnp.int32(capacity))
        row = pltpu.make_async_remote_copy(
            src_ref=msgs_ref.at[pl.ds(j, 1)],
            dst_ref=o_buf.at[pl.ds(slot, 1)],
            send_sem=dsend, recv_sem=drecv,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        row.start()
        row.wait()
        return 0

    jax.lax.fori_loop(0, k, push_row, 0)

    # ---- notification: my accept count flies to the target; the incoming
    # count (from `back`) is what I publish to my tail
    my_cnt[0] = accept
    note = pltpu.make_async_remote_copy(
        src_ref=my_cnt, dst_ref=in_cnt,
        send_sem=csend, recv_sem=crecv,
        device_id=(dst,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    note.start()
    note.wait()
    _doorbell(dst, notify_sem)
    _neighbor_barrier(axis, n)      # epoch close: all puts landed

    o_ctr[1] = ctr_ref[1] + in_cnt[0]          # publish tail (owner-side)
    o_sent[0] = accept
    o_notif[0] = in_cnt[0]


def queue_push_pallas(buf: jax.Array, ctr: jax.Array, msgs: jax.Array,
                      shift: int, axis: str, n: int, capacity: int,
                      *, interpret: bool, collective_id: int = 5):
    """buf [capacity, w], ctr [2] int32 (head, tail), msgs [k, w].

    Returns (buf' [capacity+1, w], ctr', n_sent [1], n_notif [1]); callers
    slice off the trash row.
    """
    w = buf.shape[1]
    return pl.pallas_call(
        functools.partial(_queue_push_kernel, axis, n, shift, capacity),
        out_shape=(
            jax.ShapeDtypeStruct((capacity + 1, w), buf.dtype),
            jax.ShapeDtypeStruct(ctr.shape, ctr.dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        scratch_shapes=[
            pltpu.VMEM((2,), jnp.int32),       # target's counters
            pltpu.VMEM((1,), jnp.int32),       # my accept count
            pltpu.VMEM((1,), jnp.int32),       # incoming accept count
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(buf, ctr, msgs)
