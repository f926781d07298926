"""Fused all-gather matmul with RDMA/compute overlap — the paper's §3.1.1
overlap motif as a TPU kernel (collective matmul).

Problem: Y = X @ W with W row-sharded over the ring (FSDP/TP contraction
layout): each rank holds X [m, K] and W_me [K/n, N]; Y = Σ_j X[:, jK/n:(j+1)K/n] @ W_j.

Schedule per step i (double-buffered, n-1 RDMA hops):
    1. start RDMA: forward the currently-held W shard to the right neighbor
    2. compute the partial product with that same shard   <- overlaps the DMA
    3. wait on the DMA; next iteration uses the shard that just arrived

Instead of "all-gather W, then matmul" (serialized: T_comm + T_comp), the
wall-clock is max(T_comm, T_comp) + one partial — the exact benefit FOMPI
demonstrates for the FFT (Fig. 7c).  The XLA-path equivalent (unfused) is
`core.collectives.ring_all_gather` + jnp.dot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import neighbor_barrier as _neighbor_barrier


def _ring_mm_kernel(axis: str, n: int, x_ref, w_ref, o_ref, buf, send_sem, recv_sem):
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    ks = w_ref.shape[0]                       # K/n rows per shard

    _neighbor_barrier(axis, n)
    buf[0] = w_ref[...]
    o_ref[...] = jnp.zeros_like(o_ref)

    def step(i, _):
        _neighbor_barrier(axis, n)  # slot-reuse handshake
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=buf.at[slot], dst_ref=buf.at[nxt],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH,
        )

        @pl.when(i < n - 1)
        def _start():
            rdma.start()                      # MPI_Put of the W shard

        # ---- overlapped compute: partial product with the held shard ----
        j = jax.lax.rem(me - i + 2 * n, n)    # which shard buf[slot] holds
        x_blk = x_ref[pl.dslice(j * ks, ks), :]          # [K/n, m] (x pre-T)
        o_ref[...] += jax.lax.dot_general(
            x_blk, buf[slot],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

        @pl.when(i < n - 1)
        def _wait():
            rdma.wait()                       # MPI_Win_flush

        return 0

    jax.lax.fori_loop(0, n, step, 0)


def ring_matmul_pallas(
    x_t: jax.Array,      # [K, m]  (transposed activations, local full-K)
    w: jax.Array,        # [K/n, N] local W shard
    axis: str,
    n: int,
    *, interpret: bool,
    collective_id: int = 2,
) -> jax.Array:
    """Returns Y^T? No — returns Y [m, N] = x^T... see dims: out[m, N]."""
    K, m = x_t.shape
    ks, N = w.shape
    assert ks * n == K, (K, ks, n)
    return pl.pallas_call(
        functools.partial(_ring_mm_kernel, axis, n),
        out_shape=jax.ShapeDtypeStruct((m, N), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + w.shape, w.dtype),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x_t, w)
