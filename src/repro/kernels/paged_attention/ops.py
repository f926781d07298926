"""jit'd wrappers for fused paged attention: dispatch + shard_map plumbing."""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.kernels.common import interpret_mode

from . import kernel


@functools.partial(jax.jit, static_argnames=("scale", "causal", "interpret"))
def paged_attention(q: jax.Array, kv_pages: jax.Array, ids: jax.Array,
                    scale: float | None = None, causal: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """Batched pool-local fused paged attention.

    q [m, Sq, hd], kv_pages [n_pages, pt, 2, hd], ids [m, k] int32 →
    [m, Sq, hd].  Row i attends over the tokens of pool pages ids[i];
    negative ids are masked out of the softmax.  No packed KV block is
    ever materialized — the page table drives the kernel's DMAs directly.
    """
    return kernel.paged_attention_pallas(
        q, kv_pages, ids, scale=scale, causal=causal,
        interpret=interpret_mode(interpret))


def paged_attention_shift(q: jax.Array, kv_pages: jax.Array,
                          ids: jax.Array, shift: int, mesh: Mesh,
                          axis: str = "x", scale: float | None = None,
                          causal: bool = False,
                          interpret: bool | None = None) -> jax.Array:
    """Cross-rank fused paged attention over the ring.

    Global q [p, Sq, hd], kv_pages [p, n_pages, pt, 2, hd], ids [p, k]
    int32 → [p, Sq, hd]: rank r attends over pages ids[r] of rank
    (r+shift)'s pool, streamed page-at-a-time — never gathered into a
    contiguous block.
    """
    n = mesh.shape[axis]
    fn = functools.partial(kernel.paged_attention_shift_pallas,
                           shift=shift, axis=axis, n=n, scale=scale,
                           causal=causal,
                           interpret=interpret_mode(interpret))
    return jax.jit(
        shard_map(
            lambda qq, b, i: fn(qq[0], b[0], i[0])[None],
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None, None, None),
                      P(axis, None)),
            out_specs=P(axis, None, None),
            check_vma=False,
        )
    )(q, kv_pages, ids)
