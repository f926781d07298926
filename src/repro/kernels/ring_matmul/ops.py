"""jit'd wrapper: shard_map plumbing + backend dispatch."""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import interpret_mode

from .kernel import ring_matmul_pallas


def ring_matmul(x_t: jax.Array, w: jax.Array, mesh: Mesh, axis: str = "x",
                interpret: bool | None = None) -> jax.Array:
    """Y = x_t.T @ concat(w shards): x_t [K, m] replicated; w [K, N] sharded
    on dim 0 over `axis`.  Returns [m, N] replicated (identical per rank)."""
    n = mesh.shape[axis]
    fn = functools.partial(ring_matmul_pallas, axis=axis, n=n,
                           interpret=interpret_mode(interpret))
    return jax.jit(
        shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, None), P(axis, None)),
            out_specs=P(None, None),
            check_vma=False,
        )
    )(x_t, w)
