"""jit'd wrappers for the paged-gather kernel: shard_map plumbing + dispatch."""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.kernels.common import interpret_mode

from . import kernel


def paged_gather(pages: jax.Array, ids: jax.Array, shift: int, mesh: Mesh,
                 axis: str = "x", interpret: bool | None = None) -> jax.Array:
    """Global pages [p, n_pages, w], ids [p, k] int32 → [p, k, w]: each rank
    gathers rows `ids[r]` from rank (r+shift)'s pool as one fused block."""
    n = mesh.shape[axis]
    fn = functools.partial(kernel.paged_gather_pallas, shift=shift, axis=axis,
                           n=n, interpret=interpret_mode(interpret))
    return jax.jit(
        shard_map(
            lambda b, i: fn(b[0], i[0])[None],
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None)),
            out_specs=P(axis, None, None),
            check_vma=False,
        )
    )(pages, ids)
