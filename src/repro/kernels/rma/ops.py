"""jit'd wrappers: shard_map plumbing + interpret/compiled dispatch."""

from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import interpret_mode

from . import kernel


def _sm(mesh, fn, in_specs, out_specs):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )


def put_shift(x: jax.Array, shift: int, mesh: Mesh, axis: str = "x",
              interpret: bool | None = None) -> jax.Array:
    """Global [n*rows, ...] array; each shard put to rank (r+shift)%n."""
    n = mesh.shape[axis]
    fn = functools.partial(kernel.put_shift_pallas, shift=shift, axis=axis, n=n,
                           interpret=interpret_mode(interpret))
    spec = P(axis, *([None] * (x.ndim - 1)))
    return _sm(mesh, fn, spec, spec)(x)


def get_shift(x: jax.Array, src_shift: int, mesh: Mesh, axis: str = "x",
              interpret: bool | None = None) -> jax.Array:
    n = mesh.shape[axis]
    fn = functools.partial(kernel.get_shift_pallas, src_shift=src_shift, axis=axis, n=n,
                           interpret=interpret_mode(interpret))
    spec = P(axis, *([None] * (x.ndim - 1)))
    return _sm(mesh, fn, spec, spec)(x)


def accumulate_shift(x: jax.Array, acc: jax.Array, shift: int, mesh: Mesh,
                     axis: str = "x", interpret: bool | None = None) -> jax.Array:
    n = mesh.shape[axis]
    fn = functools.partial(kernel.accumulate_shift_pallas, shift=shift, axis=axis, n=n,
                           interpret=interpret_mode(interpret))
    spec = P(axis, *([None] * (x.ndim - 1)))
    return _sm(mesh, fn, (spec, spec), spec)(x, acc)


def ring_all_gather(x: jax.Array, mesh: Mesh, axis: str = "x",
                    interpret: bool | None = None) -> jax.Array:
    """Input sharded on dim 0 ([n*rows, ...]); output [n, rows, ...] is the
    full gather, identical on (replicated across) every rank."""
    n = mesh.shape[axis]
    fn = functools.partial(kernel.ring_all_gather_pallas, axis=axis, n=n,
                           interpret=interpret_mode(interpret))
    in_spec = P(axis, *([None] * (x.ndim - 1)))
    out_spec = P(*([None] * (x.ndim + 1)))
    return _sm(mesh, fn, in_spec, out_spec)(x)
