"""jit'd wrappers for the rmaq kernels: shard_map plumbing + dispatch."""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.kernels.common import interpret_mode

from . import kernel


def _sm(mesh, fn, in_specs, out_specs):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )


def notified_put(x: jax.Array, cnt: jax.Array, shift: int, mesh: Mesh,
                 axis: str = "x",
                 interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Global x [p*rows, ...], cnt [p] int32: each shard + its count put to
    rank (r+shift)%p with notification.  Returns (delivered, counts)."""
    n = mesh.shape[axis]
    fn = functools.partial(kernel.notified_put_pallas, shift=shift, axis=axis,
                           n=n, interpret=interpret_mode(interpret))
    xs = P(axis, *([None] * (x.ndim - 1)))
    return _sm(mesh, fn, (xs, P(axis)), (xs, P(axis)))(x, cnt)


def notify_accumulate(cnt: jax.Array, local: jax.Array, shift: int, mesh: Mesh,
                      axis: str = "x",
                      interpret: bool | None = None) -> jax.Array:
    """Counter-only notification: local[r] + cnt[(r-shift)%p]."""
    n = mesh.shape[axis]
    fn = functools.partial(kernel.notify_accumulate_pallas, shift=shift,
                           axis=axis, n=n, interpret=interpret_mode(interpret))
    return _sm(mesh, fn, (P(axis), P(axis)), P(axis))(cnt, local)


def queue_push(buf: jax.Array, ctr: jax.Array, msgs: jax.Array, shift: int,
               mesh: Mesh, axis: str = "x", capacity: int | None = None,
               interpret: bool | None = None):
    """Ring-slot enqueue toward rank (r+shift)%p.

    buf [p, capacity, w], ctr [p, 2] int32, msgs [p, k, w] (k msgs per rank).
    Returns (buf', ctr', n_sent [p], n_notif [p]).
    """
    n = mesh.shape[axis]
    cap = capacity if capacity is not None else buf.shape[1]
    imode = interpret_mode(interpret)

    def body(b, c, m):
        ob, oc, sent, notif = kernel.queue_push_pallas(
            b[0], c[0], m[0], shift=shift, axis=axis, n=n, capacity=cap,
            interpret=imode)
        return ob[None, :cap], oc[None], sent, notif  # drop the trash row

    out = _sm(
        mesh, body,
        (P(axis, None, None), P(axis, None), P(axis, None, None)),
        (P(axis, None, None), P(axis, None), P(axis), P(axis)),
    )(buf, ctr, msgs)
    return out
