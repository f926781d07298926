"""Pure-jnp oracles for the RMA kernels (lax collectives, no Pallas)."""

from __future__ import annotations

import jax
from jax import lax



def put_shift_ref(x: jax.Array, shift: int, axis: str) -> jax.Array:
    n = jax.lax.axis_size(axis)
    return lax.ppermute(x, axis, [(i, (i + shift) % n) for i in range(n)])


def get_shift_ref(x: jax.Array, src_shift: int, axis: str) -> jax.Array:
    return put_shift_ref(x, -src_shift, axis)


def accumulate_shift_ref(x: jax.Array, acc: jax.Array, shift: int, axis: str) -> jax.Array:
    return acc + put_shift_ref(x, shift, axis)


def ring_all_gather_ref(x: jax.Array, axis: str) -> jax.Array:
    return lax.all_gather(x, axis)
