"""Shared Pallas kernel helpers (one copy of the cross-device handshake
and of the interpret-vs-compiled dispatch probe)."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

_INTERPRET: bool | None = None


def interpret_mode(override: bool | None = None) -> bool:
    """Should kernels run under the Pallas interpreter?

    One cached env probe for every kernel package (previously each ops.py
    carried its own `_interpret()` copy).  The probe — "is the default
    backend a TPU?" — is stable for the life of the process, so it is
    evaluated once.  `override` short-circuits the probe entirely: tests
    pass `True`/`False` to pin the dispatch mode regardless of backend.
    """
    if override is not None:
        return override
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = jax.default_backend() != "tpu"
    return _INTERPRET


def neighbor_barrier(axis: str, n: int) -> None:
    """Barrier with both ring neighbors (paper: post/start matching).

    Prevents a device from racing ahead and tearing down buffers while a
    neighbor's DMA is inflight — the same reason FOMPI's start blocks on
    matching posts.
    """
    me = jax.lax.axis_index(axis)
    left = jax.lax.rem(me - 1 + n, n)
    right = jax.lax.rem(me + 1, n)
    sem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(sem, device_id=(left,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_signal(sem, device_id=(right,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(sem, 2)
