"""Paper Fig. 6a: accumulate (MPI_SUM), non-accelerated MPI_MIN, and CAS.

Slotted accumulate (hardware path) vs fetch-modify-writeback fallback
(§2.4's lock+get+op+put) — the paper's two accumulate regimes.
"""
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core import rma
from repro.core.perfmodel import DEFAULT_MODEL


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    spec = P("x", None)
    for size in (8, 1024, 65536):
        elems = max(size // 4, 1)
        x = jnp.ones((n, elems), jnp.float32)
        acc = jnp.zeros((n, elems), jnp.float32)

        f = jax.jit(shard_map(
            functools.partial(rma.accumulate_shift, shift=1, axis="x", op=jnp.add),
            mesh=mesh, in_specs=(spec, spec), out_specs=spec, check_vma=False))
        us = time_fn(f, x, acc)
        emit(f"accumulate_sum_{size}B", us,
             f"tpu_model_us={DEFAULT_MODEL.p_accumulate(size)*1e6:.2f}")

        fmin = jax.jit(shard_map(
            functools.partial(rma.accumulate_shift, shift=1, axis="x", op=jnp.minimum),
            mesh=mesh, in_specs=(spec, spec), out_specs=spec, check_vma=False))
        emit(f"accumulate_min_{size}B", time_fn(fmin, x, acc),
             "fallback=fetch_modify_writeback" if
             DEFAULT_MODEL.select_accumulate_mode(size, 1) != "slotted" else "mode=slotted")

    # 8-byte CAS emulation: conditional store via where
    x8 = jnp.zeros((n, 2), jnp.float32)
    def cas(v):
        cur = rma.get_shift(v, 1, "x")
        new = jnp.where(cur == 0.0, 1.0, cur)
        return rma.put_shift(new, -1, "x")
    f = jax.jit(shard_map(cas, mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
                          check_vma=False))
    emit("cas_8B", time_fn(f, x8), "paper_cray_us=2.4")


if __name__ == "__main__":
    main()
