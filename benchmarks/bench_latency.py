"""Paper Fig. 4: put/get latency vs message size; one-sided vs two-sided.

Measured: CPU wall time of the XLA lowering (8 forced-host devices).
Derived: the §3 performance-model prediction for TPU v5e (what the same
schedule costs on the target), plus the paper's own Cray numbers shape:
P_put = 0.16ns*s + 1us.
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
    for log2s in (3, 8, 13, 17, 20):
        size = 2 ** log2s
        elems = max(size // 4, 1)
        x = jnp.zeros((n * 1, elems), jnp.float32)

        put = jax.jit(shard_map(functools.partial(rma.put_shift, shift=1, axis="x"),
                                mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        us = time_fn(put, x)
        emit(f"put_one_sided_{size}B", us, f"tpu_model_us={DEFAULT_MODEL.p_put(size)*1e6:.2f}")

        get = jax.jit(shard_map(functools.partial(rma.get_shift, shift=1, axis="x"),
                                mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        us = time_fn(get, x)
        emit(f"get_one_sided_{size}B", us, f"tpu_model_us={DEFAULT_MODEL.p_get(size)*1e6:.2f}")

        # two-sided baseline: payload + ack + matching barrier (message passing)
        def two_sided(v):
            y = rma.put_shift(v, 1, "x")
            ack = rma.put_shift(jnp.zeros((1, 1), jnp.float32), -1, "x")
            y = jax.lax.optimization_barrier((y, ack))[0]
            return jax.lax.psum(y * 0, "x") + y  # matching/sync side-effect

        ts = jax.jit(shard_map(two_sided, mesh=mesh, in_specs=spec, out_specs=spec,
                               check_vma=False))
        us2 = time_fn(ts, x)
        emit(f"put_two_sided_{size}B", us2, f"one_sided_speedup={us2/max(us,1e-9):.2f}x")


if __name__ == "__main__":
    main()
